"""LM serving with Device-First-Use cache placement (DESIGN.md §4).

    PYTHONPATH=src python examples/serve_offload.py

The paper's policies applied to the decode cache of a small LM: DFU
migrates the cache once at prefill; Mem-Copy round-trips it per token.
"""
import jax
import jax.numpy as jnp

from repro.models import get_config
from repro.models.registry import Model
from repro.train import Server, ServeConfig


def main():
    cfg = get_config("mamba2_1_3b").reduced()
    model = Model.from_config(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (4, 16),
                                0, cfg.vocab)
    outs = {}
    for policy in ("dfu", "memcopy", "pinned"):
        srv = Server(model, params,
                     ServeConfig(max_len=96, offload_policy=policy,
                                 cache_dtype=jnp.float32))
        outs[policy] = srv.generate(prompt, 32)
        s = srv.stats
        print(f"{policy:8s} decode={s.decode_s:6.2f}s "
              f"h->d={s.bytes_host_to_dev/1e6:8.2f}MB "
              f"d->h={s.bytes_dev_to_host/1e6:8.2f}MB "
              f"migrations={s.migrations} reuses={s.cache_reuses}")
    import numpy as np
    np.testing.assert_array_equal(outs["dfu"], outs["memcopy"])
    np.testing.assert_array_equal(outs["dfu"], outs["pinned"])
    print("identical generations under all policies: OK")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
