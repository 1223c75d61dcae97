"""End-to-end driver: train a ~100M-parameter LM.

    PYTHONPATH=src python examples/train_100m.py --steps 300

Full substrate in play: deterministic data pipeline, AdamW + cosine,
microbatching, remat, async checkpointing, straggler watchdog. On a
laptop CPU use --steps 20; on real accelerators run the full few
hundred steps.
"""
import argparse

from repro.configs.base import ModelConfig
from repro.data import DataConfig, TokenPipeline
from repro.models.registry import Model
from repro.train import Trainer, TrainConfig

CONFIG_100M = ModelConfig(
    name="repro-100m", family="dense", n_layers=12, d_model=768,
    n_heads=12, n_kv_heads=12, d_ff=3072, vocab=32000,
    tie_embeddings=True, dtype="float32",
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_100m_ckpt")
    args = ap.parse_args()

    total, active = CONFIG_100M.param_count()
    print(f"model: {CONFIG_100M.name}  params={total/1e6:.1f}M")
    model = Model.from_config(CONFIG_100M)
    pipe = TokenPipeline(DataConfig(vocab=CONFIG_100M.vocab,
                                    seq_len=args.seq,
                                    global_batch=args.batch))
    tcfg = TrainConfig(steps=args.steps, n_micro=2, remat="dots",
                       ckpt_every=100, log_every=10)
    trainer = Trainer(model, pipe, tcfg, ckpt_dir=args.ckpt_dir)
    hist = trainer.fit()
    print(f"loss: {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f} "
          f"over {len(hist)} steps")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
