"""Share of its roofline that the gemm programs reach on the device.

The least time of every gemm the device ran in the window, from
``bench/cost.py`` (the larger of its operations over the bf16 peak and its
least bytes over the HBM bandwidth), over the device time of the
programs named ``jit_*gemm*`` in the trace.  The runtime sends a call to
the host when its size is under the offload threshold, so of each
routine's calls the smallest, as many as its ``on_host`` count, are left
out.
"""
import numpy as np

from bench import cost
from bench import trace as tracing

_DTYPE = {"s": np.float32, "d": np.float64, "c": np.complex64,
          "z": np.complex128}


def read(w):
    if w.trace is None or not w.peaks:
        return None
    device_s = tracing.program_seconds(w.trace.programs, r"^jit_.*gemm")
    if not device_s:
        return None
    least = 0.0
    for routine, row in w.counters.items():
        if routine[1:] != "gemm":
            continue
        calls = sorted((c for c in w.calls if c.routine == routine),
                       key=lambda c: c.m * c.n * c.k * c.batch
                       )[int(row["on_host"]):]
        dt = _DTYPE[routine[0]]
        for c in calls:
            has_c = any(op[0] == "C" for op in c.operands)
            least += cost.roofline_s(
                cost.gemm_flops(c.m, c.n, c.k, dt, c.batch),
                cost.gemm_bytes(c.m, c.n, c.k, dt, has_c, c.batch),
                w.peaks)
    return 100.0 * least / device_s if least else None
