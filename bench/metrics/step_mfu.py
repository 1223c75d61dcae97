"""The whole step's share of the chip's bf16 peak: the step's algorithmic
operations (``bench/cost.py``) times the steps over the window's seconds
times the peak.  It bounds a gain whatever kernels the step runs."""


def read(w):
    if not w.peaks:
        return None
    return (100.0 * w.flops_per_step * w.steps
            / (w.seconds * w.peaks["bf16_flops_per_s"]))
