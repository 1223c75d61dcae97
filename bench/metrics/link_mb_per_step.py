"""Megabytes the runtime moved between the host and the device tier per
application step: the window's difference of ``bytes_in + bytes_out`` over
every routine, over its steps.  The LU's panel round trips
(``core/lapack._pivot_panel`` and ``_store``) go around the BLAS counters
and are not in it.
"""


def read(w):
    moved = sum(c["bytes_in"] + c["bytes_out"] for c in w.counters.values())
    return moved / w.steps / 1e6
