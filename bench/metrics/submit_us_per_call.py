"""Host microseconds per intercepted level-3 call from the plan to the
submitted result, without operand and output placement: the call's
``seconds`` less its ``place_seconds``.  Unlike
``dispatch_us_per_call`` it holds no placement, so it reads the same
on a cell whose calls each move a fresh block.

Summed from the window's call records (``BlasCall.seconds`` and
``place_seconds``, the same seconds ``RoutineStats`` adds up) over
every routine but the host-side ``*getf2`` panels.  None on a program
whose records carry no stage seconds.
"""


def read(w):
    calls = [c for c in w.calls if not c.routine.endswith("getf2")]
    if not calls or not hasattr(calls[0], "place_seconds"):
        return None
    return 1e6 * sum(c.seconds - c.place_seconds for c in calls) / len(calls)
