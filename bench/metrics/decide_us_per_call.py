"""Host microseconds of the dispatch decision per intercepted level-3
call: the runtime's canonicalize (call-site fingerprint included) and
decide stages, which come before the call's ``seconds``.

Summed from the window's call records (``BlasCall.decide_seconds``,
the same seconds ``RoutineStats.decide_seconds`` adds up) over every
routine but the host-side ``*getf2`` panels.  None on a program whose
records carry no stage seconds.
"""


def read(w):
    calls = [c for c in w.calls if not c.routine.endswith("getf2")]
    if not calls or not hasattr(calls[0], "decide_seconds"):
        return None
    return 1e6 * sum(c.decide_seconds for c in calls) / len(calls)
