"""Host microseconds inside the runtime per intercepted level-3 call.

The window's difference of ``RoutineStats.seconds`` over its difference of
calls, over every routine but the host-side ``*getf2`` panels.  In the
runtime's default asynchronous mode the seconds are placement and
submission, not device time.
"""


def read(w):
    rows = [c for r, c in w.counters.items() if not r.endswith("getf2")]
    calls = sum(c["calls"] for c in rows)
    if not calls:
        return None
    return 1e6 * sum(c["seconds"] for c in rows) / calls
