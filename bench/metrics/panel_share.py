"""Share of the window in which the solver tier's getf2 panels ran on
the host backend: the executions the trace attributes to ``jit__getf2``.
"""


def read(w):
    if w.trace is None:
        return None
    panel_s = w.trace.host_programs.get("jit__getf2")
    if not panel_s:
        return None
    return 100.0 * panel_s / w.trace.window_s
