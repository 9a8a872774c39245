"""Mean wait, in ms, from a task's due time to the dispatch of the
``step()`` that placed it (the ring and the block rule), over the tasks due
in the traced sub-window; the benchmark's own clock."""


def read(ctx):
    w = ctx.window
    lo = w.t1 - min(3.0, w.t1 - w.t0)
    due = w.due[:w.placed]
    inside = (due >= lo) & (due < w.t1)
    if not inside.any():
        return None
    return float(((w.dispatch - due)[inside]).mean() * 1e3)
