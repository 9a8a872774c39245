"""Per step, how far the mini-clusters' chips part, in %: the slowest
chip's execution of the served step's program minus the fastest's, over
their mean, averaged over the steps every chip ran in the traced window.
Each chip's executions are read from its own device plane, and one step's
are matched by time (each chip's execution nearest the first chip's, and
overlapping it).  The step waits for its slowest mini-cluster, so this is
the share the split costs; a cell on one chip gives no reading."""
from harness.trace import STEP_PROGRAM


def read(ctx):
    chips = [sorted((e for e in programs if STEP_PROGRAM in e.name),
                    key=lambda e: e.start)
             for _, programs in ctx.view.devices]
    if len(chips) < 2 or not all(chips):
        return None
    skews = []
    for first in chips[0]:
        step = [first]
        for other in chips[1:]:
            near = min(other, key=lambda e: abs(e.start - first.start))
            if near.start >= first.end or first.start >= near.end:
                break
            step.append(near)
        else:
            durs = [e.dur for e in step]
            skews.append((max(durs) - min(durs)) / (sum(durs) / len(durs)))
    return 100.0 * sum(skews) / len(skews) if skews else None
