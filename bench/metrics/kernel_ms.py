"""Per block, the device time in ms of the sparse two-choice kernel
(candidate sampling, scoring and selection)."""


def read(ctx):
    steps = ctx.view.steps()
    calls = ctx.view.kernel_calls()
    if not steps or not calls:
        return None
    return sum(e.dur for e in calls) / len(steps) / 1e6
