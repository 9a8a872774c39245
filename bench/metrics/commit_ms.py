"""Per block, the device time in ms of the served step's program outside
the kernel: commit rounds, cache push, flush and the message ledger (the
step program's execution time less the kernel's)."""


def read(ctx):
    steps = ctx.view.steps()
    if not steps:
        return None
    kernel = sum(e.dur for e in ctx.view.kernel_calls())
    return (sum(e.dur for e in steps) - kernel) / len(steps) / 1e6
