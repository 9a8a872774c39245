"""Per block, the device time in ms of the step program's ops under its
``commit`` scope (the commit rounds): the busy union of the ops that the
compiled step's ``op_name`` metadata assigns to ``commit``.  The map comes
from compiling the cell's step once more after the window, built with the
configuration's ``service`` keywords as the window's was; a program
without the scope gives no reading."""
from harness import phases


def read(ctx):
    if not ctx.view.steps():
        return None
    smap = phases.step_scopes(ctx.fleet, ctx.policy, ctx.service)
    return phases.scope_ms(ctx.view, smap, "commit")
