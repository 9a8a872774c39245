"""Per block, the host's share of a ``step()`` call in ms: the span the
benchmark records around the call minus the device busy time inside it
(uploads, dispatch, readbacks, the snapshot publish)."""
from harness.trace import covered


def read(ctx):
    spans = ctx.view.spans("bench.step")
    if not spans or not ctx.view.busy:
        return None
    gaps = [s.dur - covered(ctx.view.busy, s.start, s.end) for s in spans]
    return sum(gaps) / len(gaps) / 1e6
