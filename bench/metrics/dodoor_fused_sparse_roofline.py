"""The sparse two-choice kernel's share of its roofline, in %: the
compulsory HBM bytes of one call (bench's bytes model, the server table
read once per call) at the chip's peak bandwidth, over the measured time
of a call.  Bound by bytes alone: the kernel's element work on the vector
unit has no published peak."""
from harness.roofline import roofline_pct, sparse_kernel_bytes


def read(ctx):
    calls = ctx.view.kernel_calls()
    if not calls or ctx.peaks is None:
        return None
    per_call_s = sum(e.dur for e in calls) / len(calls) / 1e9
    moved = sparse_kernel_bytes(int(ctx.policy["b"]), ctx.fleet.n,
                                node_types=len(ctx.fleet.type_names))
    return roofline_pct(moved, per_call_s, ctx.peaks["hbm_bytes_per_s"])
