"""The sparse two-choice kernel's share of its roofline on a mini-cluster,
in %: each call places one mini-cluster's b/k decisions over its n/k
servers, k being the configuration's ``service.mini_clusters``; the
compulsory HBM bytes of such a call (bench's bytes model) at the chip's
peak bandwidth, over the measured time of a call, every chip's calls
counted.  Bound by bytes alone, as ``dodoor_fused_sparse_roofline`` is."""
from harness.roofline import roofline_pct, sparse_kernel_bytes


def read(ctx):
    calls = ctx.view.kernel_calls()
    if not calls or ctx.peaks is None:
        return None
    k = int(ctx.service.get("mini_clusters", 1))
    per_call_s = sum(e.dur for e in calls) / len(calls) / 1e9
    moved = sparse_kernel_bytes(int(ctx.policy["b"]) // k, ctx.fleet.n // k,
                                node_types=len(ctx.fleet.type_names))
    return roofline_pct(moved, per_call_s, ctx.peaks["hbm_bytes_per_s"])
