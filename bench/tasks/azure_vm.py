"""The Azure VM mix of the paper's §6.2: virtual machines as tasks.

A copy of the program's generator arithmetic (``repro.workloads.azure``),
kept with the benchmark so that the yardstick does not move when the
program does, and drawn from the harness's ``np.random.Generator``.  The
configuration's ``tasks`` block gives every parameter:

- lifetime: a ``short_share`` of LogNormal(ln ``short_median_s``,
  ``short_sigma``) seconds, the rest Uniform[``long_s``], clipped to
  ``clip_s`` (the paper keeps VMs shorter than 10 minutes; mean 4.13 min);
- cores drawn from ``cores`` with ``core_weights``, and ``mb_per_core`` MB
  of memory a core (7 GB a vCPU of a Standard_E96as_v6 host);
- the same cores, memory and duration on every node type, and the actual
  duration equal to the profiled one (the VM runs for its lifetime).
"""
from __future__ import annotations

import math

import numpy as np

from harness.tasks import Tasks


def _phi(x: float) -> float:
    return 0.5 * (1.0 + math.erf(x / math.sqrt(2.0)))


def _mean_clipped_lognormal(mu: float, sigma: float, lo: float,
                            hi: float) -> float:
    """E[clip(X, lo, hi)] for ln X ~ Normal(mu, sigma)."""
    a, b = math.log(lo), math.log(hi)
    inside = math.exp(mu + sigma ** 2 / 2) * (
        _phi((b - mu - sigma ** 2) / sigma)
        - _phi((a - mu - sigma ** 2) / sigma))
    return (lo * _phi((a - mu) / sigma) + inside
            + hi * (1.0 - _phi((b - mu) / sigma)))


def _mean_clipped_uniform(u0: float, u1: float, lo: float,
                          hi: float) -> float:
    """E[clip(U, lo, hi)] for U ~ Uniform[u0, u1]."""
    below = max(0.0, min(u1, lo) - u0) * lo
    m0, m1 = max(u0, lo), min(u1, hi)
    middle = max(0.0, m1 - m0) * (m0 + m1) / 2
    above = max(0.0, u1 - max(u0, hi)) * hi
    return (below + middle + above) / (u1 - u0)


def mean_lifetime_s(params: dict) -> float:
    """The mean clipped lifetime of a VM, in seconds, from the
    distribution."""
    lo, hi = params["clip_s"]
    share = float(params["short_share"])
    short = _mean_clipped_lognormal(math.log(params["short_median_s"]),
                                    float(params["short_sigma"]), lo, hi)
    long_ = _mean_clipped_uniform(*params["long_s"], lo, hi)
    return share * short + (1.0 - share) * long_


def mean_cores(params: dict) -> float:
    w = np.asarray(params["core_weights"], np.float64)
    return float(np.asarray(params["cores"], np.float64) @ (w / w.sum()))


def core_seconds_per_task(fleet, params: dict) -> float:
    """Mean cores times mean lifetime: a VM's demand does not depend on the
    node type, nor its lifetime on its size."""
    return mean_cores(params) * mean_lifetime_s(params)


def draw(rng: np.random.Generator, fleet, m: int, params: dict,
         submit_ms: np.ndarray) -> Tasks:
    """``m`` VMs with the given virtual arrivals."""
    lo, hi = params["clip_s"]
    short = np.exp(rng.normal(math.log(params["short_median_s"]),
                              float(params["short_sigma"]), size=m))
    long_ = rng.uniform(*params["long_s"], size=m)
    is_short = rng.random(m) < float(params["short_share"])
    life_ms = (np.clip(np.where(is_short, short, long_), lo, hi)
               * 1000.0).astype(np.float32)
    w = np.asarray(params["core_weights"], np.float64)
    cores = np.asarray(params["cores"], np.float32)[
        rng.choice(len(w), size=m, p=w / w.sum())]
    res = np.stack([cores, cores * np.float32(params["mb_per_core"])], 1)
    types = len(fleet.type_names)
    d_est = np.repeat(life_ms[:, None], types, axis=1)
    return Tasks(r_submit=res, r_exec=np.repeat(res[:, None], types, axis=1),
                 d_est=d_est, d_act=d_est,
                 submit_ms=np.asarray(submit_ms, np.float32))
