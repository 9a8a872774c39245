"""The task streams each loop's ``prepare`` draws, for every committed
configuration under every committed traffic mix, are pinned bit for bit:
the digests below were computed before task mixes were found by name, so a
change to how a mix is found or bound must leave every stream as it was."""
import hashlib

import numpy as np
import pytest

from conftest import BENCH
from harness import traffic
from helpers import config, task_mix

SECONDS = 20.0   # the benchmark's run_seconds

PINNED = {
    ("cell10k-fb", "open", 2**31 + 101):
        "c4f340ec6ab8a5bb0aa991bae0ff8b6e2611b7665ff7832e2e4fb9d3ec07eb6d",
    ("cell10k-fb", "open", 5):
        "3191e1551c092cb2b6af26755d29361fb293a709b54caba721d4534539d23f56",
    ("cell10k-fb", "drain", 2**31 + 101):
        "19cf9b8cc260f171ed13e9333c3b2b4d11f460abeb32f05607528e643100de8f",
    ("cell10k-fb", "drain", 5):
        "afa64f7180449ae534a2d6f6f6efa2d485986e16d9003b9604525509600f8752",
    ("testbed-fb", "open", 2**31 + 101):
        "8c741cd9b2f7b47a0c48599e1de566914b4a8731fb8e4aebcf247dffc81d0bb7",
    ("testbed-fb", "open", 5):
        "d8bf3a0edd7a2d885c84906ea135a470eeb64a70e48bfd3634396061b8d3b700",
    ("testbed-fb", "drain", 2**31 + 101):
        "6b36c448d38d107c64ce72f840a6c3d0dec390bf08044b8d7923650cdc75d0e5",
    ("testbed-fb", "drain", 5):
        "6cab1ffa632a72de7125e0217b602ddee5292f7cbcc593fa2fba89863e2202df",
}


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("cfg,mix,seed", sorted(PINNED))
def test_prepared_stream_is_pinned(cfg, mix, seed):
    """The open loop's whole stream and due times; the backlog's prefill
    and one chunk drawn past it, as a window that outruns it would."""
    m = traffic.load(BENCH, mix)
    tasks = task_mix(cfg)
    plan = m.loop.prepare(np.random.default_rng(seed), m, tasks.fleet,
                          tasks.draw, SECONDS, int(config(cfg)["policy"]["b"]),
                          traffic.rate_per_s(m, tasks))
    if mix == "open":
        arrays = list(plan.tasks) + [plan.due_s]
    else:
        plan.stream.take(int(m["prefill_per_s"] * SECONDS) + traffic.CHUNK)
        arrays = list(plan.stream.consumed())
    assert _digest(arrays) == PINNED[cfg, mix, seed]
