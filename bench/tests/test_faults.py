"""A run with the timed path broken underneath must come out not correct:
once for each fault a served cell can have.  (The cells run on one chip,
so there is no exchange between chips to leave out.)"""
import shutil

import jax
import jax.numpy as jnp
import pytest

import run
from conftest import BENCH
from helpers import tiny_spec, write_tiny
from repro.serve import service as served


def _unchanged_state(real):
    def step(carry, blk, *a, **kw):
        kept = jax.tree.map(lambda x: None if x is None else jnp.copy(x),
                            carry)
        _, out = real(carry, blk, *a, **kw)
        return kept, out
    return step


def _half_batch(real):
    def step(carry, blk, *a, **kw):
        valid = blk[7]
        half = valid & (jnp.arange(valid.shape[0]) < valid.shape[0] // 2)
        return real(carry, blk[:7] + (half,), *a, **kw)
    return step


def _altered_answer(real):
    def step(carry, blk, *a, **kw):
        carry, out = real(carry, blk, *a, **kw)
        j = out[0].at[0].set((out[0][0] + 1) % kw["n"])
        return carry, (j,) + tuple(out[1:])
    return step


@pytest.fixture(scope="module")
def bench_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("bench") / "bench"
    shutil.copytree(BENCH, d, ignore=shutil.ignore_patterns("tests"))
    write_tiny(str(d))
    return str(d)


def _run(bench_dir, traffic="drain", seconds=0.5):
    return run.run_cell(tiny_spec(traffic), "tiny-fb." + traffic, 2**31 + 7,
                        seconds, False, allow_cpu=True, bench_dir=bench_dir,
                        log=lambda *a, **k: None)


@pytest.mark.parametrize("traffic", ["drain", "open"])
def test_sound_run_is_correct(bench_dir, traffic):
    out = _run(bench_dir, traffic, 1.0 if traffic == "open" else 0.5)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _altered_answer])
def test_broken_step_is_not_correct(bench_dir, monkeypatch, fault):
    real = served._serve_step
    broken = fault(real)
    broken._cache_size = real._cache_size
    monkeypatch.setattr(served, "_serve_step", broken)
    out = _run(bench_dir)
    assert not out["correct"]
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
