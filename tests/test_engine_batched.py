"""Batched decision-block engine: exact parity vs the sequential oracle,
plus property tests for the per-task invariants.

The acceptance contract (ISSUE 1): the batched engine reproduces the
sequential engine's *placements* and *message ledger* exactly.  Timestamps
agree to float32 round-off — the two drivers emit the same arithmetic, but
XLA may contract the interference multiply-add into an FMA in one lowering
and not the other (observed only on single-server fleets), so they are
compared with ``allclose`` at 1-ulp-scale tolerances.
"""
import numpy as np
import pytest
from hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

from repro.sim import (EngineConfig, make_homogeneous, make_testbed,
                       resource_violations, simulate)
from repro.workloads import functionbench as fb

PARITY_POLICIES = ("dodoor", "random", "pot", "one_plus_beta", "prequal")


def assert_parity(seq, bat, *, timestamps_exact=False):
    assert (seq.server == bat.server).all(), "placements diverge"
    ledger = lambda r: (r.msgs_base, r.msgs_probe, r.msgs_push, r.msgs_flush)
    assert ledger(seq) == ledger(bat), "message ledger diverges"
    for f in ("enqueue_ms", "start_ms", "finish_ms", "sched_ms",
              "cores", "mem_mb"):
        a, b = getattr(seq, f), getattr(bat, f)
        if timestamps_exact:
            assert np.array_equal(a, b), f"{f} not bit-identical"
        else:
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-3,
                                       err_msg=f)


class TestParityFunctionBench:
    """fb_small on the 20-node small testbed — the ISSUE's parity suite."""

    @pytest.mark.parametrize("policy", PARITY_POLICIES)
    def test_default_b(self, policy, small_testbed, fb_small, sim_cache):
        cfg = EngineConfig(policy=policy,
                           b=max(1, small_testbed.num_servers // 2))
        seq = sim_cache(fb_small, small_testbed, cfg, key="fb_small")
        bat = sim_cache(fb_small, small_testbed, cfg, mode="batched",
                        key="fb_small")
        assert_parity(seq, bat, timestamps_exact=True)

    @pytest.mark.parametrize("b", (1, 7, 160, 1000))
    def test_block_sizes_and_ragged_tail(self, b, small_testbed, fb_small,
                                         sim_cache):
        """b=1 (push every task), b=7 (600 % 7 != 0: every block boundary is
        ragged-adjacent), b=160 (partial tail), b=1000 (> m: single partial
        block, no pushes)."""
        fe = 1 if b == 1 else 2
        cfg = EngineConfig(policy="dodoor", b=b, flush_every=fe)
        seq = sim_cache(fb_small, small_testbed, cfg, key="fb_small")
        bat = sim_cache(fb_small, small_testbed, cfg, mode="batched",
                        key="fb_small")
        assert_parity(seq, bat, timestamps_exact=True)
        if b == 1000:
            assert bat.msgs_push == 0      # never reaches the b-th decision

    @pytest.mark.parametrize("policy", ("pot", "prequal"))
    @pytest.mark.parametrize("b", (7, 160))
    def test_probing_policies_ragged_tail(self, policy, b, small_testbed,
                                          fb_small, sim_cache):
        """b ∤ m for the probing policies: the padded tail tasks must be
        inert in the PoT speculative loop and the Prequal segment scan."""
        cfg = EngineConfig(policy=policy, b=b)
        seq = sim_cache(fb_small, small_testbed, cfg, key="fb_small")
        bat = sim_cache(fb_small, small_testbed, cfg, mode="batched",
                        key="fb_small")
        assert_parity(seq, bat, timestamps_exact=True)

    def test_outage_window(self, small_testbed, fb_small):
        cfg = EngineConfig(policy="dodoor", b=10,
                           outage_ms=(1000.0, 5000.0))
        seq = simulate(fb_small, small_testbed, cfg)
        bat = simulate(fb_small, small_testbed, cfg, mode="batched")
        assert_parity(seq, bat, timestamps_exact=True)
        healthy = simulate(fb_small, small_testbed,
                           EngineConfig(policy="dodoor", b=10),
                           mode="batched")
        assert bat.msgs_push < healthy.msgs_push

    def test_alpha_extremes(self, small_testbed, fb_small):
        for alpha in (0.0, 1.0):
            cfg = EngineConfig(policy="dodoor", b=10, alpha=alpha)
            assert_parity(simulate(fb_small, small_testbed, cfg),
                          simulate(fb_small, small_testbed, cfg,
                                   mode="batched"),
                          timestamps_exact=True)

    def test_seed_sensitivity(self, small_testbed, fb_small):
        runs = [simulate(fb_small, small_testbed,
                         EngineConfig(policy="dodoor", b=10), seed=s,
                         mode="batched")
                for s in (0, 1)]
        assert (runs[0].server != runs[1].server).any()
        assert_parity(simulate(fb_small, small_testbed,
                               EngineConfig(policy="dodoor", b=10), seed=1),
                      runs[1], timestamps_exact=True)


class TestParityEdges:
    def test_single_server_fleet(self):
        """n=1 exercises the FMA-contraction caveat: placements and the
        ledger stay exact, timestamps to round-off."""
        cluster = make_homogeneous(1, cores=28, mem_mb=128_000)
        wl = fb.synthesize(m=100, qps=20.0, seed=0)
        cfg = EngineConfig(policy="dodoor", b=1, flush_every=1)
        seq = simulate(wl, cluster, cfg)
        bat = simulate(wl, cluster, cfg, mode="batched")
        assert_parity(seq, bat)
        assert (bat.server == 0).all()

    def test_burst_arrivals(self, small_testbed):
        from dataclasses import replace
        wl = fb.synthesize(m=300, qps=50.0, seed=3)
        burst = replace(wl, submit_ms=np.zeros_like(wl.submit_ms))
        cfg = EngineConfig(policy="dodoor", b=10)
        assert_parity(simulate(burst, small_testbed, cfg),
                      simulate(burst, small_testbed, cfg, mode="batched"),
                      timestamps_exact=True)

    def test_full_testbed(self, testbed, sim_cache):
        wl = fb.synthesize(m=1200, qps=120.0, seed=2)
        cfg = EngineConfig(policy="dodoor", b=50)
        assert_parity(sim_cache(wl, testbed, cfg, key="fb1200"),
                      sim_cache(wl, testbed, cfg, mode="batched",
                                key="fb1200"),
                      timestamps_exact=True)

    def test_unknown_mode_rejected(self, small_testbed, fb_small):
        with pytest.raises(ValueError):
            simulate(fb_small, small_testbed, EngineConfig(), mode="warp")


class TestPoTSpeculative:
    """The speculative-commit PoT driver: exactness across the conflict
    spectrum (ISSUE 2 satellite)."""

    def test_high_conflict_block(self):
        """4 servers, b=48: nearly every task's candidates collide with an
        earlier same-block commit, so the speculative loop degenerates to
        short prefixes — placements and ledger must stay exact."""
        cluster = make_homogeneous(4, cores=28, mem_mb=128_000)
        wl = fb.synthesize(m=288, qps=120.0, seed=3)
        cfg = EngineConfig(policy="pot", b=48)
        assert_parity(simulate(wl, cluster, cfg),
                      simulate(wl, cluster, cfg, mode="batched"),
                      timestamps_exact=True)

    def test_zero_conflict_blocks(self, small_testbed, fb_small):
        """b=1: every block holds a single task, so no speculative decision
        can ever conflict — the loop must commit each block in one pass."""
        cfg = EngineConfig(policy="pot", b=1)
        assert_parity(simulate(fb_small, small_testbed, cfg),
                      simulate(fb_small, small_testbed, cfg,
                               mode="batched"),
                      timestamps_exact=True)

    def test_low_conflict_wide_fleet(self):
        """100-server fleet, b=20: conflicts are rare, the common case the
        speculative commit optimizes for."""
        cluster = make_homogeneous(100, cores=28, mem_mb=128_000)
        wl = fb.synthesize(m=400, qps=100.0, seed=5)
        cfg = EngineConfig(policy="pot", b=20)
        assert_parity(simulate(wl, cluster, cfg),
                      simulate(wl, cluster, cfg, mode="batched"),
                      timestamps_exact=True)


class TestPrequalSegmentScan:
    """The scheduler-parallel Prequal driver (probe pools + exact probe
    revert) — no longer delegates to the sequential oracle."""

    def test_parity_small_fleet_collisions(self):
        """5 servers: same-chunk commits frequently hit probed servers, so
        the rb-slot revert path is exercised hard."""
        cluster = make_homogeneous(5, cores=28, mem_mb=128_000)
        wl = fb.synthesize(m=300, qps=100.0, seed=7)
        cfg = EngineConfig(policy="prequal", b=30)
        assert_parity(simulate(wl, cluster, cfg),
                      simulate(wl, cluster, cfg, mode="batched"),
                      timestamps_exact=True)

    def test_parity_block_larger_than_trace(self, small_testbed, fb_small):
        """b > m: one partial block — chunk masking over the padded tail."""
        cfg = EngineConfig(policy="prequal", b=1000)
        assert_parity(simulate(fb_small, small_testbed, cfg),
                      simulate(fb_small, small_testbed, cfg,
                               mode="batched"),
                      timestamps_exact=True)

    def test_chunks_straddle_scheduler_rounds(self, small_testbed, fb_small):
        """b=8 with S=5 schedulers: chunk boundaries never align with
        global scheduler rounds, so the chunk gather/scatter masking must
        carry pool state across blocks exactly."""
        cfg = EngineConfig(policy="prequal", b=8)
        assert_parity(simulate(fb_small, small_testbed, cfg),
                      simulate(fb_small, small_testbed, cfg,
                               mode="batched"),
                      timestamps_exact=True)


def _assert_kernel_parity(seq, bat, wl, cluster, seed=0):
    """Kernel-path placements are expected to be bit-identical to the jnp
    path on this platform; on a platform whose lowering rounds the score's
    multiply-by-reciprocal differently, a near-tie may legitimately flip to
    the task's *other* sampled candidate (and downstream placements then
    diverge).  Accept exactly that failure mode and nothing else: the first
    divergent task must have picked one of its two Algorithm-1 candidates.
    """
    assert seq.msgs_total == bat.msgs_total
    if (seq.server == bat.server).all():
        np.testing.assert_allclose(seq.finish_ms, bat.finish_ms,
                                   rtol=1e-5, atol=1e-2)
        return
    import jax
    from repro.core.prefilter import feasible_mask, sample_feasible
    i = int(np.argmax(seq.server != bat.server))
    key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
    k_cand = jax.random.split(key)[0]
    import jax.numpy as jnp
    mask = feasible_mask(jnp.asarray(wl.r_submit[i]),
                         jnp.asarray(cluster.C))
    cand = set(np.asarray(sample_feasible(k_cand, mask, 2)).tolist())
    assert {int(seq.server[i]), int(bat.server[i])} <= cand, (
        f"first divergence at task {i} is not a candidate tie-flip")


class TestKernelEnginePath:
    """use_kernel=True routes the dodoor/(1+β) decision through the fused
    sample→score→select Pallas megakernel (interpret mode on CPU) inside
    the batched driver."""

    def test_kernel_parity(self, small_testbed, fb_small, sim_cache):
        cfg = EngineConfig(policy="dodoor", b=10)
        seq = sim_cache(fb_small, small_testbed, cfg, key="fb_small")
        bat = sim_cache(fb_small, small_testbed, cfg, mode="batched",
                        use_kernel=True, key="fb_small")
        _assert_kernel_parity(seq, bat, fb_small, small_testbed)

    def test_kernel_parity_one_plus_beta(self, small_testbed, fb_small,
                                         sim_cache):
        """(1+β) consumes the megakernel's cand output for its β-mix."""
        cfg = EngineConfig(policy="one_plus_beta", b=10)
        seq = sim_cache(fb_small, small_testbed, cfg, key="fb_small")
        bat = sim_cache(fb_small, small_testbed, cfg, mode="batched",
                        use_kernel=True, key="fb_small")
        _assert_kernel_parity(seq, bat, fb_small, small_testbed)

    def test_kernel_partial_tail(self, small_testbed):
        """m=137, b=25 → last block holds 12 real + 13 padded tasks; the
        kernel's tile padding must not leak into placements or messages."""
        wl = fb.synthesize(m=137, qps=30.0, seed=1)
        cfg = EngineConfig(policy="dodoor", b=25)
        seq = simulate(wl, small_testbed, cfg)
        bat = simulate(wl, small_testbed, cfg, mode="batched",
                       use_kernel=True)
        _assert_kernel_parity(seq, bat, wl, small_testbed)

    def test_engine_config_kernel_knobs(self, small_testbed, fb_small):
        """block_t/interpret flow from EngineConfig into the megakernel's
        grid program (interpret=True pinned — the CPU auto-detected value —
        and a non-default tile size)."""
        base = EngineConfig(policy="dodoor", b=10)
        knobbed = EngineConfig(policy="dodoor", b=10, block_t=32,
                               interpret=True)
        a = simulate(fb_small, small_testbed, base, mode="batched",
                     use_kernel=True)
        bvt = simulate(fb_small, small_testbed, knobbed, mode="batched",
                       use_kernel=True)
        assert (a.server == bvt.server).all()
        assert a.msgs_total == bvt.msgs_total


def _sorted_fill_gather(arr, k, value):
    """The shift-merge in its plain gather form: each kept lane reads the
    entry ``k`` places to its right through ``take_along_axis``."""
    import jax.numpy as jnp
    W = arr.shape[1]
    iota = jnp.arange(W, dtype=jnp.int32)[None, :]
    kk = k[:, None]
    idx = jnp.sum((iota >= kk) & (arr < value[:, None]), axis=1)[:, None]
    src = jnp.where(iota < idx, iota + kk, iota)
    gathered = jnp.take_along_axis(arr, jnp.minimum(src, W - 1), axis=1)
    in_win = (iota >= idx) & (iota < idx + kk)
    return jnp.where(in_win, value[:, None], gathered)


class TestSortedFill:
    """``_sorted_fill``'s gather-free shift equals the gather form bit for
    bit, for every ``k`` in 1..W, and leaves each row sorted."""

    @pytest.mark.parametrize("kind", ("ties", "kth", "inf_pad"))
    @pytest.mark.parametrize("W", (1, 7, 28, 64))
    def test_matches_gather_form(self, W, kind):
        import jax.numpy as jnp
        from repro.sim.engine import _sorted_fill
        rng = np.random.RandomState(W)
        reps = 16
        k = np.repeat(np.arange(1, W + 1, dtype=np.int32), reps)   # [n]
        n = k.size
        # Few distinct values, so rows and values tie often.
        arr = np.sort(rng.randint(0, 6, (n, W)).astype(np.float32), axis=1)
        if kind == "inf_pad":
            # Like _init_carry's core_free: units past a server's count
            # are +inf, and a task takes at most that many units.
            width = rng.randint(k, W + 1)
            arr[np.arange(W)[None, :] >= width[:, None]] = np.inf
        kth = arr[np.arange(n), k - 1]
        if kind == "kth":
            value = kth
        else:
            # An entry of the row at or past the k-th (finite under
            # padding), or a fresh value above it.
            top = np.sum(np.isfinite(arr), axis=1) - 1
            pick = arr[np.arange(n), rng.randint(k - 1, top + 1)]
            value = np.where(rng.rand(n) < 0.7, pick,
                             kth + rng.randint(0, 4, n))
        value = value.astype(np.float32)
        assert (value >= kth).all() and np.isfinite(value).all()
        args = (jnp.asarray(arr), jnp.asarray(k), jnp.asarray(value))
        got = np.asarray(_sorted_fill(*args))
        want = np.asarray(_sorted_fill_gather(*args))
        assert np.array_equal(got.view(np.int32), want.view(np.int32))
        assert (got[:, 1:] >= got[:, :-1]).all()
        # The k smallest entries gave way to k copies of the value.
        for row, kr, v, a in zip(got, k, value, arr):
            assert np.array_equal(row, np.sort(np.r_[a[kr:], [v] * kr]))


class TestFusedMegakernelDraws:
    """The megakernel's in-kernel sampling pinned draw-for-draw to the
    two-stage ``sample_feasible_batch`` + ``dodoor_choice_ref`` path at
    engine-realistic shapes."""

    def _pin(self, T, N, seed):
        import jax
        import jax.numpy as jnp
        from repro.core.prefilter import feasible_mask, sample_feasible_batch
        from repro.kernels.dodoor_choice import (dodoor_choice_ref,
                                                 dodoor_fused)
        rng = np.random.RandomState(seed)
        base = jax.random.PRNGKey(seed)
        keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(
            jnp.arange(T))
        r = jnp.asarray(rng.rand(T, 2).astype(np.float32) * 8)
        d = jnp.asarray(rng.rand(T, N).astype(np.float32) * 1000)
        L = jnp.asarray(rng.rand(N, 2).astype(np.float32) * 50)
        D = jnp.asarray(rng.rand(N).astype(np.float32) * 5000)
        C = jnp.asarray(8.0 + rng.rand(N, 2).astype(np.float32) * 100)
        choice, cand, scores = dodoor_fused(keys, r, d, L, D, C, 0.5)
        # draws: bit-exact vs the two-stage sampler
        ref_cand = sample_feasible_batch(keys, feasible_mask(r, C), 2)
        assert (np.asarray(cand) == np.asarray(ref_cand)).all()
        # choices: agree with the two-stage oracle wherever the score
        # margin is firm (1-ulp FMA-contraction caveat on exact ties)
        d_cand = jnp.take_along_axis(d, ref_cand, axis=1)
        rchoice, rscores = dodoor_choice_ref(r, ref_cand, d_cand, L, D, C,
                                             0.5)
        np.testing.assert_allclose(np.asarray(scores), np.asarray(rscores),
                                   rtol=2e-5, atol=1e-6)
        margin = np.abs(np.asarray(rscores[:, 0] - rscores[:, 1]))
        firm = margin > 1e-5
        assert (np.asarray(choice)[firm] == np.asarray(rchoice)[firm]).all()

    @pytest.mark.parametrize("T,N", [(50, 20), (600, 101), (2048, 100)])
    def test_pinned_at_benchmark_shapes(self, T, N):
        self._pin(T, N, seed=T + N)

    @pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
    @given(T=st.integers(1, 200), N=st.integers(1, 130),
           seed=st.integers(0, 5))
    @settings(max_examples=10, deadline=None)
    def test_pinned_property(self, T, N, seed):
        self._pin(T, N, seed)


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
class TestBatchedInvariantsProperty:
    """Per-task invariants hold for arbitrary (m, qps, b, policy, seed)."""

    @given(m=st.integers(40, 160), qps=st.floats(10.0, 120.0),
           b=st.integers(1, 64), seed=st.integers(0, 3),
           policy=st.sampled_from(PARITY_POLICIES))
    @settings(max_examples=8, deadline=None)
    def test_invariants(self, m, qps, b, seed, policy, small_testbed):
        wl = fb.synthesize(m=m, qps=qps, seed=seed)
        cfg = EngineConfig(policy=policy, b=b, flush_every=1)
        res = simulate(wl, small_testbed, cfg, seed=seed, mode="batched")
        assert res.server.shape[0] == m
        assert (res.server >= 0).all()
        assert (res.server < small_testbed.num_servers).all()
        # enqueue ≤ start ≤ finish, enqueue ≥ submit
        assert (res.enqueue_ms >= res.submit_ms - 1e-3).all()
        assert (res.start_ms >= res.enqueue_ms - 1e-3).all()
        assert (res.finish_ms > res.start_ms).all()
        assert np.isfinite(res.finish_ms).all()
        # concurrent per-server core/memory usage never exceeds capacity
        assert resource_violations(res, small_testbed, dt_ms=500.0) == 0
