"""The streaming decision service (`repro.serve`).

The contract under test is the ISSUE-10 tentpole: the online service,
driving the factored-out single-block scan body one donated-buffer step
at a time, must be **bit-exact** against ``simulate(mode="batched")``
over the same arrival plane — for every policy, any submission chunking,
and across checkpoint/resume — with zero recompiles in steady state.
"""
import numpy as np
import pytest

from hypothesis_compat import given, settings, st
from repro.serve import ArrivalRing, DecisionService, LatencyRecorder, \
    serve_workload
from repro.serve.service import _serve_step
from repro.sim import (CacheFaults, Dynamics, EngineConfig, LocalityModel,
                       RetryPolicy, make_testbed, simulate)
from repro.workloads import functionbench as fb

POLICIES = ("random", "pot", "dodoor", "prequal", "one_plus_beta")


@pytest.fixture(scope="module")
def cluster():
    return make_testbed(scale=0.2)


@pytest.fixture(scope="module")
def wl():
    # 317 tasks: a ragged tail at every tested b, so flush() padding is
    # always exercised.
    return fb.synthesize(m=317, qps=60.0, seed=0)


def _assert_same(off, res, label=""):
    assert (off.server == res.server).all(), label
    for f in ("enqueue_ms", "start_ms", "finish_ms", "sched_ms",
              "cores", "mem_mb", "submit_ms"):
        assert np.array_equal(getattr(off, f), getattr(res, f)), (label, f)
    for f in ("msgs_base", "msgs_probe", "msgs_push", "msgs_flush"):
        assert getattr(off, f) == getattr(res, f), (label, f)


class TestOfflineParity:
    """The offline batched engine is the online engine's oracle."""

    @pytest.mark.parametrize("policy", POLICIES)
    def test_all_policies_bit_exact(self, cluster, wl, policy):
        cfg = EngineConfig(policy=policy, b=25)
        off = simulate(wl, cluster, cfg, seed=0, mode="batched")
        _, res = serve_workload(wl, cluster, cfg, seed=0, chunk=13)
        _assert_same(off, res, policy)

    def test_open_loop_same_placements(self, cluster, wl):
        """Arrival pressure changes latencies, never placements."""
        cfg = EngineConfig(policy="dodoor", b=25)
        _, closed = serve_workload(wl, cluster, cfg, seed=0)
        _, opened = serve_workload(wl, cluster, cfg, seed=0,
                                   open_loop=True, chunk=50)
        _assert_same(closed, opened, "open vs closed")

    def test_dynamics_and_cache_faults_parity(self, cluster, wl):
        dyn = Dynamics(outages=((3, 100.0, 900.0),),
                       cache_faults=CacheFaults(loss_rate=0.3, seed=7))
        cfg = EngineConfig(policy="dodoor", b=25)
        off = simulate(wl, cluster, cfg, seed=0, mode="batched",
                       dynamics=dyn)
        _, res = serve_workload(wl, cluster, cfg, seed=0, dynamics=dyn)
        _assert_same(off, res, "faulted")

    def test_kernel_path_parity(self, cluster, wl):
        """use_kernel=True (interpret-mode megakernel) through the
        service matches the offline kernel run draw-for-draw."""
        cfg = EngineConfig(policy="dodoor", b=25)
        off = simulate(wl, cluster, cfg, seed=0, mode="batched",
                       use_kernel=True)
        _, res = serve_workload(wl, cluster, cfg, seed=0, use_kernel=True)
        _assert_same(off, res, "kernel")


class TestStreamingSemantics:
    def test_step_needs_full_block(self, cluster, wl):
        svc = DecisionService(cluster, EngineConfig(policy="dodoor", b=25))
        svc.submit_workload(wl, 0, 10)
        with pytest.raises(ValueError, match="full block"):
            svc.step()
        assert svc.available == 10

    def test_flush_handles_ragged_tail_and_result_gate(self, cluster, wl):
        svc = DecisionService(cluster, EngineConfig(policy="dodoor", b=25))
        svc.submit_workload(wl, 0, 60)
        assert svc.drain() == 50
        with pytest.raises(ValueError, match="flush"):
            svc.result()
        assert svc.flush() == 10
        assert svc.scheduled == 60
        assert svc.result().server.shape == (60,)

    def test_ring_overflow_raises(self, cluster, wl):
        svc = DecisionService(cluster, EngineConfig(policy="dodoor", b=25),
                              capacity=30)
        with pytest.raises(RuntimeError, match="ring full"):
            svc.submit_workload(wl, 0, 31)

    def test_unsupported_knobs_raise(self, cluster):
        with pytest.raises(NotImplementedError, match="RetryPolicy"):
            DecisionService(cluster, EngineConfig(
                policy="dodoor", b=25, retry=RetryPolicy()))
        with pytest.raises(NotImplementedError, match="trace"):
            DecisionService(cluster, EngineConfig(
                policy="dodoor", b=25, trace=True))
        with pytest.raises(NotImplementedError, match="LocalityModel"):
            DecisionService(cluster, EngineConfig(
                policy="dodoor", b=25, locality=LocalityModel()))

    def test_latency_recorders_populate(self, cluster, wl):
        svc, _ = serve_workload(wl, cluster,
                                EngineConfig(policy="dodoor", b=25),
                                seed=0)
        m = wl.r_submit.shape[0]
        assert svc.decision_latency.count == m
        assert svc.step_wall.count == -(-m // 25)
        summ = svc.latency_summary()
        assert summ["decision"]["count"] == m
        assert summ["decision"]["p99_ms"] >= summ["decision"]["p50_ms"]
        hist = summ["step"]["histogram"]
        assert sum(hist["counts"]) == svc.step_wall.count
        assert len(hist["edges_ms"]) == len(hist["counts"]) + 1

    def test_snapshot_double_buffered(self, cluster, wl):
        svc = DecisionService(cluster, EngineConfig(policy="dodoor", b=25))
        assert svc.snapshot() is None
        svc.submit_workload(wl, 0, 50)
        svc.step()
        s1 = svc.snapshot()
        assert s1["step"] == 1
        svc.step()
        s2 = svc.snapshot()
        # the first snapshot buffer was not overwritten in place
        assert s2["step"] == 2 and s1["step"] == 1
        assert s1["view_L"].shape == (cluster.num_servers, 2)

    def test_snapshot_is_the_post_step_views_and_stays_put(self, cluster,
                                                          wl):
        """Each published snapshot holds, bit for bit, the views the step
        left in the carry, and a later step leaves it as it was."""
        svc = DecisionService(cluster, EngineConfig(policy="dodoor", b=25))
        svc.submit_workload(wl, 0, 150)
        fields = ("view_L", "view_D", "view_rif")
        kept = []
        for _ in range(6):
            svc.step()
            snap = svc.snapshot()
            for f in fields:
                assert np.array_equal(snap[f],
                                      np.asarray(getattr(svc._carry, f)))
            kept.append((snap, {f: snap[f].copy() for f in fields}))
        assert any(not np.array_equal(kept[0][1][f], kept[-1][1][f])
                   for f in fields)          # the views did move
        for i, (snap, copy) in enumerate(kept, start=1):
            assert snap["step"] == i
            for f in fields:
                assert np.array_equal(snap[f], copy[f]), (i, f)

    def test_ragged_tail_uses_its_own_mask(self, cluster, wl, monkeypatch):
        """Every block uploads its own validity mask: all true for a full
        block, the 17 real tasks of flush()'s tail, which are then placed
        as the offline engine places them; full blocks and the tail share
        one unpack program."""
        from repro.serve.service import _unpack_block
        staged = []
        stage = DecisionService._stage

        def keep(svc, rows, valid_count):
            staged.append(stage(svc, rows, valid_count))
            return staged[-1]
        monkeypatch.setattr(DecisionService, "_stage", keep)
        cfg = EngineConfig(policy="dodoor", b=25)
        off = simulate(wl, cluster, cfg, seed=0, mode="batched")
        svc = DecisionService(cluster, cfg, seed=0, capacity=317)
        svc.submit_workload(wl)
        svc.step()
        warm = _unpack_block._cache_size()
        assert svc.drain() == 275
        assert svc.flush() == 17
        assert _unpack_block._cache_size() == warm
        _assert_same(off, svc.result(), "ragged tail")
        masks = [buf[:, -1].tolist() for buf in staged]
        assert masks == [[1] * 25] * 12 + [[1] * 17 + [0] * 8]
        ids = np.concatenate([buf[:, 0] for buf in staged])
        assert ids.tolist() == list(range(325))

class TestTracing:
    """The ``serve.*`` host spans on a profiler trace, and ``ring_wait``."""

    PHASES = ("serve.ring_pop", "serve.upload", "serve.dispatch",
              "serve.device_wait", "serve.readback", "serve.publish")

    @pytest.fixture(scope="class")
    def traced_stats(self, cluster, wl, tmp_path_factory):
        """A service driven through submit / drain / flush under the
        profiler: the service and its ``serve.*`` events (name, tags,
        start, end) in start order."""
        import glob
        import os

        import jax
        from jax.profiler import ProfileData
        log = str(tmp_path_factory.mktemp("serve_trace"))
        m = wl.r_submit.shape[0]
        svc = DecisionService(cluster, EngineConfig(policy="dodoor", b=25),
                              capacity=m)
        with jax.profiler.trace(log):
            for lo in range(0, m, 40):
                svc.submit_workload(wl, lo, min(lo + 40, m))
                svc.drain()
            svc.flush()
        path, = glob.glob(os.path.join(log, "**", "*.xplane.pb"),
                          recursive=True)
        events = [(e.name, dict(e.stats), e.start_ns, e.end_ns)
                  for plane in ProfileData.from_file(path).planes
                  if plane.name.startswith("/host:")
                  for line in plane.lines for e in line.events
                  if e.name.startswith("serve.")]
        return svc, sorted(events, key=lambda e: e[2])

    @pytest.fixture(scope="class")
    def traced(self, traced_stats):
        """The same events as (name, block, start, end)."""
        svc, events = traced_stats
        return svc, [(name, tags.get("block"), lo, hi)
                     for name, tags, lo, hi in events]

    def test_one_parent_span_per_block(self, traced, wl):
        svc, events = traced
        parents = [e for e in events if e[0] in ("serve.step", "serve.flush")]
        nb = -(-wl.r_submit.shape[0] // 25)
        assert [e[1] for e in parents] == list(range(nb))
        assert [e[0] for e in parents] == ["serve.step"] * (nb - 1) + [
            "serve.flush"]                # 317 = 12·25 + a 17-task tail

    def test_each_phase_once_in_order_with_the_block_id(self, traced):
        _, events = traced
        parents = [e for e in events if e[0] in ("serve.step", "serve.flush")]
        children = [e for e in events if e[0] in self.PHASES]
        assert len(children) == len(self.PHASES) * len(parents)
        for name, k, lo, hi in parents:
            inside = [c for c in children if lo <= c[2] and c[3] <= hi]
            assert tuple(c[0] for c in inside) == self.PHASES, k
            assert {c[1] for c in inside} == {k}

    def test_one_transfer_each_way_tagged_with_what_it_moved(
            self, traced_stats, cluster):
        """``serve.upload`` sends the ids, the five planes and the mask as
        one buffer; ``serve.readback`` fetches seven output planes and
        three views in one call; each span says how many arrays and
        bytes."""
        svc, events = traced_stats
        b, n, tt = 25, cluster.num_servers, cluster.num_types
        planes = 4 * b * (1 + 2 + 2 * tt + tt + tt + 1)  # ids .. submit_ms
        views = sum(np.asarray(getattr(svc._carry, f)).nbytes
                    for f in ("view_L", "view_D", "view_rif"))
        assert views == 4 * (2 * n + n + n)
        uploads = [t for name, t, _, _ in events if name == "serve.upload"]
        readbacks = [t for name, t, _, _ in events
                     if name == "serve.readback"]
        nb = len(uploads)
        assert nb == len(readbacks) == -(-317 // b)
        for t in uploads:       # one int32 buffer: the planes and a mask
            assert (t["arrays"], t["bytes"]) == (1, planes + 4 * b)
        for t in readbacks:
            assert (t["arrays"], t["bytes"]) == (10, 7 * 4 * b + views)
        assert [t["block"] for t in uploads] == list(range(nb))

    def test_submit_spans_name_the_first_task_block(self, traced, wl):
        _, events = traced
        submits = [e for e in events if e[0] == "serve.submit"]
        m = wl.r_submit.shape[0]
        assert [e[1] for e in submits] == [lo // 25
                                          for lo in range(0, m, 40)]

    def test_ring_wait_per_decision_within_its_latency(self, traced, wl):
        svc, _ = traced
        m = wl.r_submit.shape[0]
        wait = svc.ring_wait.samples()
        assert svc.ring_wait.count == m == svc.decision_latency.count
        assert (wait >= 0).all()
        assert (wait <= svc.decision_latency.samples()).all()
        assert svc.latency_summary()["ring_wait"]["count"] == m

    def test_one_part_adds_no_spans_or_tags(self, traced_stats):
        """Without mini-clusters the trace is as it was: no ``serve.deal``
        or ``serve.merge``, and no ``parts`` tag."""
        _, events = traced_stats
        names = {name for name, _, _, _ in events}
        assert not names & {"serve.deal", "serve.merge"}
        assert all("parts" not in tags for _, tags, _, _ in events)


class TestDonationAndCompiles:
    def test_zero_recompiles_after_warmup(self, cluster, wl):
        """Steady-state steps and the edge-padded flush tail reuse one
        compiled program — the ISSUE-10 acceptance assert."""
        cfg = EngineConfig(policy="dodoor", b=25)
        svc = DecisionService(cluster, cfg, seed=3)
        svc.submit_workload(wl)
        svc.step()                      # warmup (may compile)
        warm = svc.compiles
        for _ in range(5):
            svc.step()
        svc.flush()
        assert svc.compiles == warm, "steady-state step recompiled"

    def test_carry_buffers_are_donated(self, cluster, wl):
        """The previous carry is consumed by the step — its buffers are
        handed back to XLA, which is what makes steady state
        allocation-free.  JAX enforces this: a donated buffer cannot be
        read afterwards."""
        svc = DecisionService(cluster, EngineConfig(policy="dodoor", b=25))
        svc.submit_workload(wl, 0, 50)
        old_carry = svc._carry
        svc.step()
        with pytest.raises(RuntimeError):
            np.asarray(old_carry.view_D)


class TestCheckpointResume:
    def test_resume_is_bit_exact_continuation(self, cluster, wl):
        cfg = EngineConfig(policy="dodoor", b=25)
        m = wl.r_submit.shape[0]
        cut = 150
        a = DecisionService(cluster, cfg, seed=0, capacity=m)
        a.submit_workload(wl, 0, cut)
        a.drain()
        ck = a.export_checkpoint()
        a.submit_workload(wl, cut, m)
        a.flush()
        uninterrupted = a.result()

        b = DecisionService.from_checkpoint(cluster, cfg, ck, capacity=m)
        b.submit_workload(wl, cut, m)
        b.flush()
        resumed = b.result()
        assert (resumed.server == uninterrupted.server[cut:]).all()
        assert np.array_equal(resumed.finish_ms,
                              uninterrupted.finish_ms[cut:])
        # ledger continues, not restarts
        assert resumed.msgs_base == uninterrupted.msgs_base

    def test_checkpoint_requires_empty_ring(self, cluster, wl):
        svc = DecisionService(cluster, EngineConfig(policy="dodoor", b=25))
        svc.submit_workload(wl, 0, 10)
        with pytest.raises(ValueError, match="buffered"):
            svc.export_checkpoint()

    def test_mismatched_restore_raises(self, cluster, wl):
        cfg = EngineConfig(policy="dodoor", b=25)
        svc = DecisionService(cluster, cfg, seed=0, capacity=400)
        svc.submit_workload(wl, 0, 50)
        svc.drain()
        ck = svc.export_checkpoint()
        with pytest.raises(ValueError, match="does not match"):
            DecisionService.from_checkpoint(
                cluster, cfg._replace(b=50), ck)


class TestRechunkingProperty:
    @given(st.lists(st.integers(min_value=1, max_value=97),
                    min_size=1, max_size=8),
           st.sampled_from(POLICIES))
    @settings(max_examples=10, deadline=None)
    def test_any_chunking_yields_identical_placements(self, cuts, policy):
        """Re-chunking the same arrival stream — any split sizes, any
        policy — never changes placements or the ledger: blocks are
        formed by the service, not the submitter."""
        cluster = make_testbed(scale=0.2)
        wl = fb.synthesize(m=180, qps=60.0, seed=1)
        m = wl.r_submit.shape[0]
        cfg = EngineConfig(policy=policy, b=25)
        off = simulate(wl, cluster, cfg, seed=0, mode="batched")
        svc = DecisionService(cluster, cfg, seed=0, capacity=m)
        lo = 0
        for c in cuts:
            if lo >= m:
                break
            svc.submit_workload(wl, lo, min(lo + c, m))
            svc.drain()
            lo = min(lo + c, m)
        if lo < m:
            svc.submit_workload(wl, lo, m)
        svc.flush()
        res = svc.result()
        _assert_same(off, res, (cuts, policy))


class TestRingAndLatencyUnits:
    def test_ring_fifo_wraparound(self):
        ring = ArrivalRing(capacity=7, num_types=2)
        def chunk(lo, hi):
            k = hi - lo
            ring.push(np.full((k, 2), lo, np.float32),
                      np.zeros((k, 2, 2), np.float32),
                      np.zeros((k, 2), np.float32),
                      np.zeros((k, 2), np.float32),
                      np.arange(lo, hi, dtype=np.float32), t_enq=0.0)
        chunk(0, 5)
        assert ring.pop(3).submit_ms.tolist() == [0.0, 1.0, 2.0]
        chunk(5, 10)                      # wraps the 7-slot buffer
        assert ring.count == 7
        assert ring.pop(7).submit_ms.tolist() == [3.0, 4.0, 5.0, 6.0,
                                                  7.0, 8.0, 9.0]

    def test_latency_recorder_percentiles_and_histogram(self):
        rec = LatencyRecorder()
        rec.record(np.arange(1.0, 101.0))
        assert rec.count == 100
        assert abs(rec.percentile(50) - 50.5) < 1e-9
        h = rec.histogram(nbins=10)
        assert sum(h["counts"]) == 100
        s = rec.summary()
        assert s["p99_ms"] <= s["max_ms"] == 100.0

    @pytest.mark.parametrize("seed", range(6))
    def test_histogram_counts_every_sample(self, seed):
        """The outer edges hold the extremes exactly, so no sample falls
        outside the buckets (a plain log-space grid can round its last
        edge below the largest sample)."""
        rng = np.random.default_rng(seed)
        for size in (1, 2, 13, 13, 13, 40, 13, 97):
            for _ in range(8):
                rec = LatencyRecorder()
                rec.record(rng.lognormal(0.0, 1.0, size=size))
                h = rec.histogram()
                assert sum(h["counts"]) == rec.count == size
                s = rec.samples()
                assert h["edges_ms"][0] <= round(float(s.min()), 6)
                assert h["edges_ms"][-1] >= round(float(s.max()), 6)
        rec = LatencyRecorder()
        rec.record([0.0, 2.0, 3.0])        # below the 1 ns floor
        assert sum(rec.histogram(nbins=4)["counts"]) == 3


def _hierarchical(wl, cluster, cfg, k, seed):
    """The offline oracle of a ``mini_clusters=k`` service."""
    from repro.sim import simulate_hierarchical
    b = cfg.b // k
    return simulate_hierarchical(wl, cluster, cfg._replace(b=b), k, seed,
                                 mode="batched", b=b)


def _serve_parts(wl, cluster, cfg, k, seed, chunk):
    m = wl.r_submit.shape[0]
    svc = DecisionService(cluster, cfg, seed=seed, capacity=m,
                          mini_clusters=k)
    for lo in range(0, m, chunk):
        svc.submit_workload(wl, lo, min(lo + chunk, m))
        svc.drain()
    svc.flush()
    return svc, svc.result()


class TestMiniClusters:
    """``mini_clusters=k``: the §4.2 split on the served path, bit-exact
    with ``simulate_hierarchical`` (here every part rides one device's
    ``vmap``; the four-device mesh runs in a subprocess below)."""

    @pytest.fixture(scope="class")
    def vms(self):
        from repro.workloads import azure
        return azure.synthesize(m=317, qps=5.0, seed=1)

    @pytest.mark.parametrize("chunk", (7, 40))
    @pytest.mark.parametrize("mix", ("functionbench", "azure"))
    @pytest.mark.parametrize("k", (2, 4))
    @pytest.mark.parametrize("policy", ("dodoor", "pot"))
    def test_bit_exact_with_simulate_hierarchical(self, cluster, wl, vms,
                                                  policy, k, mix, chunk):
        tasks = wl if mix == "functionbench" else vms
        cfg = EngineConfig(policy=policy, b=20)      # 317 = 15·20 + 17
        off = _hierarchical(tasks, cluster, cfg, k, seed=3)
        svc, res = _serve_parts(tasks, cluster, cfg, k, 3, chunk)
        _assert_same(off, res, (policy, k, mix, chunk))
        parts = svc.part_decisions
        assert parts.shape == (k,) and parts.sum() == svc.scheduled == 317
        assert parts.tolist() == [len(range(c, 317, k)) for c in range(k)]

    @pytest.mark.parametrize("policy", ("dodoor", "pot"))
    def test_tail_that_leaves_parts_empty(self, cluster, wl, policy):
        """A one-task tail: three of four parts step a block with no valid
        row, which must leave their state as it was."""
        from dataclasses import replace
        short = replace(wl, **{f: getattr(wl, f)[:301] for f in (
            "r_submit", "r_exec", "d_est", "d_act", "task_type",
            "submit_ms")})
        cfg = EngineConfig(policy=policy, b=20)
        off = _hierarchical(short, cluster, cfg, 4, seed=0)
        svc, res = _serve_parts(short, cluster, cfg, 4, 0, 50)
        _assert_same(off, res, policy)
        assert svc.part_decisions.tolist() == [76, 75, 75, 75]

    def test_one_compile_and_snapshots_over_the_whole_fleet(self, cluster,
                                                            wl):
        cfg = EngineConfig(policy="dodoor", b=20)
        svc = DecisionService(cluster, cfg, seed=0, capacity=317,
                              mini_clusters=4)
        svc.submit_workload(wl)
        svc.step()
        warm = svc.compiles
        svc.drain()
        svc.flush()
        assert svc.compiles == warm
        snap = svc.snapshot()
        n = cluster.num_servers
        assert snap["view_L"].shape == (n, 2)
        assert snap["view_D"].shape == snap["view_rif"].shape == (n,)
        # Each server's view is its own part's: server j of part j mod 4.
        carry_D = np.asarray(svc._carry.view_D)               # [4, n/4]
        for c in range(4):
            assert np.array_equal(snap["view_D"][c::4], carry_D[c])

    def test_one_part_is_the_served_path(self, cluster, wl):
        """``mini_clusters=1`` is today's service: the same placements and
        the same step program."""
        cfg = EngineConfig(policy="dodoor", b=25)
        plain = DecisionService(cluster, cfg, seed=0, capacity=317)
        one = DecisionService(cluster, cfg, seed=0, capacity=317,
                              mini_clusters=1)
        assert one.lower_step().as_text() == plain.lower_step().as_text()
        for svc in (plain, one):
            svc.submit_workload(wl)
            svc.flush()
        _assert_same(plain.result(), one.result(), "k=1")
        assert one.part_decisions.tolist() == [317]

    @pytest.mark.parametrize("k,b", [(3, 21), (4, 30), (0, 20), (40, 40)])
    def test_parts_must_divide_fleet_and_block(self, cluster, k, b):
        with pytest.raises(ValueError, match="mini_clusters"):
            DecisionService(cluster, EngineConfig(policy="dodoor", b=b),
                            mini_clusters=k)

    def test_no_dynamics_or_checkpoints(self, cluster, wl):
        cfg = EngineConfig(policy="dodoor", b=20)
        with pytest.raises(NotImplementedError, match="dynamics"):
            DecisionService(cluster, cfg, mini_clusters=2,
                            dynamics=Dynamics(outages=((3, 1.0, 9.0),)))
        svc = DecisionService(cluster, cfg, mini_clusters=2)
        with pytest.raises(NotImplementedError, match="checkpoint"):
            svc.export_checkpoint()
        plain = DecisionService(cluster, cfg)
        plain.submit_workload(wl, 0, 40)
        plain.drain()
        with pytest.raises(NotImplementedError, match="checkpoint"):
            DecisionService.from_checkpoint(
                cluster, cfg, plain.export_checkpoint(), mini_clusters=2)

    def test_deal_and_merge_spans(self, cluster, wl, tmp_path):
        """``serve.deal`` inside ``serve.upload`` and ``serve.merge`` inside
        ``serve.readback``, once a block, all four tagged with the parts."""
        import glob
        import os

        import jax
        from jax.profiler import ProfileData
        svc = DecisionService(cluster, EngineConfig(policy="dodoor", b=20),
                              capacity=317, mini_clusters=4)
        svc.submit_workload(wl)
        svc.step()
        with jax.profiler.trace(str(tmp_path)):
            svc.flush()
        path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                          recursive=True)
        events = {e.name: (dict(e.stats), e.start_ns, e.end_ns)
                  for plane in ProfileData.from_file(path).planes
                  if plane.name.startswith("/host:")
                  for line in plane.lines for e in line.events
                  if e.name.startswith("serve.")}
        for inner, outer in (("serve.deal", "serve.upload"),
                             ("serve.merge", "serve.readback")):
            (tags, lo, hi), (otags, olo, ohi) = events[inner], events[outer]
            assert olo <= lo and hi <= ohi
            assert tags["parts"] == otags["parts"] == 4
            assert tags["block"] == otags["block"] == 15

    def test_four_devices_one_part_each(self):
        """On four (virtual) devices every part's state lives on its own
        device, the step is one program, and the results match the offline
        oracle for k = 4 and k = 2."""
        import os
        import subprocess
        import sys
        code = r"""
import jax, numpy as np
from repro.serve import DecisionService
from repro.sim import EngineConfig, make_testbed, simulate_hierarchical
from repro.workloads import azure, functionbench as fb
assert len(jax.devices()) == 4
cluster = make_testbed(scale=0.4)
for wl in (fb.synthesize(m=317, qps=60.0, seed=0),
           azure.synthesize(m=301, qps=5.0, seed=1)):
    for k in (4, 2):
        cfg = EngineConfig(policy="dodoor", b=20)
        m = wl.r_submit.shape[0]
        off = simulate_hierarchical(wl, cluster, cfg._replace(b=20 // k), k,
                                    7, mode="batched", b=20 // k)
        svc = DecisionService(cluster, cfg, seed=7, capacity=m,
                              mini_clusters=k)
        held = svc._carry.rb_release.sharding.device_set
        assert len(held) == k, held
        svc.submit_workload(wl)
        svc.step()
        warm = svc.compiles
        svc.flush()
        assert svc.compiles == warm
        res = svc.result()
        assert (off.server == res.server).all()
        for f in ("start_ms", "finish_ms", "enqueue_ms", "sched_ms"):
            assert np.array_equal(getattr(off, f), getattr(res, f)), f
        assert off.msgs_total == res.msgs_total
        assert (off.msgs_push, off.msgs_flush) == (res.msgs_push,
                                                   res.msgs_flush)
        assert svc.part_decisions.sum() == m
print("mini-clusters on four devices exact")
"""
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = {**os.environ,
               "PYTHONPATH": os.path.join(repo, "src"),
               "JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                             " --xla_force_host_platform_device_count=4")
               .strip()}
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=420)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "mini-clusters on four devices exact" in out.stdout
