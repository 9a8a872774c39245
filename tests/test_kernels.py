"""Per-kernel allclose tests against pure-jnp oracles (interpret mode),
sweeping shapes and dtypes per the deliverable contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.dodoor_choice import (dodoor_choice, dodoor_choice_ref,
                                         dodoor_fused, dodoor_fused_ref,
                                         dodoor_fused_sparse,
                                         dodoor_fused_sparse_ref)
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.rl_score import rl_score_matrix, rl_score_matrix_ref
from repro.kernels.ssd_chunk import ssd, ssd_ref
from repro.kernels.ssd_chunk.ops import ssd_decode_step


class TestRLScoreKernel:
    @pytest.mark.parametrize("T,N,K", [(8, 10, 2), (128, 128, 2), (200, 100, 2),
                                       (130, 300, 4), (1, 1, 2), (384, 257, 8)])
    def test_matches_ref(self, T, N, K):
        rng = np.random.RandomState(T + N)
        r = jnp.asarray(rng.rand(T, K).astype(np.float32) * 8)
        L = jnp.asarray(rng.rand(N, K).astype(np.float32) * 100)
        C = jnp.asarray(1.0 + rng.rand(N, K).astype(np.float32) * 100)
        out = rl_score_matrix(r, L, C)
        ref = rl_score_matrix_ref(r, L, C)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=1e-7)

    def test_small_blocks(self):
        rng = np.random.RandomState(0)
        r = jnp.asarray(rng.rand(40, 2).astype(np.float32))
        L = jnp.asarray(rng.rand(70, 2).astype(np.float32))
        C = jnp.asarray(1.0 + rng.rand(70, 2).astype(np.float32))
        out = rl_score_matrix(r, L, C, block_t=16, block_n=32)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(rl_score_matrix_ref(r, L, C)),
                                   rtol=2e-5)


class TestDodoorChoiceKernel:
    @pytest.mark.parametrize("T,N,alpha", [(16, 20, 0.5), (300, 100, 0.5),
                                           (257, 64, 0.0), (64, 500, 1.0)])
    def test_matches_ref(self, T, N, alpha):
        rng = np.random.RandomState(T)
        r = jnp.asarray(rng.rand(T, 2).astype(np.float32) * 8)
        cand = jnp.asarray(rng.randint(0, N, size=(T, 2)).astype(np.int32))
        d_cand = jnp.asarray(rng.rand(T, 2).astype(np.float32) * 1000)
        L = jnp.asarray(rng.rand(N, 2).astype(np.float32) * 50)
        D = jnp.asarray(rng.rand(N).astype(np.float32) * 5000)
        C = jnp.asarray(8.0 + rng.rand(N, 2).astype(np.float32) * 100)
        choice, scores = dodoor_choice(r, cand, d_cand, L, D, C, alpha,
                                       block_t=64)
        rchoice, rscores = dodoor_choice_ref(r, cand, d_cand, L, D, C, alpha)
        np.testing.assert_allclose(np.asarray(scores), np.asarray(rscores),
                                   rtol=2e-5, atol=1e-6)
        # Score ties can flip the pick under float reassociation; require
        # agreement wherever the margin is meaningful.
        margin = np.abs(np.asarray(rscores[:, 0] - rscores[:, 1]))
        firm = margin > 1e-5
        assert (np.asarray(choice)[firm] == np.asarray(rchoice)[firm]).all()

    def test_identical_candidates(self):
        """cand A == cand B (Algorithm 1 samples with replacement)."""
        N = 10
        rng = np.random.RandomState(1)
        cand = jnp.full((8, 2), 3, jnp.int32)
        r = jnp.asarray(rng.rand(8, 2).astype(np.float32))
        d = jnp.ones((8, 2))
        L = jnp.asarray(rng.rand(N, 2).astype(np.float32))
        D = jnp.ones(N)
        C = jnp.ones((N, 2)) * 10
        choice, scores = dodoor_choice(r, cand, d, L, D, C, 0.5, block_t=8)
        assert (np.asarray(choice) == 3).all()
        np.testing.assert_allclose(np.asarray(scores[:, 0]),
                                   np.asarray(scores[:, 1]), rtol=1e-6)


class TestDodoorFusedMegakernel:
    """The fused sample→score→select megakernel: in-kernel threefry PRNG,
    prefilter mask from the table's capacity columns, inverse-CDF pick."""

    def _inputs(self, T, N, seed=0):
        rng = np.random.RandomState(seed)
        base = jax.random.PRNGKey(seed)
        keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(jnp.arange(T))
        r = jnp.asarray(rng.rand(T, 2).astype(np.float32) * 8)
        d = jnp.asarray(rng.rand(T, N).astype(np.float32) * 1000)
        L = jnp.asarray(rng.rand(N, 2).astype(np.float32) * 50)
        D = jnp.asarray(rng.rand(N).astype(np.float32) * 5000)
        C = jnp.asarray(8.0 + rng.rand(N, 2).astype(np.float32) * 100)
        return keys, r, d, L, D, C

    @pytest.mark.parametrize("T,N,alpha", [(16, 20, 0.5), (300, 100, 0.5),
                                           (257, 64, 0.0), (64, 500, 1.0)])
    def test_matches_fused_ref(self, T, N, alpha):
        """Candidate draws and choices are bit-exact vs the jnp reference
        (which itself delegates draws to sample_feasible_batch); scores to
        the documented 1-ulp FMA caveat."""
        keys, r, d, L, D, C = self._inputs(T, N, seed=T)
        choice, cand, scores = dodoor_fused(keys, r, d, L, D, C, alpha,
                                            block_t=64)
        rchoice, rcand, rscores = dodoor_fused_ref(keys, r, d, L, D, C,
                                                   alpha)
        assert (np.asarray(cand) == np.asarray(rcand)).all()
        assert (np.asarray(choice) == np.asarray(rchoice)).all()
        np.testing.assert_allclose(np.asarray(scores), np.asarray(rscores),
                                   rtol=2e-5, atol=1e-6)

    @pytest.mark.parametrize("T", (1, 9, 12, 137))
    def test_partial_block_padding(self, T):
        """T not a multiple of block_t: padded rows (zero demand, zero
        keys) must not leak into the first T outputs."""
        keys, r, d, L, D, C = self._inputs(T, 20, seed=T)
        choice, cand, _ = dodoor_fused(keys, r, d, L, D, C, 0.5, block_t=8)
        rchoice, rcand, _ = dodoor_fused_ref(keys, r, d, L, D, C, 0.5)
        assert choice.shape == (T,)
        assert (np.asarray(cand) == np.asarray(rcand)).all()
        assert (np.asarray(choice) == np.asarray(rchoice)).all()

    def test_infeasible_fallback_uniform_over_all(self):
        """No feasible server → uniform over the whole fleet (submission
        is never rejected), with the exact sample_feasible draws."""
        from repro.core.prefilter import feasible_mask, sample_feasible_batch
        T, N = 32, 7
        keys, _, d, L, D, C = self._inputs(T, N, seed=2)
        r = jnp.full((T, 2), 1e6, jnp.float32)       # exceeds every C
        choice, cand, _ = dodoor_fused(keys, r, d, L, D, C, 0.5)
        ref_cand = sample_feasible_batch(keys, feasible_mask(r, C), 2)
        assert (np.asarray(cand) == np.asarray(ref_cand)).all()
        assert (np.asarray(cand) >= 0).all() and (np.asarray(cand) < N).all()
        assert np.isin(np.asarray(choice),
                       np.asarray(cand)).all()

    def test_mixed_feasibility_rows(self):
        """Some tasks feasible on a strict subset of servers: the in-kernel
        prefix-sum pick must respect each row's own mask."""
        from repro.core.prefilter import feasible_mask
        T, N = 64, 10
        keys, _, d, L, D, C = self._inputs(T, N, seed=3)
        rng = np.random.RandomState(3)
        # Half the tasks demand more than the smaller servers offer.
        r = jnp.asarray(
            np.where(rng.rand(T, 1) < 0.5, 4.0, 60.0).astype(np.float32)
            * np.ones((1, 2), np.float32))
        C = C.at[:5].set(jnp.asarray([[8.0, 8.0]] * 5))
        choice, cand, _ = dodoor_fused(keys, r, d, L, D, C, 0.5)
        mask = np.asarray(feasible_mask(r, C))
        feas_rows = mask.any(axis=1)
        picked = np.take_along_axis(mask, np.asarray(cand), axis=1)
        assert picked[feas_rows].all()


class TestDodoorFusedMaskedMegakernel:
    """The masked-sampling megakernel variant (ISSUE 5): a per-task
    availability plane — the scenario engine's down-window mask — is ANDed
    into the in-kernel prefilter, with draws pinned bit-for-bit against
    the two-stage masked ``sample_feasible_batch`` oracle."""

    def _inputs(self, T, N, seed=0):
        rng = np.random.RandomState(seed)
        base = jax.random.PRNGKey(seed)
        keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(jnp.arange(T))
        r = jnp.asarray(rng.rand(T, 2).astype(np.float32) * 8)
        d = jnp.asarray(rng.rand(T, N).astype(np.float32) * 1000)
        L = jnp.asarray(rng.rand(N, 2).astype(np.float32) * 50)
        D = jnp.asarray(rng.rand(N).astype(np.float32) * 5000)
        C = jnp.asarray(8.0 + rng.rand(N, 2).astype(np.float32) * 100)
        avail = jnp.asarray(rng.rand(T, N) > 0.4)
        return keys, r, d, L, D, C, avail

    @pytest.mark.parametrize("T,N", [(16, 20), (300, 100), (137, 64)])
    def test_draws_pinned_to_masked_oracle(self, T, N):
        """Candidates and choice are bit-exact vs the jnp reference, whose
        draws delegate to sample_feasible_batch on the intersected mask —
        the engine-level contract that makes use_kernel legal under down
        windows."""
        from repro.core.prefilter import feasible_mask, sample_feasible_batch
        keys, r, d, L, D, C, avail = self._inputs(T, N, seed=T)
        choice, cand, scores = dodoor_fused(keys, r, d, L, D, C, 0.5,
                                            avail=avail, block_t=64)
        rchoice, rcand, rscores = dodoor_fused_ref(keys, r, d, L, D, C,
                                                   0.5, avail=avail)
        assert (np.asarray(cand) == np.asarray(rcand)).all()
        assert (np.asarray(choice) == np.asarray(rchoice)).all()
        np.testing.assert_allclose(np.asarray(scores), np.asarray(rscores),
                                   rtol=2e-5, atol=1e-6)
        # and directly against the prefilter layer's two-stage draws
        two_stage = sample_feasible_batch(
            keys, feasible_mask(r, C) & avail, 2)
        assert (np.asarray(cand) == np.asarray(two_stage)).all()

    def test_all_true_mask_equals_unmasked_kernel(self):
        """avail ≡ 1 must reproduce the unmasked program bit-for-bit (the
        engine always routes through the masked form; scenario-free runs
        may not shift a single draw)."""
        keys, r, d, L, D, C, _ = self._inputs(128, 32, seed=5)
        ones = jnp.ones((128, 32), bool)
        c0, k0, s0 = dodoor_fused(keys, r, d, L, D, C, 0.5)
        c1, k1, s1 = dodoor_fused(keys, r, d, L, D, C, 0.5, avail=ones)
        assert (np.asarray(k0) == np.asarray(k1)).all()
        assert (np.asarray(c0) == np.asarray(c1)).all()
        np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))

    def test_all_down_fallback_uniform(self):
        """No available server → the same uniform-over-all substitution as
        an all-infeasible task (submission is never rejected)."""
        from repro.core.prefilter import feasible_mask, sample_feasible_batch
        T, N = 32, 9
        keys, r, d, L, D, C, _ = self._inputs(T, N, seed=2)
        none = jnp.zeros((T, N), bool)
        choice, cand, _ = dodoor_fused(keys, r, d, L, D, C, 0.5, avail=none)
        ref_cand = sample_feasible_batch(keys, feasible_mask(r, C) & none, 2)
        assert (np.asarray(cand) == np.asarray(ref_cand)).all()
        assert (np.asarray(cand) >= 0).all() and (np.asarray(cand) < N).all()

    @pytest.mark.parametrize("T", (1, 9, 137))
    def test_partial_block_padding(self, T):
        """T not a multiple of block_t: padded avail rows are all-ones and
        must not leak into the first T outputs."""
        keys, r, d, L, D, C, avail = self._inputs(T, 20, seed=T)
        choice, cand, _ = dodoor_fused(keys, r, d, L, D, C, 0.5,
                                       avail=avail, block_t=8)
        rchoice, rcand, _ = dodoor_fused_ref(keys, r, d, L, D, C, 0.5,
                                             avail=avail)
        assert choice.shape == (T,)
        assert (np.asarray(cand) == np.asarray(rcand)).all()
        assert (np.asarray(choice) == np.asarray(rchoice)).all()


class TestDodoorFusedSparseMegakernel:
    """The sparse-candidate-gather megakernel (ISSUE 6 tentpole): the
    dense per-task ``d [T, N]`` duration plane is replaced by the
    factorized ``d_types [T, TT]`` + server→type map, with node_type
    riding the server table as one extra column and each candidate's
    duration resolved by a TT-wide one-hot pick after the row gather.
    Draws stay bit-exact vs ``sample_feasible_batch``; choices and
    candidates are exactly the dense megakernel's on the expanded d."""

    def _inputs(self, T, N, TT=4, seed=0):
        rng = np.random.RandomState(seed)
        base = jax.random.PRNGKey(seed)
        keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(jnp.arange(T))
        r = jnp.asarray(rng.rand(T, 2).astype(np.float32) * 8)
        d_types = jnp.asarray(rng.rand(T, TT).astype(np.float32) * 1000)
        node_type = jnp.asarray(rng.randint(0, TT, N), jnp.int32)
        L = jnp.asarray(rng.rand(N, 2).astype(np.float32) * 50)
        D = jnp.asarray(rng.rand(N).astype(np.float32) * 5000)
        C = jnp.asarray(8.0 + rng.rand(N, 2).astype(np.float32) * 100)
        avail = jnp.asarray(rng.rand(T, N) > 0.4)
        return keys, r, d_types, node_type, L, D, C, avail

    @pytest.mark.parametrize("T,N,alpha", [(16, 20, 0.5), (300, 100, 0.5),
                                           (257, 64, 0.0), (64, 500, 1.0)])
    def test_matches_sparse_ref(self, T, N, alpha):
        """Candidates and choice bit-exact vs the jnp oracle (which
        expands d and delegates to the dense reference); scores to the
        documented 1-ulp FMA caveat."""
        keys, r, dt, nt, L, D, C, _ = self._inputs(T, N, seed=T)
        choice, cand, scores = dodoor_fused_sparse(keys, r, dt, nt, L, D, C,
                                                   alpha, block_t=64)
        rchoice, rcand, rscores = dodoor_fused_sparse_ref(keys, r, dt, nt,
                                                          L, D, C, alpha)
        assert (np.asarray(cand) == np.asarray(rcand)).all()
        assert (np.asarray(choice) == np.asarray(rchoice)).all()
        np.testing.assert_allclose(np.asarray(scores), np.asarray(rscores),
                                   rtol=2e-5, atol=1e-6)

    @pytest.mark.parametrize("T,N", [(64, 33), (300, 100)])
    def test_matches_dense_megakernel_exactly(self, T, N):
        """On the expanded ``d[t, j] = d_types[t, node_type[j]]`` plane
        the dense and sparse kernels are the *same program* observationally
        — candidates, choice, and scores all bit-identical (the gathered
        duration is the same float either way)."""
        keys, r, dt, nt, L, D, C, _ = self._inputs(T, N, seed=T + 1)
        d = dt[:, nt]
        c0, k0, s0 = dodoor_fused(keys, r, d, L, D, C, 0.5, block_t=64)
        c1, k1, s1 = dodoor_fused_sparse(keys, r, dt, nt, L, D, C, 0.5,
                                         block_t=64)
        assert (np.asarray(k0) == np.asarray(k1)).all()
        assert (np.asarray(c0) == np.asarray(c1)).all()
        np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))

    @pytest.mark.parametrize("typed", (False, True))
    def test_uniform_pair_is_jax_uniform(self, typed):
        """The kernel's two uniforms are ``jax.random.uniform(k, (2,))``
        bit for bit, for raw uint32 and typed keys, under the installed
        JAX's default threefry layout."""
        from repro.kernels.dodoor_choice.kernel import _uniform_pair
        make = jax.random.key if typed else jax.random.PRNGKey
        keys = jax.vmap(lambda i: jax.random.fold_in(make(3), i))(
            jnp.arange(64))
        words = jax.random.key_data(keys) if typed else keys
        u0, u1 = _uniform_pair(words[:, 0], words[:, 1])
        want = jax.vmap(lambda k: jax.random.uniform(k, (2,)))(keys)
        np.testing.assert_array_equal(np.stack([u0, u1], axis=1),
                                      np.asarray(want))

    def test_draws_pinned_to_two_stage_sampler(self):
        """The in-kernel draws ARE sample_feasible_batch's — the ISSUE 6
        acceptance pin at n ≤ 10³."""
        from repro.core.prefilter import feasible_mask, sample_feasible_batch
        T, N = 128, 1000
        keys, r, dt, nt, L, D, C, _ = self._inputs(T, N, seed=9)
        _, cand, _ = dodoor_fused_sparse(keys, r, dt, nt, L, D, C, 0.5)
        two_stage = sample_feasible_batch(keys, feasible_mask(r, C), 2)
        assert (np.asarray(cand) == np.asarray(two_stage)).all()

    @pytest.mark.parametrize("T", (1, 9, 137))
    def test_partial_block_padding(self, T):
        """T not a multiple of block_t: padded rows must not leak."""
        keys, r, dt, nt, L, D, C, _ = self._inputs(T, 20, seed=T)
        choice, cand, _ = dodoor_fused_sparse(keys, r, dt, nt, L, D, C, 0.5,
                                              block_t=8)
        rchoice, rcand, _ = dodoor_fused_sparse_ref(keys, r, dt, nt, L, D,
                                                    C, 0.5)
        assert choice.shape == (T,)
        assert (np.asarray(cand) == np.asarray(rcand)).all()
        assert (np.asarray(choice) == np.asarray(rchoice)).all()

    def test_masked_variant_pinned_and_all_true_inert(self):
        """The masked sparse kernel draws from the intersected mask
        bit-exactly, and an all-true mask reproduces the unmasked program
        (the study planner's static masked/unmasked selection relies on
        this)."""
        from repro.core.prefilter import feasible_mask, sample_feasible_batch
        T, N = 137, 40
        keys, r, dt, nt, L, D, C, avail = self._inputs(T, N, seed=6)
        choice, cand, scores = dodoor_fused_sparse(keys, r, dt, nt, L, D, C,
                                                   0.5, avail=avail,
                                                   block_t=64)
        rchoice, rcand, _ = dodoor_fused_sparse_ref(keys, r, dt, nt, L, D,
                                                    C, 0.5, avail=avail)
        two_stage = sample_feasible_batch(keys,
                                          feasible_mask(r, C) & avail, 2)
        assert (np.asarray(cand) == np.asarray(rcand)).all()
        assert (np.asarray(cand) == np.asarray(two_stage)).all()
        assert (np.asarray(choice) == np.asarray(rchoice)).all()
        ones = jnp.ones((T, N), bool)
        c0, k0, s0 = dodoor_fused_sparse(keys, r, dt, nt, L, D, C, 0.5)
        c1, k1, s1 = dodoor_fused_sparse(keys, r, dt, nt, L, D, C, 0.5,
                                         avail=ones)
        assert (np.asarray(k0) == np.asarray(k1)).all()
        assert (np.asarray(c0) == np.asarray(c1)).all()
        np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))

    def test_all_down_fallback_uniform(self):
        """No available server → uniform-over-all substitution, exactly
        the two-stage sampler's."""
        from repro.core.prefilter import feasible_mask, sample_feasible_batch
        T, N = 32, 9
        keys, r, dt, nt, L, D, C, _ = self._inputs(T, N, seed=2)
        none = jnp.zeros((T, N), bool)
        _, cand, _ = dodoor_fused_sparse(keys, r, dt, nt, L, D, C, 0.5,
                                         avail=none)
        ref_cand = sample_feasible_batch(keys, feasible_mask(r, C) & none, 2)
        assert (np.asarray(cand) == np.asarray(ref_cand)).all()
        assert (np.asarray(cand) >= 0).all() and (np.asarray(cand) < N).all()


class TestDodoorFusedSparseLocality:
    """The locality gather (ISSUE 8): ``psrv``/``pbytes`` per-task parent
    planes stream into the sparse megakernel and each candidate's score is
    charged ``gamma_bw`` per MB of parent output on a *different* server.
    ``gamma_bw = 0`` must be bit-identical to running without the planes
    (the frontier loop's pinned contract), and γ > 0 must match the jnp
    oracle, which applies the same penalty in the same reduction order."""

    def _inputs(self, T, N, P=3, TT=4, seed=0):
        rng = np.random.RandomState(seed)
        base = jax.random.PRNGKey(seed)
        keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(jnp.arange(T))
        r = jnp.asarray(rng.rand(T, 2).astype(np.float32) * 8)
        d_types = jnp.asarray(rng.rand(T, TT).astype(np.float32) * 1000)
        node_type = jnp.asarray(rng.randint(0, TT, N), jnp.int32)
        L = jnp.asarray(rng.rand(N, 2).astype(np.float32) * 50)
        D = jnp.asarray(rng.rand(N).astype(np.float32) * 5000)
        C = jnp.asarray(8.0 + rng.rand(N, 2).astype(np.float32) * 100)
        avail = jnp.asarray(rng.rand(T, N) > 0.4)
        # Parent planes with −1 padding holes, like a real DagPlan wave.
        psrv = rng.randint(-1, N, size=(T, P)).astype(np.int32)
        pbytes = np.where(psrv >= 0,
                          rng.rand(T, P) * 64.0, 0.0).astype(np.float32)
        return (keys, r, d_types, node_type, L, D, C, avail,
                jnp.asarray(psrv), jnp.asarray(pbytes))

    @pytest.mark.parametrize("masked", (False, True))
    def test_gamma_zero_bitwise_inert(self, masked):
        """γ = 0 with the locality planes present reproduces the
        plane-free program bitwise — choice, candidates, AND scores —
        for both the unmasked and masked-sampling variants."""
        T, N = 137, 40
        keys, r, dt, nt, L, D, C, avail, psrv, pbytes = self._inputs(
            T, N, seed=11)
        av = avail if masked else None
        c0, k0, s0 = dodoor_fused_sparse(keys, r, dt, nt, L, D, C, 0.5,
                                         avail=av, block_t=64)
        c1, k1, s1 = dodoor_fused_sparse(keys, r, dt, nt, L, D, C, 0.5,
                                         avail=av, psrv=psrv, pbytes=pbytes,
                                         gamma_bw=0.0, block_t=64)
        assert (np.asarray(k0) == np.asarray(k1)).all()
        assert (np.asarray(c0) == np.asarray(c1)).all()
        np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))

    @pytest.mark.parametrize("T,N,gamma", [(64, 33, 0.25), (300, 100, 2.0),
                                           (137, 40, 0.5)])
    def test_matches_ref_with_penalty(self, T, N, gamma):
        """γ > 0: candidates/choice bit-exact vs the jnp oracle carrying
        the same penalty; scores to the 1-ulp FMA caveat."""
        keys, r, dt, nt, L, D, C, _, psrv, pbytes = self._inputs(
            T, N, seed=T)
        choice, cand, scores = dodoor_fused_sparse(
            keys, r, dt, nt, L, D, C, 0.5, psrv=psrv, pbytes=pbytes,
            gamma_bw=gamma, block_t=64)
        rchoice, rcand, rscores = dodoor_fused_sparse_ref(
            keys, r, dt, nt, L, D, C, 0.5, psrv=psrv, pbytes=pbytes,
            gamma_bw=gamma)
        assert (np.asarray(cand) == np.asarray(rcand)).all()
        assert (np.asarray(choice) == np.asarray(rchoice)).all()
        np.testing.assert_allclose(np.asarray(scores), np.asarray(rscores),
                                   rtol=2e-5, atol=1e-4)

    def test_masked_variant_with_penalty(self):
        """Penalty and masked sampling compose: draws from the intersected
        mask, scores carrying the γ charge, all pinned to the oracle."""
        T, N = 96, 30
        keys, r, dt, nt, L, D, C, avail, psrv, pbytes = self._inputs(
            T, N, seed=5)
        choice, cand, scores = dodoor_fused_sparse(
            keys, r, dt, nt, L, D, C, 0.5, avail=avail, psrv=psrv,
            pbytes=pbytes, gamma_bw=1.5, block_t=32)
        rchoice, rcand, rscores = dodoor_fused_sparse_ref(
            keys, r, dt, nt, L, D, C, 0.5, avail=avail, psrv=psrv,
            pbytes=pbytes, gamma_bw=1.5)
        assert (np.asarray(cand) == np.asarray(rcand)).all()
        assert (np.asarray(choice) == np.asarray(rchoice)).all()
        np.testing.assert_allclose(np.asarray(scores), np.asarray(rscores),
                                   rtol=2e-5, atol=1e-4)

    def test_manual_penalty(self):
        """One hand-checked row: the penalty is exactly γ_bw · Σ bytes of
        parents on a different server than the candidate."""
        T, N = 8, 12
        keys, r, dt, nt, L, D, C, _, _, _ = self._inputs(T, N, seed=3)
        _, cand, s_plain = dodoor_fused_sparse(keys, r, dt, nt, L, D, C,
                                               0.5, block_t=8)
        cand = np.asarray(cand)
        # Parent 0 sits on candidate A's server (local for A, remote for
        # B); parent 1 is a padding hole (−1, zero bytes).
        psrv = np.stack([cand[:, 0], np.full(T, -1)], axis=1).astype(np.int32)
        pbytes = np.stack([np.full(T, 10.0), np.zeros(T)],
                          axis=1).astype(np.float32)
        gamma = 0.75
        _, _, s_loc = dodoor_fused_sparse(
            keys, r, dt, nt, L, D, C, 0.5, psrv=jnp.asarray(psrv),
            pbytes=jnp.asarray(pbytes), gamma_bw=gamma, block_t=8)
        s_plain, s_loc = np.asarray(s_plain), np.asarray(s_loc)
        remote_b = (cand[:, 1] != cand[:, 0]).astype(np.float32)
        np.testing.assert_allclose(s_loc[:, 0], s_plain[:, 0], rtol=1e-6)
        np.testing.assert_allclose(
            s_loc[:, 1], s_plain[:, 1] + gamma * 10.0 * remote_b, rtol=1e-5)

    def test_psrv_without_pbytes_raises(self):
        T, N = 8, 12
        keys, r, dt, nt, L, D, C, _, psrv, _ = self._inputs(T, N, seed=4)
        with pytest.raises(ValueError, match="together"):
            dodoor_fused_sparse(keys, r, dt, nt, L, D, C, 0.5, psrv=psrv)


class TestDodoorChoiceEnginePath:
    """The kernel as the batched engine consumes it (ISSUE 1 satellite):
    Algorithm-1 tie-breaking, the padded tail of a partial decision block,
    and the interpret=True CPU path the engine runs on."""

    def _inputs(self, T, N, seed=0):
        rng = np.random.RandomState(seed)
        r = jnp.asarray(rng.rand(T, 2).astype(np.float32) * 8)
        cand = jnp.asarray(rng.randint(0, N, size=(T, 2)).astype(np.int32))
        d_cand = jnp.asarray(rng.rand(T, 2).astype(np.float32) * 1000)
        L = jnp.asarray(rng.rand(N, 2).astype(np.float32) * 50)
        D = jnp.asarray(rng.rand(N).astype(np.float32) * 5000)
        C = jnp.asarray(8.0 + rng.rand(N, 2).astype(np.float32) * 100)
        return r, cand, d_cand, L, D, C

    def test_tie_breaks_keep_candidate_a(self):
        """Exact score ties (identical server rows) must resolve to A —
        Algorithm 1 line 11 only switches on a strict '>'."""
        N, T = 6, 16
        rng = np.random.RandomState(2)
        r = jnp.asarray(rng.rand(T, 2).astype(np.float32))
        # Servers 1 and 4 share identical (L, D, C) rows → exact tie.
        L = jnp.asarray(rng.rand(N, 2).astype(np.float32) * 20)
        L = L.at[4].set(L[1])
        D = jnp.asarray(rng.rand(N).astype(np.float32) * 100)
        D = D.at[4].set(D[1])
        C = jnp.ones((N, 2)) * 30
        cand = jnp.tile(jnp.array([[1, 4]], jnp.int32), (T, 1))
        d_cand = jnp.ones((T, 2)) * 7.0
        choice, scores = dodoor_choice(r, cand, d_cand, L, D, C, 0.5,
                                       block_t=8)
        np.testing.assert_allclose(np.asarray(scores[:, 0]),
                                   np.asarray(scores[:, 1]))
        assert (np.asarray(choice) == 1).all()       # ties keep A

    @pytest.mark.parametrize("T", (1, 9, 12, 137))
    def test_partial_block_padding(self, T):
        """T not a multiple of block_t: the padded tail must neither corrupt
        the first T outputs nor leak padded rows into them (the engine's
        last decision block is exactly this shape)."""
        r, cand, d_cand, L, D, C = self._inputs(T, 20, seed=T)
        choice, scores = dodoor_choice(r, cand, d_cand, L, D, C, 0.5,
                                       block_t=8)
        rchoice, rscores = dodoor_choice_ref(r, cand, d_cand, L, D, C, 0.5)
        assert choice.shape == (T,)
        np.testing.assert_allclose(np.asarray(scores), np.asarray(rscores),
                                   rtol=2e-5, atol=1e-6)
        margin = np.abs(np.asarray(rscores[:, 0] - rscores[:, 1]))
        firm = margin > 1e-5
        assert (np.asarray(choice)[firm] == np.asarray(rchoice)[firm]).all()

    def test_interpret_cpu_path_matches_policy_layer(self):
        """dodoor_choice_batch(use_kernel=True, interpret=True) — the exact
        call the batched engine makes — agrees with the jnp path."""
        from repro.core import SchedulerView, dodoor_choice_batch
        r, cand, d_cand, L, D, C = self._inputs(50, 20, seed=5)
        view = SchedulerView(L=L, D=D, rif=jnp.zeros(20), C=C)
        jnp_choice = dodoor_choice_batch(r, cand, d_cand, view, 0.5,
                                         use_kernel=False)
        k_choice = dodoor_choice_batch(r, cand, d_cand, view, 0.5,
                                       use_kernel=True, interpret=True)
        assert (np.asarray(jnp_choice) == np.asarray(k_choice)).all()

    def test_engine_block_sizes_cover_kernel_tiles(self):
        """Engine-realistic block sizes b ∈ {1, 10, 50} all round-trip
        through the kernel's tile clamp (block_t is shrunk to cover b)."""
        for b in (1, 10, 50):
            r, cand, d_cand, L, D, C = self._inputs(b, 20, seed=b)
            choice, _ = dodoor_choice(r, cand, d_cand, L, D, C, 0.5)
            rchoice, rscores = dodoor_choice_ref(r, cand, d_cand, L, D, C,
                                                 0.5)
            margin = np.abs(np.asarray(rscores[:, 0] - rscores[:, 1]))
            firm = margin > 1e-5
            assert (np.asarray(choice)[firm]
                    == np.asarray(rchoice)[firm]).all()


class TestFlashAttention:
    @pytest.mark.parametrize("B,H,Hkv,Lq,Lk,D,causal,window", [
        (1, 2, 2, 128, 128, 64, True, None),      # square causal
        (2, 4, 2, 128, 128, 64, True, None),      # GQA 2:1
        (1, 8, 2, 64, 256, 64, True, None),       # Lq < Lk (chunked prefill)
        (1, 2, 1, 1, 384, 64, True, None),        # decode: 1 query vs cache
        (1, 2, 2, 128, 256, 64, True, 64),        # local window
        (1, 2, 2, 100, 200, 32, True, None),      # ragged (padding path)
        (1, 2, 2, 64, 64, 128, False, None),      # non-causal (cross-attn)
    ])
    def test_matches_ref(self, B, H, Hkv, Lq, Lk, D, causal, window):
        rng = np.random.RandomState(Lq + Lk)
        q = jnp.asarray(rng.randn(B, H, Lq, D).astype(np.float32)) * 0.5
        k = jnp.asarray(rng.randn(B, Hkv, Lk, D).astype(np.float32)) * 0.5
        v = jnp.asarray(rng.randn(B, Hkv, Lk, D).astype(np.float32))
        out = flash_attention(q, k, v, causal=causal, window=window,
                              block_q=64, block_k=64)
        ref = attention_ref(q, k, v, causal=causal, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_bf16_inputs(self):
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(1, 2, 128, 64), jnp.bfloat16)
        k = jnp.asarray(rng.randn(1, 2, 128, 64), jnp.bfloat16)
        v = jnp.asarray(rng.randn(1, 2, 128, 64), jnp.bfloat16)
        out = flash_attention(q, k, v, block_q=64, block_k=64)
        ref = attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                            v.astype(jnp.float32))
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), rtol=0.05, atol=0.05)


class TestSSDChunk:
    @pytest.mark.parametrize("B,L,H,P,G,S,chunk", [
        (1, 64, 2, 16, 1, 32, 32),
        (2, 128, 4, 32, 2, 64, 64),
        (1, 256, 2, 64, 1, 128, 64),    # mamba2-1.3b head geometry
        (1, 64, 4, 16, 4, 16, 16),      # G == H (ungrouped)
    ])
    def test_matches_recurrence(self, B, L, H, P, G, S, chunk):
        rng = np.random.RandomState(L + S)
        x = jnp.asarray(rng.randn(B, L, H, P).astype(np.float32)) * 0.5
        dt = jnp.asarray(0.01 + rng.rand(B, L, H).astype(np.float32))
        A = jnp.asarray(-(0.1 + rng.rand(H).astype(np.float32)))
        Bm = jnp.asarray(rng.randn(B, L, G, S).astype(np.float32)) * 0.3
        Cm = jnp.asarray(rng.randn(B, L, G, S).astype(np.float32)) * 0.3
        y, h = ssd(x, dt, A, Bm, Cm, chunk=chunk)
        y_ref, h_ref = ssd_ref(x, dt, A, Bm, Cm)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                                   rtol=2e-4, atol=2e-4)

    def test_initial_state_threading(self):
        """Splitting a sequence across two ssd() calls must equal one call —
        the property serving (stateful decode) depends on."""
        rng = np.random.RandomState(7)
        B, L, H, P, G, S = 1, 128, 2, 16, 1, 32
        x = jnp.asarray(rng.randn(B, L, H, P).astype(np.float32)) * 0.5
        dt = jnp.asarray(0.01 + rng.rand(B, L, H).astype(np.float32))
        A = jnp.asarray(-(0.1 + rng.rand(H).astype(np.float32)))
        Bm = jnp.asarray(rng.randn(B, L, G, S).astype(np.float32)) * 0.3
        Cm = jnp.asarray(rng.randn(B, L, G, S).astype(np.float32)) * 0.3
        y_full, h_full = ssd(x, dt, A, Bm, Cm, chunk=32)
        y1, h1 = ssd(x[:, :64], dt[:, :64], A, Bm[:, :64], Cm[:, :64],
                     chunk=32)
        y2, h2 = ssd(x[:, 64:], dt[:, 64:], A, Bm[:, 64:], Cm[:, 64:],
                     h0=h1, chunk=32)
        np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                                   np.asarray(y_full), rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full),
                                   rtol=2e-4, atol=2e-4)

    def test_decode_step_matches_scan(self):
        rng = np.random.RandomState(9)
        B, H, P, G, S = 2, 2, 16, 1, 32
        A = jnp.asarray(-(0.1 + rng.rand(H).astype(np.float32)))
        h = jnp.zeros((B, H, S, P))
        ys = []
        xs = jnp.asarray(rng.randn(B, 8, H, P).astype(np.float32))
        dts = jnp.asarray(0.01 + rng.rand(B, 8, H).astype(np.float32))
        Bms = jnp.asarray(rng.randn(B, 8, G, S).astype(np.float32)) * 0.3
        Cms = jnp.asarray(rng.randn(B, 8, G, S).astype(np.float32)) * 0.3
        for t in range(8):
            y, h = ssd_decode_step(xs[:, t], dts[:, t], A, Bms[:, t],
                                   Cms[:, t], h)
            ys.append(y)
        y_seq = jnp.stack(ys, axis=1)
        y_ref, h_ref = ssd_ref(xs, dts, A, Bms, Cms)
        np.testing.assert_allclose(np.asarray(y_seq), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                                   rtol=1e-4, atol=1e-5)


class TestBlockTAutotune:
    """`autotune_block_t` sweeps megakernel tile sizes and reports the
    measured curve — the benchmark harness persists it at the gate-point
    shape, so the helper's output contract is pinned here."""

    def test_curve_shape_and_winner(self):
        from repro.kernels.dodoor_choice import autotune_block_t
        out = autotune_block_t(48, 12, candidates=(16, 32, 64), reps=1)
        assert out["T"] == 48 and out["N"] == 12
        assert [r["block_t"] for r in out["curve"]] == [16, 32, 64]
        assert out["best_block_t"] in (16, 32, 64)
        assert out["best_ms"] == min(r["ms"] for r in out["curve"])

    def test_clamped_candidates_share_one_measurement(self):
        """Candidates that clamp to the same effective tile (T caps the
        tile) must report identical timings — the sweep runs each
        distinct program once."""
        from repro.kernels.dodoor_choice import autotune_block_t
        out = autotune_block_t(24, 10, candidates=(64, 128), reps=1)
        rows = out["curve"]
        assert rows[0]["effective_block_t"] == rows[1]["effective_block_t"]
        assert rows[0]["ms"] == rows[1]["ms"]
