"""The sparse kernel's bytes model against the operands the program's
kernel entry point builds, and the peaks table."""
import jax
import jax.numpy as jnp
import pytest

from harness import roofline
from conftest import BENCH


@pytest.mark.parametrize("T,n", [(500, 10_000), (50, 100)])
def test_bytes_model_matches_operand_shapes(T, n):
    from repro.kernels.dodoor_choice.ops import dodoor_fused_sparse
    TT, K = 4, 2
    keys = jax.ShapeDtypeStruct((T, 2), jnp.uint32)
    r = jax.ShapeDtypeStruct((T, K), jnp.float32)
    dt = jax.ShapeDtypeStruct((T, TT), jnp.float32)
    nt = jax.ShapeDtypeStruct((n,), jnp.int32)
    L = jax.ShapeDtypeStruct((n, K), jnp.float32)
    D = jax.ShapeDtypeStruct((n,), jnp.float32)
    C = jax.ShapeDtypeStruct((n, K), jnp.float32)
    outs = jax.eval_shape(lambda *a: dodoor_fused_sparse(*a, interpret=True),
                          keys, r, dt, nt, L, D, C)
    out_words = sum(int(jnp.size(jnp.zeros(o.shape))) for o in outs)
    in_words = T * (2 + K + TT)
    # The transposed table: L (K), D, 1/sum C^2, C (K), node type -> 7 rows,
    # padded to 8 sublanes and to lanes of 128; read once per call.
    table_words = 8 * (-(-n // 128) * 128)
    assert roofline.sparse_kernel_bytes(T, n, K, TT) == 4 * (
        in_words + out_words + table_words)


def test_table_is_read_once_not_per_tile():
    # 10^4 servers, b = 500: the tile is 24 rows (21 tiles); charging the
    # table per tile would count its bytes 21 times.
    one = roofline.sparse_kernel_bytes(500, 10_000)
    assert one < 2 * 8 * 10_112 * 4


def test_peaks_known_and_unknown():
    import os
    path = os.path.join(BENCH, "peaks.json")
    p = roofline.peaks(path, "TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    assert "source" in p
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks(path, "cpu")
    assert roofline.roofline_pct(819, 1e-9, 819e9) == pytest.approx(100.0)
