"""Compile the served path's TPU programs for a described v5e chip.

Nothing runs: the TPU compiler that ships with jaxlib lowers each program
for a chip that is described, not attached, and raises what Mosaic or XLA
would raise on the chip (unsupported primitives, illegal block shapes,
scoped-VMEM overflow).  The sparse megakernel is compiled alone, unmasked
and masked, at the decision-block and fleet widths users run, and inside
the jitted ``_serve_step`` for ``dodoor`` at the testbed width and at
n = 10⁴, and the four-mini-cluster step of the 10⁵-server cell over the
2x2 host's four chips.  Each compiled program must contain the Mosaic
kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, so that only the
test process that runs this file loads the TPU library.  The persistent
compilation cache is off around these compiles: an entry compiled for a
described chip cannot be read back without one.
"""
import re

import pytest

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, SingleDeviceSharding

from repro.kernels.dodoor_choice import dodoor_fused_sparse
from repro.serve import DecisionService
from repro.sim import EngineConfig, make_scaled, make_testbed


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices), ("parts",))


@pytest.mark.parametrize("masked", (False, True))
@pytest.mark.parametrize("T,N", [(56, 100), (56, 10_000), (512, 100),
                                 (512, 10_000)])
def test_sparse_kernel_compiles(one_chip, T, N, masked):
    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def run(keys, r, dt, nt, L, D, C, avail=None):
        return dodoor_fused_sparse(keys, r, dt, nt, L, D, C, 0.5,
                                   avail=avail, interpret=False)

    args = [S((T, 2), jnp.uint32), S((T, 2), jnp.float32),
            S((T, 4), jnp.float32), S((N,), jnp.int32),
            S((N, 2), jnp.float32), S((N,), jnp.float32),
            S((N, 2), jnp.float32)]
    kw = {"avail": S((T, N), jnp.bool_)} if masked else {}
    compiled = jax.jit(run).lower(*args, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("masked", (False, True))
@pytest.mark.parametrize("n,b", [(100, 50), (10_000, 500)])
def test_serve_step_compiles_with_kernel(one_chip, n, b, masked):
    """The dodoor step the service jits, with the kernel compiled (not
    interpreted), at the paper testbed and at the n = 10⁴ scale point;
    ``masked`` is the form that down windows select."""
    cluster = make_testbed() if n == 100 else make_scaled(n)
    assert cluster.num_servers == n
    cfg = EngineConfig(policy="dodoor", b=b, interpret=False)
    svc = DecisionService(cluster, cfg, use_kernel=True, capacity=b)
    compiled = svc.lower_step(one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_serve_step_stages_are_named(one_chip):
    """The step's named scopes reach the compiled program's ``op_name``
    metadata, which maps a device trace's ops to stages: the commit
    rounds' ``while`` under ``commit``, and the kernel's custom-call,
    named ``dodoor_fused_sparse``, under ``select``."""
    cfg = EngineConfig(policy="dodoor", b=50, interpret=False)
    svc = DecisionService(make_testbed(), cfg, use_kernel=True, capacity=50)
    lines = svc.lower_step(one_chip).compile().as_text().splitlines()

    def op_name(line):
        return re.search(r'op_name="([^"]*)"', line).group(1)

    whiles = [op_name(ln) for ln in lines if " while(" in ln]
    assert whiles and all("/commit/" in n for n in whiles)
    kernel, = [ln for ln in lines if "tpu_custom_call" in ln]
    assert kernel.lstrip().startswith("%dodoor_fused_sparse.")
    assert "/select/" in op_name(kernel)
    assert op_name(kernel).endswith("/dodoor_fused_sparse/pallas_call")


def test_mini_cluster_step_compiles_on_four_chips(four_chips):
    """The step of ``cell100k-azure``: 10⁵ servers as four mini-clusters,
    b = 50,000, one part a chip of the 2x2 host.  Each chip runs the
    one-part program (25,000 servers, 12,500 decisions) with the kernel
    compiled, exchanges nothing with the others, and holds one part's
    carry."""
    cluster = make_scaled(100_000)
    cfg = EngineConfig(policy="dodoor", b=50_000, interpret=False)
    svc = DecisionService(cluster, cfg, use_kernel=True, capacity=50_000,
                          mini_clusters=4)
    compiled = svc.lower_step(four_chips).compile()
    text = compiled.as_text()
    kernel, = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert kernel.lstrip().startswith("%dodoor_fused_sparse.")
    assert "f32[25000,256]" in text and "f32[100000" not in text
    # The stage scopes name each chip's instructions as on one chip, so a
    # trace's ops map to stages.
    whiles = [re.search(r'op_name="([^"]*)"', ln).group(1)
              for ln in text.splitlines() if " while(" in ln]
    assert whiles and all("/commit/" in n for n in whiles)
    assert "/select/" in re.search(r'op_name="([^"]*)"', kernel).group(1)
    assert not re.search(r"all-reduce|all-gather|all-to-all|"
                         r"collective-permute", text)
    carry = sum(np.prod(x.shape) * x.dtype.itemsize
                for x in jax.tree.leaves(svc._carry)) // 4
    assert compiled.memory_analysis().argument_size_in_bytes < 1.1 * carry
