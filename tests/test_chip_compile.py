"""Compile the main-path Pallas kernels for a described TPU v5e.

Interpret mode accepts kernels that the chip's compiler (Mosaic) refuses:
row windows not aligned to the sublane tile, grid operands placed in
VMEM, or more fast memory than a kernel may use. These tests compile each
policy at the paper's width (a 1024x9216 interior, ringed 1026x9218), in
f32 and bf16, with the block height the planner chooses, for a v5e that is
described rather than attached. Nothing runs; a compile that passes is not
a chip run.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test workers import
every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.stencil import StencilSpec, jacobi_2d_5pt
from repro.engine import get_device, plan_for
from repro.engine.plan import default_depth
from repro.engine import policies as P

SPEC = jacobi_2d_5pt()
PAPER = (1026, 9218)
# One shard of the paper's grid on a (4,) mesh with t=6: 256 rows plus a
# t*r-deep halo on each side, 268 rows that no multiple of the sublane
# tile divides, so the temporal kernel's last block is ragged.
SHARD = (268, 9228)
CASES = [("shifted", False), ("rowchunk", False), ("dbuf", False),
         ("temporal", False), ("temporal", True)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(one_chip, shape, dtype, policy, masked, depth=8):
    t = depth if policy == "temporal" else None
    plan = plan_for(shape, dtype, SPEC, policy, t=t, device="tpu_v5e",
                    masked=masked)
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    if policy == "temporal":
        m = jax.ShapeDtypeStruct(shape, jnp.bool_, sharding=one_chip)
        fn = jax.jit(lambda u, mk: P.stencil_temporal(
            u, SPEC, t=t, interpret=False, device="tpu_v5e",
            mask=mk if masked else None))
        compiled = fn.lower(x, m).compile()
    else:
        kernel = getattr(P, f"stencil_{policy}")
        fn = jax.jit(lambda u: kernel(u, SPEC, interpret=False,
                                      device="tpu_v5e"))
        compiled = fn.lower(x).compile()
    return plan, compiled


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("policy,masked", CASES,
                         ids=[p + ("_masked" if m else "") for p, m in CASES])
def test_kernel_compiles_at_paper_width(one_chip, policy, masked, dtype):
    plan, compiled = _compile(one_chip, PAPER, dtype, policy, masked)
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    # The device operation carries the kernel's own name.
    assert f"%{P.KERNEL_PREFIX}{policy}." in hlo
    assert plan.vmem_bytes <= get_device("tpu_v5e").fast_memory_bytes
    assert plan.nblocks >= 2  # the paper's grid never fits one block


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_masked_temporal_compiles_on_a_ragged_shard(one_chip, dtype):
    plan, compiled = _compile(one_chip, SHARD, dtype, "temporal",
                              True, depth=6)
    assert plan.nblocks * plan.bm > plan.tiled_rows == 268  # ragged
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("masked", [False, True], ids=["ring", "masked"])
def test_temporal_compiles_as_one_block_of_untiled_height(one_chip, masked):
    """A grid small enough for one block, 26 rows: the temporal kernel
    streams it as one block rounded up to the sublane tile."""
    plan, compiled = _compile(one_chip, (26, 300), jnp.float32, "temporal",
                              masked)
    assert plan.nblocks == 1 and plan.kernel_rows == 32
    assert "tpu_custom_call" in compiled.as_text()


def test_temporal3d_compiles_at_polybench_size(one_chip):
    """PolyBench heat-3d at EXTRALARGE (a 198^3 interior, ringed 200^3,
    f32): the 3-D temporal kernel at the planner's default depth and
    plane block, whose lane axis (200) fills two lane tiles in part."""
    spec = StencilSpec(
        offsets=((0, 0, 0), (-1, 0, 0), (1, 0, 0), (0, -1, 0), (0, 1, 0),
                 (0, 0, -1), (0, 0, 1)),
        weights=(0.25,) + (0.125,) * 6)
    shape = (200, 200, 200)
    t = default_depth(shape, jnp.float32, spec, "tpu_v5e")
    plan = plan_for(shape, jnp.float32, spec, "temporal", t=t,
                    device="tpu_v5e")
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    fn = jax.jit(lambda u: P.stencil_temporal(
        u, spec, t=t, interpret=False, device="tpu_v5e"))
    hlo = fn.lower(x).compile().as_text()
    assert f"%{P.KERNEL_PREFIX}temporal3d." in hlo
    assert plan.vmem_bytes <= get_device("tpu_v5e").fast_memory_bytes
    assert plan.nblocks > 1 and plan.nblocks * plan.bm >= 200
