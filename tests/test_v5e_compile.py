"""The fused dense kernels compile for a TPU v5e at the benchmark's widths —
without a chip: the TPU compiler is installed here and compiles for a
DESCRIBED topology, so what Mosaic would refuse on the chip (a tile past
its 16 MiB scoped VMEM, a misaligned slice) is refused in tier-1. Nothing
runs, so nothing here is a time or a result.

One file, the topology described inside a fixture: only one process may
hold the TPU library, and every xdist worker imports every test file.
"""

import jax
import jax.numpy as jnp
import pytest

from libskylark_tpu.base import randgen
from libskylark_tpu.sketch import pallas_dense as pd
from libskylark_tpu.sketch.dense import BLOCK_COLS

ROWS, N = 65536, 8192           # the jlt_apply cell's panel


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler, or the library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(call, one_chip, shape, s_dim, seq_axis, precision, *operands,
             **statics):
    """Lower and compile ``call`` on the operand the dispatch would hand
    it (the tile :func:`effective_plan` resolves); returns the plan and
    the number of Mosaic custom calls in the executable."""
    plan = pd.effective_plan(randgen.Normal(), shape, jnp.float32, s_dim,
                             seq_axis, precision=precision, interpret=True,
                             m_tile=statics.pop("m_tile", None))
    assert plan["kernel"], plan

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n = shape[seq_axis]
    compiled = call.lower(
        arg(shape, jnp.float32), arg((n // BLOCK_COLS, 2), jnp.uint32),
        *[arg(*o) for o in operands],
        s_dim=s_dim, dist_kind="normal", m_tile=plan["m_tile"],
        precision=precision, **statics).compile()
    return plan, compiled.as_text().count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("precision", ["bf16x3", "f32", "bf16gen2", "bf16"])
def test_cell_shape_rowwise_hbm(one_chip, precision):
    """65536 × 8192 → 1024: a generation call and a contraction call."""
    plan, kernels = _compile(pd._fused_call, one_chip, (ROWS, N), 1024, 1,
                             precision, ((), jnp.float32))
    assert plan["operator_residency"] == "hbm" and plan["m_tile"] == 512
    assert kernels == 2


@pytest.mark.parametrize("s_dim,m_tile", [(512, 1024), (2048, None),
                                          (1536, None), (1024, 1024)])
def test_other_widths_rowwise_hbm(one_chip, s_dim, m_tile):
    """Sketch sizes around the headline one, at the tile the plan lets
    through (a requested 1024 at s_dim = 1024 is shrunk: Mosaic refused
    it on the chip, PR 27)."""
    plan, kernels = _compile(pd._fused_call, one_chip, (ROWS, N), s_dim, 1,
                             "bf16x3", ((), jnp.float32), m_tile=m_tile)
    assert plan["operator_residency"] == "hbm" and kernels == 2


def test_cell_shape_rft_cos_hbm(one_chip):
    plan, kernels = _compile(
        pd._fused_call_cos, one_chip, (ROWS, N), 1024, 1, "bf16x3",
        ((1, 1024), jnp.float32), ((1, 1024), jnp.float32),
        inscale=0.5, outscale=0.25)
    assert plan["operator_residency"] == "hbm" and kernels == 2


@pytest.mark.parametrize("shape,seq_axis,call,residency", [
    ((N, ROWS), 0, "_fused_call_cw", "per_tile"),     # columnwise big S
    ((512, N), 1, "_fused_call", "per_tile"),         # one m-tile
    ((4096, 1024), 1, "_fused_call", "vmem"),         # small S
])
def test_generating_kernels_still_compile(one_chip, shape, seq_axis, call,
                                          residency):
    s_dim = 1024 if residency == "per_tile" else 128
    operands = (((), jnp.float32),) if call == "_fused_call" else ()
    plan, kernels = _compile(getattr(pd, call), one_chip, shape, s_dim,
                             seq_axis, "bf16x3", *operands)
    assert plan["operator_residency"] == residency and kernels == 1
