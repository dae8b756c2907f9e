"""The sketch kernels of the cells — the fused dense ones (the s-tiled
feature-map kernels among them) and the rowwise sparse hash kernel —
compile for a TPU v5e at the benchmark's widths — without a chip: the TPU compiler is installed here and compiles for a
DESCRIBED topology, so what Mosaic would refuse on the chip (a tile past
its 16 MiB scoped VMEM, or past the ``vmem_limit_bytes`` the "hbm"
contraction passes for its grown row tile; a misaligned slice) is refused
in tier-1. Nothing runs, so nothing here is a time or a result.

One file, the topology described inside a fixture: only one process may
hold the TPU library, and every xdist worker imports every test file.
"""

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest

from libskylark_tpu.base import randgen
from libskylark_tpu.sketch import pallas_dense as pd
from libskylark_tpu.sketch import pallas_sparse, sparse_serve
from libskylark_tpu.sketch.dense import BLOCK_COLS

ROWS, N = 65536, 8192           # the jlt_apply cell's panel
# an rft_features_apply panel: examples, inputs (ragged), one feature block
FEATURE_ROWS, FEATURE_N, FEATURE_S = 32768, 440, 16384
# a cwt_sparse_apply block: rows, features, lane_class of its ~19.4 M nonzeros
SPARSE_ROWS, SPARSE_N, SPARSE_LANES = 262144, 47236, 19922944
KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topology():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler, or the library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topology):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topology.devices[0])


@pytest.fixture(scope="module")
def vmem_cap(one_chip):
    """The scope a grown contraction may ask for on the DESCRIBED chip:
    the package's own reading (``pd._vmem_cap`` of
    ``pltpu.get_tpu_info()``) with the described device standing in for
    the attached one — on this box the default device is a CPU, whose
    reading is the default scope and grows nothing."""
    from jax._src.mesh import AbstractDevice
    from jax.sharding import AbstractMesh, use_abstract_mesh

    (device,) = one_chip.device_set
    with use_abstract_mesh(AbstractMesh((), (), abstract_device=AbstractDevice(
            device_kind=device.device_kind, num_cores=device.num_cores))):
        cap = pd._vmem_cap.__wrapped__()    # the reading itself, uncached
    assert pd._VMEM_BUDGET_BYTES < cap < 128 << 20, cap
    return cap


def _executable(call, one_chip, shape, s_dim, seq_axis, precision, *operands,
                vmem_cap=None, **statics):
    """Lower and compile ``call`` on the operand the dispatch would hand
    it (the tile :func:`effective_plan` resolves, under the described
    chip's ``vmem_cap`` where given; the call works its k step and its
    limit out of that tile by the plan's own rule); returns the plan and
    the compiled executable."""
    plan = pd.effective_plan(randgen.Normal(), shape, jnp.float32, s_dim,
                             seq_axis, precision=precision, interpret=True,
                             m_tile=statics.pop("m_tile", None),
                             vmem_cap=vmem_cap)
    assert plan["kernel"], plan

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    # the allocation's key words: the program derives its block-key table
    compiled = call.lower(
        arg(shape, jnp.float32), arg((2,), jnp.uint32),
        *[arg(*o) for o in operands],
        s_dim=s_dim, dist_kind="normal", m_tile=plan["m_tile"],
        precision=precision, **statics).compile()
    return plan, compiled


def _compile(*args, **statics):
    """:func:`_executable`, reduced to the plan and the number of Mosaic
    custom calls in the executable."""
    plan, compiled = _executable(*args, **statics)
    return plan, compiled.as_text().count(KERNEL)


def _scoped_in(compiled) -> int:
    """The most scoped VMEM a Mosaic call of the compiled executable
    holds (its ``scoped_memory_configs`` entry): what Mosaic took of the
    scope it was given, in bytes."""
    found = re.findall(r"scoped_memory_configs.{0,60}?size\D{1,6}(\d+)",
                       compiled.as_text())
    return max(map(int, found), default=0)


def _grown(plan, compiled, vmem_cap, k_cols=2 * BLOCK_COLS):
    """The cells' plan since PR 49: the 2048-row tile at the k step of
    the 512-row one, and a limit on the contraction call that is past
    the default scope, at least what Mosaic took (it compiled) and at
    most the cap."""
    assert (plan["operator_residency"], plan["m_tile"], plan["k_cols"]) == (
        "hbm", 2048, k_cols)
    assert (pd._VMEM_BUDGET_BYTES < _scoped_in(compiled)
            <= plan["vmem_limit_bytes"] <= vmem_cap)
    assert compiled.as_text().count(KERNEL) == 2


@pytest.mark.parametrize("precision", ["bf16x3", "f32", "bf16gen2", "bf16"])
def test_cell_shape_rowwise_hbm(one_chip, vmem_cap, precision):
    """65536 × 8192 → 1024: a generation call and a contraction call,
    the latter on 2048 rows a tile under the limit its plan passes."""
    plan, compiled = _executable(pd._fused_call, one_chip, (ROWS, N), 1024, 1,
                                 precision, ((), jnp.float32),
                                 vmem_cap=vmem_cap)
    if precision == "f32":
        # the one regime that keeps 512 rows rowwise (slower at 2048 on
        # the chip, PR 49): the parent's program, inside the default scope
        assert (plan["m_tile"], plan["vmem_limit_bytes"]) == (512, 0)
        assert 0 < _scoped_in(compiled) <= pd._VMEM_BUDGET_BYTES
        return
    _grown(plan, compiled, vmem_cap)


def test_cell_shape_without_tpu_info_is_the_old_plan(one_chip):
    """Planned where ``pltpu.get_tpu_info()`` has no TPU to describe (the
    default device here is a CPU) the cell keeps the 512-row tile and
    passes no limit: the parent's program."""
    plan, compiled = _executable(pd._fused_call, one_chip, (ROWS, N), 1024, 1,
                                 "bf16x3", ((), jnp.float32))
    assert (plan["m_tile"], plan["k_cols"], plan["vmem_limit_bytes"]) == (
        512, 2 * BLOCK_COLS, 0)
    assert 0 < _scoped_in(compiled) <= pd._VMEM_BUDGET_BYTES
    assert compiled.as_text().count(KERNEL) == 2


@pytest.mark.parametrize("precision", ["bf16x3", "f32", "bf16gen2", "bf16"])
def test_cell_shape_columnwise_hbm(one_chip, vmem_cap, precision):
    """The jlt_apply_cw cell, 8192 × 65536 → 1024 × 65536: the same
    generation call and the columnwise contraction call (2048 columns a
    tile; "f32", whose plane tile is the split left operand, at its one
    block a step), the scale folded into the planes — no pass over the
    256 MiB result outside them, and no temporary but the planes
    (2 × 16 MiB at "bf16x3")."""
    plan, compiled = _executable(pd._fused_call_cw, one_chip, (N, ROWS), 1024,
                                 0, precision, ((), jnp.float32),
                                 vmem_cap=vmem_cap)
    _grown(plan, compiled, vmem_cap,
           BLOCK_COLS if precision == "f32" else 2 * BLOCK_COLS)
    text = compiled.as_text()
    assert not re.search(r"f32\[1024,65536\]\S* multiply\(", text)
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == 1024 * ROWS * 4
    assert memory.temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("s_dim,m_tile,precision,k_cols", [
    (512, 1024, "bf16x3", 256), (2048, None, "bf16x3", 256),
    (1536, None, "bf16x3", 256), (1024, 256, "bf16x3", 512),
    (1536, None, "f32", 128), (2048, None, "f32", 128)])
def test_other_widths_columnwise_hbm(one_chip, s_dim, m_tile, precision,
                                     k_cols):
    """Sketch sizes around the headline one, columnwise, at the tile and
    the k step the plan lets through: the "f32" regime's plane tile is
    the split left operand there, and past s_dim 1024 only a half-block
    step fits Mosaic's scope (18.5 MiB at 1536 × 512 × 256)."""
    plan, kernels = _compile(pd._fused_call_cw, one_chip, (N, ROWS), s_dim, 0,
                             precision, ((), jnp.float32), m_tile=m_tile)
    assert plan["operator_residency"] == "hbm" and kernels == 2
    assert pd._plane_step_cols(N, plan["m_tile"], s_dim,
                               precision == "f32") == k_cols


@pytest.mark.parametrize("s_dim,m_tile", [(512, 1024), (2048, None),
                                          (1536, None), (1024, 1024)])
def test_other_widths_rowwise_hbm(one_chip, s_dim, m_tile):
    """Sketch sizes around the headline one, at the tile the plan lets
    through (a requested 1024 at s_dim = 1024 is shrunk: Mosaic refused
    it on the chip, PR 27)."""
    plan, kernels = _compile(pd._fused_call, one_chip, (ROWS, N), s_dim, 1,
                             "bf16x3", ((), jnp.float32), m_tile=m_tile)
    assert plan["operator_residency"] == "hbm" and kernels == 2


@pytest.mark.parametrize("call,seq_axis,s_dim,precision,want", [
    # the expression at its tightest: the plane tile the split left
    # operand, 20 B an entry — planned 31.5 MiB, Mosaic's least 30.05
    ("_fused_call_cw", 0, 2048, "f32", (1024, 128)),
    ("_fused_call_cw", 0, 1536, "f32", (2048, 128)),
    ("_fused_call_cw", 0, 2048, "bf16x3", (1024, 256)),
    ("_fused_call", 1, 2048, "bf16x3", (1024, 256)),
    ("_fused_call", 1, 512, "bf16x3", (2048, 512)),
    ("_fused_call", 1, 1536, "bf16gen2", (2048, 256))])
def test_other_widths_grown_hbm(one_chip, vmem_cap, call, seq_axis, s_dim,
                                precision, want):
    """Sketch sizes around the headline one at the tile the described
    chip's cap admits, the k step that of the plan inside the default
    scope: each compiles under the limit its plan passes."""
    shape = (ROWS, N) if seq_axis else (N, ROWS)
    plan, compiled = _executable(getattr(pd, call), one_chip, shape, s_dim,
                                 seq_axis, precision, ((), jnp.float32),
                                 vmem_cap=vmem_cap)
    assert (plan["m_tile"], plan["k_cols"]) == want
    assert plan["operator_residency"] == "hbm"
    assert (pd._VMEM_BUDGET_BYTES < _scoped_in(compiled)
            <= plan["vmem_limit_bytes"] <= vmem_cap)
    assert compiled.as_text().count(KERNEL) == 2


def test_cell_shape_rft_cos_hbm(one_chip, vmem_cap):
    """A dense feature map past 512 inputs accumulates over k steps and
    takes the grown tile: the cos finishes 2048 × 1024 in 32 slabs."""
    plan, compiled = _executable(
        pd._fused_call_cos, one_chip, (ROWS, N), 1024, 1, "bf16x3",
        ((1, 1024), jnp.float32), ((1, 1024), jnp.float32),
        vmem_cap=vmem_cap, inscale=0.5, outscale=0.25)
    _grown(plan, compiled, vmem_cap)


def _feature_plan():
    plan = pd.effective_plan(randgen.Normal(), (FEATURE_ROWS, FEATURE_N),
                             jnp.float32, FEATURE_S, 1, precision="bf16x3",
                             interpret=True, epilogue=True)
    assert (plan["m_tile"], plan["s_tile"], plan["operator_residency"]) == (
        512, 1024, "hbm")
    return plan


def test_feature_cell_shape_s_tiled_cos(one_chip):
    """32768 × 440 → 16384, the kernels alone on the UNPADDED operand: a
    generation call over (s-tile, column block) and a contraction call over
    (row tile, s-tile) whose one 512-deep step ends in the cos."""
    plan = _feature_plan()

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = pd._fused_call_cos.lower(
        arg((FEATURE_ROWS, FEATURE_N), jnp.float32), arg((2, 2), jnp.uint32),
        arg((1, FEATURE_S), jnp.float32), arg((1, FEATURE_S), jnp.float32),
        s_dim=FEATURE_S, dist_kind="normal", m_tile=plan["m_tile"],
        s_tile=plan["s_tile"], precision="bf16x3", inscale=1.0 / 30.0,
        outscale=0.011).compile().as_text()
    assert text.count(KERNEL) == 2


@pytest.mark.parametrize("m_tile,s_dim,want", [
    (512, 5120, (512, 1280, "hbm")), (256, 4096, (256, 1024, "vmem")),
    (128, 3200, (64, 3200, "hbm")), (512, 12288, (512, 1024, "hbm"))])
def test_other_feature_widths_compile(one_chip, m_tile, s_dim, want):
    """What the plan gives at other widths, tiled and not, under both
    residencies. The plan counts the cos's temporaries: without them it
    let 512 × 1536 through, for which Mosaic asks 17.0 MiB of its 16."""
    assert not pd._tile_fits(512, 1536, True) and pd._tile_fits(512, 1536, False)
    plan = pd.effective_plan(randgen.Normal(), (4096, FEATURE_N),
                             jnp.float32, s_dim, 1, m_tile=m_tile,
                             interpret=True, epilogue=True)
    assert (plan["m_tile"], plan["s_tile"],
            plan["operator_residency"]) == want

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = pd._fused_call_cos.lower(
        arg((4096, FEATURE_N), jnp.float32), arg((2, 2), jnp.uint32),
        arg((1, s_dim), jnp.float32), arg((1, s_dim), jnp.float32),
        s_dim=s_dim, dist_kind="normal", m_tile=plan["m_tile"],
        s_tile=plan["s_tile"], precision="bf16x3", inscale=0.5,
        outscale=0.25).compile().as_text()
    assert text.count(KERNEL) == (2 if want[2] == "hbm" else 1)


@pytest.mark.parametrize("kernel_route", [True, False],
                         ids=["pallas_planes", "xla"])
def test_feature_cell_whole_program(one_chip, kernel_route):
    """``sketch.rft_features`` at the cell's shape, both routes: key data
    and the unpadded panel in, the 2 GiB feature block out — shifts, the
    block keys and the pad to 512 columns inside the one executable."""
    from libskylark_tpu.sketch import rft

    plan = _feature_plan()
    program = jax.jit(functools.partial(
        rft.rft_features,
        spec=("GaussianRFT", FEATURE_N, FEATURE_S, (("sigma", 30.0),)),
        rowwise=True,
        plan=(pd.Plan(plan["m_tile"], plan["s_tile"], "bf16x3",
                      plan["operator_residency"])
              if kernel_route else None)))
    compiled = program.lower(
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((FEATURE_ROWS, FEATURE_N), jnp.float32,
                             sharding=one_chip)).compile()
    assert compiled.as_text().count(KERNEL) == (2 if kernel_route else 0)
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == FEATURE_ROWS * FEATURE_S * 4
    # the padded operand and the planes; never a second feature block
    assert memory.temp_size_in_bytes < 256 << 20


@pytest.mark.parametrize("shape,seq_axis,call,residency", [
    ((N, 512), 0, "_fused_call_cw", "per_tile"),      # one column tile
    ((512, N), 1, "_fused_call", "per_tile"),         # one m-tile
    ((4096, 1024), 1, "_fused_call", "vmem"),         # small S
])
def test_generating_kernels_still_compile(one_chip, shape, seq_axis, call,
                                          residency):
    s_dim = 1024 if residency == "per_tile" else 128
    plan, kernels = _compile(getattr(pd, call), one_chip, shape, s_dim,
                             seq_axis, "bf16x3", ((), jnp.float32))
    assert plan["operator_residency"] == residency and kernels == 1


def test_cell_shape_sparse_rows_program(one_chip, monkeypatch):
    """The whole ``sketch.hash_sparse`` program of the cwt_sparse_apply cell
    under the rowwise kernel: the lane prologue and ONE Mosaic call — no
    sort, no scatter, no row ids left in the executable."""
    # off the TPU the program would interpret the kernel
    monkeypatch.setattr(pallas_sparse, "available", lambda: True)
    assert sparse_serve.sparse_kernel(
        (SPARSE_ROWS, SPARSE_N), 1024, SPARSE_LANES, jnp.float32,
        True) == "pallas_rows"

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    program = jax.jit(functools.partial(
        sparse_serve.cwt_sparse_serve_apply, s_dim=1024, rowwise=True,
        shape=(SPARSE_ROWS, SPARSE_N), kernel="pallas_rows"))
    text = program.lower(
        arg((2,), jnp.uint32), arg((SPARSE_LANES,), jnp.float32),
        arg((SPARSE_LANES,), jnp.int32),
        arg((SPARSE_ROWS + 1,), jnp.int32)).compile().as_text()
    assert text.count(KERNEL) == 1
    assert not re.search(r"\b(sort|scatter|reduce-window)\(", text)


@pytest.mark.parametrize("s_dim,rows,plan", [
    (128, SPARSE_ROWS, (16, 1, 16)), (384, 4096, (16, 3, 16)),
    (2048, SPARSE_ROWS, (8, 16, 32)), (1024, 24, (8, 8, 1)),
    (1024, (8 << 16) - 8, (8, 8, 1))])      # the largest table of tile starts
def test_sparse_rows_kernel_other_shapes(one_chip, s_dim, rows, plan):
    lanes = 1 << 20
    assert pallas_sparse.rows_plan(rows, s_dim, lanes, jnp.float32) == plan

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = pallas_sparse._rows_call.lower(
        arg((rows // plan[0] + 1,), jnp.int32),
        arg((lanes // 128, 128), jnp.float32),
        arg((lanes // 128, 128), jnp.int32),
        arg((-(-rows // 1024) * 8, 128), jnp.int32),
        n_rows=rows, s_dim=s_dim, plan=plan,
        interpret=False).compile().as_text()
    assert text.count(KERNEL) == 1


# -- the fjlt_apply_cw cell: the Blendenpik sketch's block-mix kernel --------

FJLT_ROWS, FJLT_COLS, FJLT_S = 1 << 20, 1024, 4096


def _fjlt_wht_program(one_chip, rows, cols, kernel, block, tile):
    """``fjlt_mix_sample`` on the Hadamard route, columnwise of rows × cols,
    compiled for the described chip."""
    from libskylark_tpu.sketch import fjlt

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    program = jax.jit(functools.partial(
        fjlt.fjlt_mix_sample, s_dim=FJLT_S, rowwise=False, kernel=kernel,
        block=block, tile=tile))
    return program.lower(arg((2,), jnp.uint32),
                         arg((rows, cols), jnp.float32)).compile()


def _row_gathers(text):
    """``(rows, columns, rows a step)`` of every gather fusion of whole rows
    in a compiled module's text: the step is the compiler's own choice, 256
    or — where the index count fills whole 1024-index tiles, or ends within
    ≈ 256 of a tile's end — 128 with a quarter of the buffers."""
    found = re.findall(r"f32\[(\d+),(\d+)\]\S* fusion\(.*/gather\".*"
                       r"\"integer_config\":\{\"integer\":\"(\d+)\"", text)
    return [tuple(map(int, g)) for g in found]


def test_cell_shape_fjlt_mix_sample(one_chip):
    """FJLT(2²⁰, 4096, wht) columnwise of 1,048,576 × 1024 as the one
    program: the block-mix kernel (16384 × 256 tiles, 80 MiB of the core's
    VMEM asked for) and the gather of the sampled rows — no workspace beyond
    the one block-mixed matrix (4 GiB) and a few gathered chunks, no copy of
    it in another layout. The one gather fusion takes the rows of 264 samples
    a chunk, 64 a sample (``fut._sample_chunk``: 256 samples would be sixteen
    whole index tiles), 256 rows a step, and the gathered rows stay inside
    the fusion: no array of them among the temporaries."""
    from libskylark_tpu.sketch import fut, pallas_wht

    block, tile = pallas_wht.plan((FJLT_ROWS, FJLT_COLS), jnp.float32,
                                  interpret=True)
    assert (block, tile) == (16384, 256)
    a = FJLT_ROWS // block
    chunk = fut.sample_outer_chunk(a, FJLT_COLS, FJLT_S)
    assert (a, chunk) == (64, 264)
    compiled = _fjlt_wht_program(one_chip, FJLT_ROWS, FJLT_COLS,
                                 "pallas_blocks", block, tile)
    text = compiled.as_text()
    assert text.count(KERNEL) == 1
    assert _row_gathers(text) == [(chunk * a, FJLT_COLS, 256)]
    operand = FJLT_ROWS * FJLT_COLS * 4
    assert not re.search(r"f32\[64,16384,1024\]\S* copy\(", text)
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == FJLT_S * FJLT_COLS * 4
    assert operand <= memory.temp_size_in_bytes < operand + (160 << 20)


@pytest.mark.parametrize("rows,cols,kernel,tile,widths", [
    (1 << 19, 1024, "pallas_blocks", 256, (1024,)),   # 32 rows a sample
    (1 << 18, 2048, "pallas_blocks", 256, (2048,)),   # 16
    (1 << 20, 128, "xla_bf16x3", 128, (128,)),        # the XLA walk's tile
    (1 << 20, 1000, "xla_bf16x3", 128, (128, 104)),   # and its ragged rest
    (1 << 21, 512, "pallas_blocks", 256, (512,))])    # 128: no chunk is fast
def test_fjlt_sample_gather_other_shapes(one_chip, rows, cols, kernel, tile,
                                         widths):
    """Other heights and widths the Hadamard route serves: the sampled
    factor's gather takes ``fut.sample_outer_chunk`` samples of a = rows ÷
    16384 rows each, and the compiler gathers them 256 rows a step wherever
    ``fut._gathers_fast`` says so — every shape whose chunk could step off
    whole index tiles. At a = 128 (2²¹ rows) every multiple of 8 samples
    gathers whole tiles: the byte rule's chunk stands, 128 rows a step as
    before (a masked 129th row block would step off: compiled, not built)."""
    from libskylark_tpu.sketch import fut

    a = rows // 16384
    compiled = _fjlt_wht_program(one_chip, rows, cols, kernel, 16384, tile)
    want = []
    for w in widths:
        chunk = fut.sample_outer_chunk(a, w, FJLT_S)
        assert chunk % 8 == 0 and chunk < FJLT_S
        fast = fut._gathers_fast(chunk * a)
        assert fast == (a < 128)
        want.append((chunk * a, w, 256 if fast else 128))
    assert _row_gathers(compiled.as_text()) == want
    # no array of a chunk's gathered rows (64 MiB) among the temporaries:
    # the kernel route holds the block-mixed matrix, the XLA walk three
    # (rows × tile) arrays of its stages
    held = rows * cols * 4 if kernel == "pallas_blocks" else 3 * rows * tile * 4
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert held <= temporaries < held + (32 << 20)


@pytest.mark.parametrize("rows,cols,plan", [
    (1 << 13, 8192, (8192, 256)), (1 << 16, 384, (16384, 128)),
    (1 << 10, 128, (1024, 128))])
def test_fjlt_block_kernel_other_shapes(one_chip, rows, cols, plan):
    from libskylark_tpu.sketch import pallas_wht

    assert pallas_wht.plan((rows, cols), jnp.float32, interpret=True) == plan

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = pallas_wht.mix_blocks.lower(
        arg((rows, cols), jnp.float32), arg((rows,), jnp.float32),
        block=plan[0], tile=plan[1]).compile().as_text()
    assert text.count(KERNEL) == 1


# -- the fjlt_dct_apply_cw cell: the Blendenpik sketch with the DCT ------------

DCT_ROWS = 1_000_000


def _instructions(text):
    """``(computation, name, elements, opcode, shape, operands)`` of every
    array-valued instruction of a compiled module's text — a fusion is one
    instruction of the computation that calls it, and what it fuses is
    listed under its own ``fused_computation``."""
    computation = None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", line)
        if head:
            computation = head.group(1)
            continue
        op = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\w+\[([\d,]*)\])\S* "
                      r"([\w\-]+)\(([^)]*)\)", line)
        if op:
            elements = math.prod(int(d) for d in op.group(3).split(",") if d)
            yield (computation, op.group(1), elements, op.group(4),
                   op.group(2), re.findall(r"%([\w.\-]+)", op.group(5)))


@pytest.mark.parametrize("cols,slabs", [
    (FJLT_COLS, 50),            # the cell: two passes of 50 slabs
    (128, 100)])                # short rows: every slab in one pass, no loop
def test_cell_shape_fjlt_dct_mix_sample(one_chip, cols, slabs):
    """FJLT(1,000,000, 4096) — the default mixer — columnwise of 1,000,000 ×
    1024 as the one program: the blocked DFT on XLA (no Mosaic call), walked
    over the sampled digit, 50 of its 100 slabs a pass. A pass is three
    tile-sized fusions — the gather of whole rows of the operand itself (no
    slice of it first, no op over an operand-shaped array), stage one with
    the signs of the gathered rows as an operand of its own fusion (no sign
    pass, no gather of scalars anywhere), stage two — and, every array
    between them lying on whole (8, 128) tiles, no tile-sized ``reshape``,
    ``copy`` or ``transpose``. Both row gathers run 256 rows a step. Beside
    the operand and the result the program holds two of a pass's arrays at a
    time — 4.26 GB of the ≈ 7 that two resident operands leave of the chip —
    and never an operand-sized or a complex one. The same at 128 columns,
    where all 100 slabs fit one pass."""
    from libskylark_tpu.sketch import fjlt, fut

    r, f1, f2 = factors = fut.dft_factors(DCT_ROWS)
    assert factors == (100, 125, 80)
    assert fjlt.dft_slabs(DCT_ROWS, factors, cols, cols, False) == slabs
    part = (slabs, f1, f2)
    f1p, _, _, blocks = fut.dft_pads(part)
    gathered = blocks * slabs * f1p             # rows a pass gathers
    assert (f1p, blocks) == (128, 81)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    program = jax.jit(functools.partial(
        fjlt.fjlt_mix_sample, s_dim=FJLT_S, rowwise=False, kernel="xla_dft",
        tile=cols, fut="dct", factors=factors))
    tables = fut.dft_tables(part)
    compiled = program.lower(
        arg((2,), jnp.uint32), arg((DCT_ROWS, cols), jnp.float32),
        *[arg(t.shape, jnp.float32) for t in tables]).compile()
    text = compiled.as_text()
    assert KERNEL not in text
    assert not re.search(r"\bc64\[", text)                # no complex array
    called = {i[1]: i for i in _instructions(text)
              if "fused_computation" not in i[0]}
    # the operand is read where it lies: nothing computes an array of its
    # shape (the parent sliced the tile out of it with the signs)
    over = [i for i in called.values() if i[4].startswith("f32[1000000,")
            and i[3] not in ("parameter", "get-tuple-element", "bitcast")]
    assert not over, over
    tile_sized = [i for i in called.values() if i[2] >= gathered * cols // 2]
    moved = [i for i in tile_sized if i[3] in ("reshape", "copy", "transpose")]
    assert not moved, moved
    # a pass: the row gather, stage one, stage two
    passes = [i for i in tile_sized if i[3] == "fusion"]
    assert len(passes) == 3, passes
    assert [i[4] for i in passes] == [
        f"f32[{gathered},{cols}]", f"f32[{blocks * slabs},{cols},128]",
        f"f32[64,{slabs},{cols},160,1]"]
    # the signs, folded as the rows are gathered (no gather of scalars,
    # which costs a scalar what it costs a row), enter stage one's fusion
    def source(name):
        while called[name][3] == "bitcast":
            name = called[name][5][0]
        return called[name]
    entering = [source(name) for name in passes[1][5]]
    assert passes[0] in entering
    assert [i[4] for i in entering if i is not passes[0]
            and i[2] == gathered] == [f"f32[{blocks * slabs},128]"]
    assert not re.search(r"f32\[\d+\]\S* gather\(", text)
    # both row gathers 256 rows a step: 81 blocks of 50 (100) slabs of 128
    # rows, and the sample chunk's 2ρ rows a sample, fill no whole number of
    # 1024-index tiles (fut._gathers_fast)
    steps = re.findall(r"f32\[\d+,%d\]\S* fusion\(.*/gather\".*"
                       r"\"integer_config\":\{\"integer\":\"(\d+)\"" % cols,
                       text)
    assert steps == ["256", "256"], steps
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == FJLT_S * cols * 4
    # two of a pass's arrays (4.25 GB at the cell) and 16 MB of the rest
    assert (2 * gathered * cols * 4 < memory.temp_size_in_bytes
            < 2 * gathered * cols * 4 + (32 << 20))
    assert memory.temp_size_in_bytes < min(fjlt._DFT_TEMP_BYTES, 4.3e9)


# -- the fastfood_features_apply cell: Fastfood at CIFAR-10 widths ------------

FF_ROWS, FF_N, FF_S = 50000, 3072, 16384


def test_cell_shape_fastfood_features(one_chip):
    """``Gaussian(3072, 78).create_rft(16384, ctx, "fast")`` rowwise of
    50,000 × 3072 as the one program: the transposed, padded operand made
    once, then a walk of 4 blocks × 2 chunks of 49 tiles of 512 examples —
    a step is ``mix_chunk`` (Mosaic), the gather of whole rows by Π in ONE
    fusion (a stage array of 25,088 entries a row: the compiler cuts a wider
    one in halves first and joins them after) and ``mix_cos_rows`` (Mosaic),
    which writes its slab of the result in place. Nothing initialises the
    result, nothing copies it, and beside the operand and the result the
    program holds the copy of the operand and two chunk-stage arrays:
    1.65 GB, not a whole stage's 3.28."""
    from libskylark_tpu.ml import kernels
    from libskylark_tpu.base.context import Context
    from libskylark_tpu.sketch import frft

    T = kernels.Gaussian(FF_N, 78.0).create_rft(FF_S, Context(1), "fast")
    tile = T.kernel_tile(FF_ROWS, interpret=True)
    assert tile == 512 and frft.walk_geometry(FF_ROWS, tile) == (2, 49)
    spec = (T.sketch_type, FF_N, FF_S, tuple(sorted(T._extra_params().items())))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    program = jax.jit(functools.partial(
        frft.fastfood_features, spec=spec, rowwise=True, kernel="pallas_wht",
        tile=tile))
    compiled = program.lower(arg((2,), jnp.uint32),
                             arg((FF_ROWS, FF_N), jnp.float32)).compile()
    text = compiled.as_text()
    assert text.count(KERNEL) == 2                      # one loop body
    called = [i for i in _instructions(text) if "fused_computation" not in i[0]]
    chunk = 4096 * 25088
    # a step: the two kernels and one gather fusion between them
    staged = [i for i in called if i[2] == chunk]
    assert sorted(i[3] for i in staged) == ["custom-call", "fusion"], staged
    # the result: made by no pass (an uninitialised buffer), written by the
    # second kernel alone, never copied
    result = [i for i in called if i[2] == FF_ROWS * FF_S
              and i[3] not in ("parameter", "get-tuple-element", "bitcast", "while")]
    assert sorted(i[3] for i in result) == ["custom-call", "custom-call"], result
    assert 'custom_call_target="AllocateBuffer"' in text or "empty" in text
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == FF_ROWS * FF_S * 4
    assert memory.temp_size_in_bytes < 1.7e9


def test_fastfood_block_kernels_other_shapes(one_chip):
    """The block kernels at the plan's other corners: the smallest block
    (1024 × 128), the widest (16384 × 256, ``pallas_wht``'s own VMEM plan),
    and the one-pass ``"bf16"`` regime of the benchmark's control."""
    from libskylark_tpu.sketch import pallas_wht

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    for n, tile, steps, passes in [(1024, 128, 3, 3), (16384, 256, 2, 3),
                                   (4096, 512, 2, 1)]:
        cols = tile * steps
        text = pallas_wht.mix_chunk.lower(
            arg((n, 2 * cols), jnp.float32), arg((n,), jnp.float32),
            arg((2,), jnp.int32), tile=tile, cols=cols,
            passes=passes).compile().as_text()
        assert text.count(KERNEL) == 1
        text = pallas_wht.mix_cos_rows.lower(
            arg((n, cols), jnp.float32), arg((n,), jnp.float32),
            arg((n,), jnp.float32), arg((n,), jnp.float32),
            arg((2 * cols - 5, 2 * n), jnp.float32), arg((2,), jnp.int32),
            tile=tile, outscale=0.01, passes=passes).compile().as_text()
        assert text.count(KERNEL) == 1


TS_ROWS, TS_N, TS_S, TS_Q = 60000, 784, 16384, 3
# the cell's bucket classes: radix, class_cols, k_tiles (R·⌈6c/128⌉)
TS_CLASSES = (4, 256, 48)


def _passes(text, least, below):
    """``(name, opcode, kind, shapes, operands, computation)`` of every
    instruction outside the fused computations whose largest array has
    ``least`` ≤ elements < ``below`` — the arrays of a multi-output fusion's tuple each counted
    (:func:`_instructions` reads single-array instructions only) — but for
    the instructions that move nothing: parameters, tuples and their
    elements, bitcasts, the loop itself. ``shapes`` are ``dtype[dims]{layout``
    strings."""
    computation, found = None, []
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", line)
        if head:
            computation = head.group(1)
            continue
        op = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\(?\w+\[.*?) ([\w\-]+)\(([^)]*)\)",
                      line)
        if not op or computation is None or "fused_computation" in computation:
            continue
        if op.group(3) in ("parameter", "get-tuple-element", "bitcast", "while",
                           "tuple"):
            continue
        shapes = re.findall(r"\w+\[[\d,]*\](?:\{[\d,]*)?", op.group(2))
        largest = max(math.prod(int(d) for d in
                                re.search(r"\[([\d,]*)\]", sh).group(1).split(",") if d)
                      for sh in shapes)
        if least <= largest < below:
            kind = re.search(r"kind=(\w+)", line)
            found.append((op.group(1), op.group(3), kind and kind.group(1), shapes,
                          re.findall(r"%([\w.\-]+)", op.group(4)), computation))
    return found


@pytest.mark.parametrize("rows", [TS_ROWS, TS_ROWS - 4])
def test_cell_shape_tensorsketch_features(one_chip, rows):
    """``Polynomial(784, 3, 1, 1/784).create_rft(16384, ctx)`` rowwise of
    60,000 × 784 as the one program, its spectral products formed by bucket
    class (PR 63): R = ``radix(784, 16384)`` classes of ``class_cols`` c
    operator rows each; the three sketches' operators generated once — 2R
    packed arrays a sketch, (6c, S/2R) bfloat16, a class's real and its
    imaginary columns each by itself, made outside the loop and carried
    through it —, then a walk of 15 blocks of 4096 examples whose features
    go into their rows of an uninitialised result in place. A block:

    -    the examples' three bfloat16 parts brought into each sketch's class
         order by an exact 0/1 product (``kOutput``, K = 784) and laid along K
         as the six partial products pair them, (6c, 4096) bfloat16 a class,
         examples next to the lanes: small passes;
    -    the 2R·q class sums (``kOutput``, MXU): ONE bfloat16 product each at
         default precision into float32, (4096, 6c)·(6c, S/2R), row-major —
         ``k_tiles`` = R·⌈6c/128⌉ MXU tiles deep a sketch, each over S/R
         columns where the whole product's 37 were over S;
    1.   the spectra's product, first half: reads all the class sums — the R
         groups an axis between the examples' two digits, (512, R, 8, S/2R),
         made of a class sum by broadcast against the R × R factors —, and
         stores the real half of stage one's operand (512, 2, R, 8, S/2R) in
         place (``kLoop``);
    2.   its second half: reads them again (no pair product is handed on:
         0.8 GB read twice a block where the whole product's two fusions
         moved 1.87 GB) and stores the imaginary half in place (``kLoop``);
    3.   stage one, reading that operand as it lies — (h, κ1, l, κ2) tile for
         tile — (``kOutput``, float32 operands at ``highest``: K = 128);
    4.   the twiddles and the midpoints' term (``kLoop``, two outputs);
    5.   stage two (``kOutput``, ``highest``: K = 256);
    6.   the store: ONE ``kLoop`` fusion that turns the two digits of t and
         writes the block at its offset of the result, carried as
         (7500, 128, 8, 128) row-major — the bytes of (60000, 16384).

    Six passes over block-sized arrays (the store among them) where PR 54's
    whole products made nine, every one a fusion, no ``copy`` / ``reshape`` / ``transpose`` among
    them and no ``concatenate`` pass; no class sum is copied on its way from
    its product to the spectra's (the layout constraints of ``ppt.class_sums``
    and ``ppt._block_features`` say row-major: left to itself the compiler
    lays the spectra's product examples-minor, 2R·q + 1 copies a block).
    The whole result is touched by the uninitialised ``custom-call`` and by
    the store alone; beside operand and result the program holds under
    1.5 GB (2.27 with the whole products: the packed operators are a third).

    Rows that are no whole (8, 128) tiles (59,996) keep the store of PR 51:
    the same passes, one more that turns the digits (a fusion around a
    ``copy``) and a bare ``dynamic-update-slice`` of the (rows, 16384)
    result."""
    from libskylark_tpu.base.context import Context
    from libskylark_tpu.ml import kernels
    from libskylark_tpu.sketch import ppt

    T = kernels.Polynomial(TS_N, q=TS_Q, c=1.0, gamma=1.0 / TS_N).create_rft(
        TS_S, Context(1))
    assert ppt.split(TS_S) == (128, 128)
    assert ppt.block_rows(rows, TS_S) == 4096
    R = ppt.radix(TS_N, TS_S)
    cols, half = ppt.class_cols(TS_N, R), TS_S // (2 * R)
    assert (R, cols, ppt.k_tiles(TS_N, "float32", R)) == TS_CLASSES
    assert T.radix() == R                   # this seed's classes fit
    spec = (T.sketch_type, TS_N, TS_S, tuple(sorted(T._extra_params().items())))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    program = jax.jit(functools.partial(
        ppt.tensorsketch_features, spec=spec, rowwise=True, radix=R))
    compiled = program.lower(arg((2,), jnp.uint32),
                             arg((rows, TS_N), jnp.float32)).compile()
    text = compiled.as_text()
    assert KERNEL not in text                       # XLA alone
    tiled = rows % 8 == 0
    # the result: an uninitialised buffer, written block by block in place
    whole = _passes(text, rows * TS_S, rows * TS_S + 1)
    assert sorted(i[1] for i in whole) == [
        "custom-call", "fusion" if tiled else "dynamic-update-slice"], whole
    carried = "f32[7500,128,8,128]{3,2,1,0" if tiled else f"f32[{rows},{TS_S}]{{1,0"
    assert all(i[3] == [carried] for i in whole), whole
    if tiled:
        (store,) = [i for i in whole if i[1] == "fusion"]
        assert store[2] == "kLoop" and "dynamic-update-slice" in store[0], store
    # a block's passes: the loop's body, every one a fusion
    sized = _passes(text, 4096 * TS_S, rows * TS_S)
    assert all(i[1] == "fusion" for i in sized), sized
    entry = re.search(r"^ENTRY %([\w.\-]+) \(", text, re.M).group(1)
    passes = [i for i in sized if i[5] != entry]
    assert len({i[5] for i in passes}) == 1, passes
    body = passes[0][5]
    stages = [i for i in passes if i[2] == "kOutput"]
    assert len(stages) == 2, passes
    loops = [i for i in passes if i[2] == "kLoop"]
    assert sorted(len(i[3]) for i in loops) == [1] * (not tiled) + [1, 1, 2], passes
    assert len(passes) == (5 if tiled else 6), passes
    # the spectra's product: two fusions that store stage one's operand in
    # place, row-major, the groups between the examples' digits
    operand = f"f32[512,2,{R},8,{half}]{{4,3,2,1,0"
    stored = [i for i in loops if operand in i[3]]
    assert len(stored) == 2 and all("dynamic-update-slice" in i[0] for i in stored)
    # the class sums: 2R products a sketch, each its own row-major array
    # that the spectra's product reads as the product wrote it
    smaller = [i for i in _passes(text, 4096 * half, 4096 * half + 1)
               if i[5] == body]
    sums = [i for i in smaller if i[2] == "kOutput" and i[3][0].startswith("f32[")]
    assert len(sums) == 2 * R * TS_Q, smaller
    assert all(i[3][0] in (f"f32[4096,{half}]{{1,0", f"f32[512,8,{half}]{{2,1,0")
               for i in sums), sums
    assert not [i for i in smaller if i[1] in ("copy", "transpose", "concatenate")
                and i[3][0].startswith("f32[")], smaller
    # the packed operators: made outside the loop, 2R a sketch carried in
    k = len(ppt._TERMS) * cols
    loop = re.search(r"= \((.*?)\) while\(", text).group(1)
    assert loop.count(f"bf16[{k},{half}]") >= 2 * R * TS_Q, loop
    assert f"f32[{TS_N},{TS_S}]" not in loop
    assert text.count("operand_precision={highest,highest}") >= 2     # the stages
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == -(-rows // 8) * 8 * TS_S * 4   # whole tiles
    assert memory.temp_size_in_bytes < 1.5e9


# -- the dense sketch of a distributed matrix: four described chips ----------

MESH_M, MESH_N, MESH_S = 262144, 16384, 1024    # the jlt_apply_mesh4 cell's


@pytest.fixture(scope="module")
def grid2x2(topology):
    """The four chips of the described v5e host as a 2 × 2 mesh."""
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(topology.devices).reshape(2, 2), ("rows", "cols"))


def _mesh_program(grid2x2, vmem_cap, shape, spec, seq_axis, s_dim):
    """``sketch.dense_mesh`` compiled for the described mesh on an operand
    laid ``spec``, under the device's plan as the dispatch resolves it."""
    from jax.sharding import NamedSharding

    from libskylark_tpu.parallel import shard_apply

    axes = shard_apply._spec_axes(spec)
    local = shard_apply._local_shape(shape, grid2x2, axes, seq_axis)
    planned = pd.effective_plan(randgen.Normal(), local, jnp.float32, s_dim,
                                seq_axis, interpret=True, vmem_cap=vmem_cap)
    plan = pd.Plan(planned["m_tile"], planned["s_tile"], planned["precision"],
                   planned["operator_residency"])
    compiled = jax.jit(functools.partial(
        shard_apply.dense_mesh, mesh=grid2x2, spec=axes, seq_axis=seq_axis,
        dist=randgen.Normal(), s_dim=s_dim, scale=(1.0 / s_dim) ** 0.5,
        plan=plan, scatter=True)).lower(
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct(shape, jnp.float32,
                             sharding=NamedSharding(grid2x2, spec))).compile()
    return local, planned, compiled


def _collectives(text: str) -> dict:
    return {op: len(re.findall(rf"= [^\n=]*\b{op}(?:-start)?\(", text))
            for op in ("reduce-scatter", "all-reduce", "all-gather",
                       "collective-permute", "all-to-all")}


def _schedule(text: str) -> list:
    """``[(name, opcode, operands)]`` of the entry computation of a compiled
    (scheduled) module, in the order the device runs them."""
    entry = text[text.index("\nENTRY "):]
    return re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*? ([\w\-]+)\((.*)$",
                      entry, re.M)


def _ring(schedule: list) -> list:
    """``[(start, done)]`` positions of the schedule's collective-permutes,
    in the order their ``start``s are issued."""
    where = {name: i for i, (name, _, _) in enumerate(schedule)}
    pairs = []
    for i, (_, opcode, operands) in enumerate(schedule):
        if opcode == "collective-permute-done":
            start = re.match(r"[^%]*%([\w.\-]+)", operands).group(1)
            pairs.append((where[start], i))
    return sorted(pairs)


def test_cell_shape_mesh_program(grid2x2, vmem_cap):
    """262144 × 16384 laid [MC,MR] over 2 × 2 → 1024: a device's local
    problem is two ``jlt_apply`` panels under the cell's own plan ("hbm",
    2048 rows a tile), contracted in eight row panels against planes made
    once. The off-chip witness of the overlap, in the scheduled module:
    panel q's half leaves by a ``collective-permute-start`` after contraction
    q and before contraction q + 1, its ``done`` (and the add into the
    result's rows) after contraction q + 1 and before contraction q + 2, and
    only the last panel's ``start`` stands behind the last contraction. No
    reduce-scatter, nothing gathered, no copy of the operand; a device holds
    its operand shard, less than one partial of temporaries and its result
    shard."""
    from jax.sharding import PartitionSpec as P

    from libskylark_tpu.parallel import shard_apply

    local, plan, compiled = _mesh_program(
        grid2x2, vmem_cap, (MESH_M, MESH_N), P("rows", "cols"), 1, MESH_S)
    assert local == (2 * ROWS, N)
    assert (plan["operator_residency"], plan["m_tile"], plan["k_cols"],
            plan["precision"]) == ("hbm", 2048, 2 * BLOCK_COLS, "bf16x3")
    k = local[0] // plan["m_tile"] // shard_apply._PANEL_TILES
    assert k == 8
    text = compiled.as_text()
    assert _collectives(text) == {"reduce-scatter": 0, "all-reduce": 0,
                                  "all-gather": 0, "collective-permute": k,
                                  "all-to-all": 0}
    assert "source_target_pairs={{0,1},{1,0},{2,3},{3,2}}" in text
    schedule = _schedule(text)
    kernels = [(name.split(".")[0], i)
               for i, (name, opcode, operands) in enumerate(schedule)
               if opcode == "custom-call" and KERNEL in operands]
    # the planes once, the result's buffer (an empty body), k contractions
    assert sorted(name for name, _ in kernels) == (
        ["partial_planes", "unwritten"] + ["window_partial"] * k)
    contractions = [i for name, i in kernels if name == "window_partial"]
    ring = _ring(schedule)
    assert len(ring) == k
    for q, (start, done) in enumerate(ring):
        assert contractions[q] < start
        if q + 1 < k:
            assert start < contractions[q + 1] < done
        if q + 2 < k:
            assert done < contractions[q + 2]       # retired in order
    assert sum(start > contractions[-1] for start, _ in ring) == 1
    # the operand is read where it lies: nothing makes an f32[…, 8192] of it
    made = {opcode for rows, opcode in re.findall(
        rf"= f32\[(\d+),{N}\]\S* ([\w\-]+)\(", text)
        if int(rows) >= plan["m_tile"]}
    assert made == {"parameter"}, made
    memory = compiled.memory_analysis()
    shard, partial, result = (4 * local[0] * local[1], 4 * local[0] * MESH_S,
                              4 * local[0] * MESH_S // 2)
    assert shard <= memory.argument_size_in_bytes <= shard + 4096
    assert memory.output_size_in_bytes == result
    # the planes and three panels' partials: less than the parent's one
    # whole partial (0.537 GB)
    assert 2 * 2 * MESH_S * N <= memory.temp_size_in_bytes < partial
    (out,) = jax.tree.leaves(compiled.output_shardings)
    assert out.spec == P("rows", "cols")


@pytest.mark.parametrize("spec,seq_axis,rows,want", [
    (("rows", None), 1, MESH_M // 16, {}),
    ((None, ("rows", "cols")), 1, MESH_M // 16, {"reduce-scatter": 1}),
    (("rows", "cols"), 0, MESH_M // 16, {"reduce-scatter": 1}),
    (("rows", "cols"), 1, MESH_M // 8, {"reduce-scatter": 1}),
    ((None, ("rows", "cols")), 1, MESH_M // 4, {"collective-permute": 12})],
    ids=["row_sharded", "col_sharded_all_axes", "columnwise", "one_panel",
         "ring_of_four"])
def test_other_layouts_mesh_program(grid2x2, vmem_cap, spec, seq_axis, rows,
                                    want):
    """Which layouts keep the parent's single collective and which pipeline.
    The parent's programs: the contracted axis whole on a device (no
    collective); over all four chips at eight row tiles a device and [MC,MR]
    at eight (fewer than two panels: one reduce-scatter); columnwise (the
    mirror image, not pipelined). Over all four chips with thirty-two row
    tiles a device the exchange is the ring of three steps a panel, four
    panels: twelve collective-permutes."""
    from jax.sharding import PartitionSpec as P

    shape = (rows, MESH_N) if seq_axis else (MESH_N, rows)
    _, plan, compiled = _mesh_program(grid2x2, vmem_cap, shape, P(*spec),
                                      seq_axis, MESH_S)
    assert plan["kernel"]
    found = _collectives(compiled.as_text())
    assert {op: n for op, n in found.items() if n} == want
    assert compiled.as_text().count(KERNEL) >= 1


# -- the jlt_sparse_apply cell: the dense sketch of a sparse row block --------

# a jlt_sparse_apply block is a cwt_sparse_apply block: SPARSE_ROWS ×
# SPARSE_N, SPARSE_LANES lane positions; the sketch is 1024 wide
SPARSE_S = 1024
CHIP_HBM = 16 << 30


def _sparse_arg(one_chip):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _makers(text: str, shape: str) -> list:
    """The opcodes of the compiled program's entry computation that
    produce an array of ``shape``: a ``custom-call`` is the Mosaic call, a
    ``bitcast`` moves nothing; anything else (``reshape``, ``copy``, a
    fusion) reads and writes the array."""
    entry = text[text.index("\nENTRY "):]
    return re.findall(rf" = {re.escape(shape)}\S* ([\w\-]+)\(", entry)


def test_cell_shape_sparse_dense_program(one_chip, monkeypatch):
    """The whole ``sketch.dense_sparse`` program of the jlt_sparse_apply
    cell under the tiles kernel: the operator generated in the program in
    the kernel's view, ONE Mosaic call whose (rows, s) output is the
    program's result as it stands — no relayout of the result or of the
    operator on either side of the call, no array of nnz × s anywhere —
    and under 0.5 GiB of temporaries (the operator and its cipher words;
    3 GiB held the parent's relayouts)."""
    from libskylark_tpu.sketch import pallas_spmm

    # off the TPU the program would interpret the kernel
    monkeypatch.setattr(sparse_serve, "_compiles_mosaic", lambda: True)
    kernel, plan = sparse_serve.product_kernel(
        (SPARSE_ROWS, SPARSE_N), SPARSE_S, SPARSE_LANES, jnp.float32)
    assert kernel == "pallas_tiles"
    assert plan == pallas_spmm.TilesPlan(2048, 1976, 4096, 8, 128, 24, 7936,
                                         8)
    # two buffers each of the result's block and of B's tile, and the
    # scratch block the walk accumulates in: 39.4 MiB
    assert pallas_spmm.vmem_bytes(plan) == (3 * 2048 + 2 * 1976) * 4096
    arg = _sparse_arg(one_chip)
    program = jax.jit(functools.partial(
        sparse_serve.dense_sparse_apply, dist=randgen.Normal(),
        s_dim=SPARSE_S, shape=(SPARSE_ROWS, SPARSE_N), kernel=kernel,
        plan=plan))
    slots = (plan.n_chunks, 1, plan.chunk)
    compiled = program.lower(
        arg((2,), jnp.uint32), arg((), jnp.float32),
        arg((plan.n_chunks,), jnp.int32), arg((plan.n_chunks,), jnp.int32),
        arg(slots, jnp.int32), arg(slots, jnp.float32)).compile()
    text = compiled.as_text()
    assert text.count(KERNEL) == 1
    assert not re.search(r"\b(sort|scatter)\(", text)
    assert f"f32[{SPARSE_LANES},{SPARSE_S}]" not in text
    result = f"f32[{SPARSE_ROWS},{SPARSE_S}]"
    (call,) = [line for line in text.splitlines() if KERNEL in line]
    assert re.search(rf"ROOT \S+ = {re.escape(result)}\S* custom-call\(",
                     call), call
    assert _makers(text, result) == ["custom-call"]
    # the operator — the padded N to the stream's block, 186 blocks of 256
    # rows — reaches the call through a bitcast of what generated it, in no
    # (rows, s) tiling
    rows = -(-plan.col_tiles * plan.col_tile // BLOCK_COLS) * BLOCK_COLS
    assert _makers(text, f"f32[{rows * plan.k_tiles},128]") == ["bitcast"]
    assert f"f32[{rows},{SPARSE_S}]" not in text
    assert f"f32[{plan.col_tiles * plan.col_tile},{SPARSE_S}]" not in text
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == SPARSE_ROWS * SPARSE_S * 4
    assert memory.temp_size_in_bytes < 1 << 29, memory


@pytest.mark.parametrize("k,lanes,chunk", [
    (128, SPARSE_LANES, 4096), (2048, SPARSE_LANES, 4096),
    (1024, 1 << 27, 8192)])
def test_sparse_dense_walk_at_the_plans_other_widths(one_chip, k, lanes,
                                                     chunk):
    """The walk's grouped span — 128 slots unrolled, eight rows loaded
    ahead of their stores — under the shipped 4096-slot chunk at the
    narrowest and the widest k the plan takes: a row of the blocks is an
    eighth of a vector register at k = 128 and two registers (1024-row
    blocks) at k = 2048; and under the 8192-slot chunk that serves an
    operand whose chunk tables would pass SMEM (two slot blocks, two
    buffers each, 128 KiB of SMEM)."""
    from libskylark_tpu.sketch import pallas_spmm

    plan, why = pallas_spmm.tiles_plan((SPARSE_ROWS, SPARSE_N), k, lanes,
                                       jnp.float32)
    assert plan is not None, why
    assert plan.chunk == chunk
    assert plan.row_block == (1024 if k == 2048 else 2048)
    arg = _sparse_arg(one_chip)
    slots = (plan.n_chunks, 1, plan.chunk)
    compiled = jax.jit(functools.partial(
        pallas_spmm.tiles_apply, shape=(SPARSE_ROWS, SPARSE_N),
        plan=plan)).lower(
            arg((plan.n_chunks,), jnp.int32), arg((plan.n_chunks,), jnp.int32),
            arg(slots, jnp.int32), arg(slots, jnp.float32),
            arg((plan.col_tiles * plan.col_tile, k), jnp.float32)).compile()
    text = compiled.as_text()
    assert text.count(KERNEL) == 1
    # k = 2048 hands its blocks over as rows, as the cells' 1024 does; at
    # k = 128 the result leaves in the kernel's view, whose bytes are the
    # rows' (at the widths between, 256 to 896, XLA relays it)
    assert _makers(text, f"f32[{SPARSE_ROWS},{k}]") == [
        "bitcast" if k == 128 else "custom-call"]


def test_cell_shape_sparse_product_replaced_the_whole_gather(one_chip):
    """What ``spmm`` was before PR 57 — ``segment_sum(v[:, None] * B[c], r)``
    over all stored nonzeros at once — does not fit a v5e at the cell's
    shape (an nnz × s temporary, 79 GB), and the program ``spmm`` runs now
    where the kernel declines (the span loop, any backend) stays under
    3 GB: the whole gather must not come back through it."""
    arg = _sparse_arg(one_chip)
    lanes = [arg((SPARSE_LANES,), jnp.int32), arg((SPARSE_LANES,), jnp.int32),
             arg((SPARSE_LANES,), jnp.float32)]
    B = arg((SPARSE_N, SPARSE_S), jnp.float32)

    def whole_gather(r, c, v, B):
        return jax.ops.segment_sum(v[:, None] * B[c], r,
                                   num_segments=SPARSE_ROWS)

    try:
        needs = jax.jit(whole_gather).lower(
            *lanes, B).compile().memory_analysis().temp_size_in_bytes
    except Exception as e:  # noqa: BLE001 — the compiler's own refusal
        assert "RESOURCE_EXHAUSTED" in str(e), e
        needs = SPARSE_LANES * SPARSE_S * 4
    assert needs > CHIP_HBM

    program = jax.jit(functools.partial(
        sparse_serve.product_lanes, kernel="xla: declined",
        shape=(SPARSE_ROWS, SPARSE_N)))
    compiled = program.lower(
        arg((SPARSE_LANES,), jnp.float32), arg((SPARSE_LANES,), jnp.int32),
        arg((SPARSE_ROWS + 1,), jnp.int32), B).compile()
    assert f"f32[{SPARSE_LANES},{SPARSE_S}]" not in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 3 << 30, memory


# -- the jlt_sparse_apply_cw cell: the columnwise sketch of a sparse row block -

# a jlt_sparse_apply_cw block: twice the rows of a jlt_sparse_apply block,
# the lane class of its ~38.8 M nonzeros
SPARSE_CW_ROWS, SPARSE_CW_LANES = 524288, 39845888


def test_cell_shape_sparse_dense_cw_program(one_chip, monkeypatch):
    """The whole ``sketch.dense_sparse_cw`` program of the
    jlt_sparse_apply_cw cell under the runs walk: Sᵀ (524288 × 1024, 2 GiB)
    generated in the program a panel at a time, ONE Mosaic call whose
    (features, s) output is the transposed result's bytes, no array of
    nnz × s and no second copy of the operator anywhere: under 2.3 GB of
    temporaries (the operator; 2.55 GB with the parent's relayout of the
    result)."""
    from libskylark_tpu.sketch import pallas_spmm

    monkeypatch.setattr(sparse_serve, "_compiles_mosaic", lambda: True)
    shape = (SPARSE_CW_ROWS, SPARSE_N)
    kernel, plan = sparse_serve.product_kernel(
        shape, SPARSE_S, SPARSE_CW_LANES, jnp.float32, rowwise=False)
    assert kernel == "pallas_runs"
    assert plan == pallas_spmm.TilesPlan(4096, 2048, 8192, 8, 12, 256, 13136,
                                         8, True)
    # + the scratch block of 4096 rows: 64 MiB, and 16 MiB of slack
    assert pallas_spmm.vmem_bytes(plan) == (3 * 4096 + 2 * 2048) * 4096
    arg = _sparse_arg(one_chip)
    program = jax.jit(functools.partial(
        sparse_serve.dense_sparse_apply_cw, dist=randgen.Normal(),
        s_dim=SPARSE_S, shape=shape, kernel=kernel, plan=plan))
    slots = (plan.n_chunks, 1, plan.chunk)
    compiled = program.lower(
        arg((2,), jnp.uint32), arg((), jnp.float32),
        arg((plan.n_chunks,), jnp.int32), arg((plan.n_chunks,), jnp.int32),
        arg(slots, jnp.int32), arg(slots, jnp.float32)).compile()
    text = compiled.as_text()
    assert text.count(KERNEL) == 1
    assert not re.search(r"\b(sort|scatter)\(", text)
    assert f"f32[{SPARSE_CW_LANES},{SPARSE_S}]" not in text
    # the call's (features, s) output is the transposed result's bytes: no
    # relayout of it, and none of the operator (m, s) before the call
    assert _makers(text, f"f32[{SPARSE_N},{SPARSE_S}]") == ["custom-call"]
    assert _makers(text, f"f32[{SPARSE_S},{SPARSE_N}]") == ["bitcast"]
    assert not _makers(text, f"f32[{SPARSE_CW_ROWS},{SPARSE_S}]")
    memory = compiled.memory_analysis()
    # the result's minor extent is laid out to a multiple of 8
    assert memory.output_size_in_bytes == SPARSE_S * (-(-SPARSE_N // 8) * 8) * 4
    assert memory.temp_size_in_bytes < 2.3e9, memory


def test_cell_shape_transposed_product_replaced_the_whole_gather(one_chip):
    """What ``spmm_t`` was before PR 61 — ``segment_sum(v[:, None] * B[r],
    c)`` over all stored nonzeros at once — does not fit a v5e at the
    jlt_sparse_apply_cw cell's shape (an nnz × s temporary, 159 GB), and the
    program ``spmm_t`` runs now where the kernel declines (the span loop
    over Aᵀ's lanes, any backend) stays under 3 GB."""
    arg = _sparse_arg(one_chip)
    lanes = [arg((SPARSE_CW_LANES,), jnp.int32),
             arg((SPARSE_CW_LANES,), jnp.int32),
             arg((SPARSE_CW_LANES,), jnp.float32)]
    B = arg((SPARSE_CW_ROWS, SPARSE_S), jnp.float32)

    def whole_gather(r, c, v, B):
        return jax.ops.segment_sum(v[:, None] * B[r], c,
                                   num_segments=SPARSE_N)

    try:
        needs = jax.jit(whole_gather).lower(
            *lanes, B).compile().memory_analysis().temp_size_in_bytes
    except Exception as e:  # noqa: BLE001 — the compiler's own refusal
        assert "RESOURCE_EXHAUSTED" in str(e), e
        needs = SPARSE_CW_LANES * SPARSE_S * 4
    assert needs > CHIP_HBM

    program = jax.jit(functools.partial(
        sparse_serve.product_lanes, kernel="xla: declined",
        shape=(SPARSE_N, SPARSE_CW_ROWS)))
    compiled = program.lower(
        arg((SPARSE_CW_LANES,), jnp.float32),
        arg((SPARSE_CW_LANES,), jnp.int32),
        arg((SPARSE_N + 1,), jnp.int32), B).compile()
    assert f"f32[{SPARSE_CW_LANES},{SPARSE_S}]" not in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 3 << 30, memory


# -- the sparse → sparse hash sketch (cwt_sparse_out_apply, PR 64) --

OUT_ROWS, OUT_N, OUT_S = 524288, 3231961, 262144
OUT_LANES = 60817408        # lane_class of 524288 × 115.6 stored nonzeros


def test_cell_shape_sparse_out_program(one_chip):
    """The whole ``sketch.hash_sparse_out`` program of the
    cwt_sparse_out_apply cell under the windowed sort: two batched
    minor-axis sorts of 2048-lane windows by one 32-bit key (no sort of the
    whole lane extent, no two-key sort), no scatter or gather over the
    lanes (the one scatter lays the 524289 row starts, the one gather reads
    the new row pointers there), the coalescing stage named by its scope,
    the result in the operand's lane extent, and under 2.5 GB of
    temporaries: ten words a lane."""
    from libskylark_tpu.engine.bucket import lane_class
    from libskylark_tpu.sketch import sparse_coalesce

    assert lane_class(round(OUT_ROWS * 115.6)) == OUT_LANES
    kernel, form, cap, _ = sparse_serve.coalesce_kernel(
        (OUT_ROWS, OUT_N), OUT_S, True, 1024)
    assert (kernel, form, cap) == ("xla_window_sort", "window", 1024)
    arg = _sparse_arg(one_chip)
    program = jax.jit(functools.partial(
        sparse_serve.cwt_sparse_out_serve_apply, s_dim=OUT_S, rowwise=True,
        shape=(OUT_ROWS, OUT_N), values=("CWT",), form=form, cap=cap))
    compiled = program.lower(
        arg((2,), jnp.uint32), arg((OUT_LANES,), jnp.float32),
        arg((OUT_LANES,), jnp.int32), arg((OUT_ROWS + 1,), jnp.int32)).compile()
    text = compiled.as_text()
    windows = OUT_LANES // 2048
    sorts = re.findall(r" = \((\S+), (\S+)\) sort\(", text)
    assert len(sorts) == 2, sorts
    assert all(k.startswith(f"u32[{windows},2048]")
               and v.startswith(f"f32[{windows},2048]") for k, v in sorts)
    assert f"/{sparse_coalesce.SCOPE}/" in text
    for lanes_wide in re.findall(r"\b(?:scatter|gather)\(([^)]*)\)", text):
        assert f"[{OUT_LANES}]" not in lanes_wide.split(",")[1], lanes_wide
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes < 2 * OUT_LANES * 4 + OUT_ROWS * 4 + 16384
    assert memory.temp_size_in_bytes < 2.5e9, memory
    assert memory.temp_size_in_bytes \
        < sparse_coalesce._WORKSPACE_WORDS * 4 * OUT_LANES, memory
