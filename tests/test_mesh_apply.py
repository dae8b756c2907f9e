"""The dense sketch of an operand on a mesh (``sketch.dense_mesh``,
parallel/shard_apply.py), through ``SketchTransform.apply``: CPU, the 8
forced host devices of conftest.py, small sizes, seeded.

The oracle is the undistributed mathematics — ``A_rows · Sᵀ`` in float32
with the operator of ``cellbench/references/dense_sketch.py`` (JLT; it
knows nothing of meshes) or the transform's own ``s_panel`` on the host (CT,
which the reference does not define) — upstream's determinism oracle:
sharded equals unsharded for one seed.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from libskylark_tpu import parallel as par
from libskylark_tpu import sketch as sk
from libskylark_tpu.base.context import Context
from libskylark_tpu.parallel import shard_apply
from libskylark_tpu.sketch import dense
from libskylark_tpu.sketch.dense import BLOCK_COLS

SEED = 55


@pytest.fixture()
def grid(devices):
    return par.make_mesh((2, 2), devices=devices[:4])


@pytest.fixture()
def line(devices):
    return par.make_mesh((4,), devices=devices[:4])


def _operator(T) -> np.ndarray:
    """The (s × N) operator outside the program under test."""
    if T.sketch_type == "JLT":
        from cellbench.references import dense_sketch as reference

        n_pad = -(-T.input_dim // BLOCK_COLS) * BLOCK_COLS
        alloc = T.allocation
        S = reference.operator(alloc.seed, alloc.counter, T.sketch_dim, n_pad)
        return np.asarray(S, np.float64)[:, :T.input_dim]
    return np.asarray(T.s_panel(0, T.input_dim), np.float64)


def _transform(family: str, n: int, s: int):
    ctx = Context(SEED)
    return sk.JLT(n, s, ctx) if family == "JLT" else sk.CT(n, s, ctx, C=2.0)


def _operand(shape, seed=0) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal(shape).astype(np.float32)


def _expected(T, A_host, rowwise: bool) -> np.ndarray:
    S = _operator(T)
    A64 = A_host.astype(np.float64)
    return A64 @ S.T if rowwise else S @ A64


def _close(out, want, tol=1e-5):
    err = np.max(np.abs(np.asarray(out, np.float64) - want)) / np.max(np.abs(want))
    assert err <= tol, err


def _spec(out) -> tuple:
    assert isinstance(out.sharding, NamedSharding)
    return shard_apply._spec_axes(out.sharding.spec, out.ndim)


# -- (1) [MC,MR] in, [MC,MR] out --------------------------------------------


@pytest.mark.parametrize("family", ["JLT", "CT"])
@pytest.mark.parametrize("rowwise", [True, False], ids=["rowwise", "columnwise"])
def test_grid2d_equals_the_undistributed_product(grid, family, rowwise):
    n, s, m = 1024, 32, 48
    T = _transform(family, n, s)
    A_host = _operand((m, n) if rowwise else (n, m))
    A = par.distribute(A_host, par.grid2d(grid))
    out = T.apply(A, sk.ROWWISE if rowwise else sk.COLUMNWISE)
    assert out.shape == ((m, s) if rowwise else (s, m))
    _close(out, _expected(T, A_host, rowwise))
    # rows as the operand's, the sketch axis over the axis that sharded N
    assert _spec(out) == (("rows",), ("cols",))
    shards = {sh.device: sh.data.shape for sh in out.addressable_shards}
    assert len(shards) == 4
    assert set(shards.values()) == {(out.shape[0] // 2, out.shape[1] // 2)}


# -- (2) 1D meshes, the ragged N, the s that does not divide ------------------


@pytest.mark.parametrize("n", [1024, 1000], ids=["even", "ragged"])
@pytest.mark.parametrize("layout", ["contracted", "free"])
@pytest.mark.parametrize("rowwise", [True, False], ids=["rowwise", "columnwise"])
def test_line_of_four(line, rowwise, layout, n):
    s, m = 16, 24
    T = _transform("JLT", n, s)
    A_host = _operand((m, n) if rowwise else (n, m), seed=1)
    over_columns = (layout == "contracted") == rowwise
    A = par.distribute(A_host, NamedSharding(
        line, P(None, "rows") if over_columns else P("rows", None)))
    out = T.apply(A, sk.ROWWISE if rowwise else sk.COLUMNWISE)
    _close(out, _expected(T, A_host, rowwise))
    # contracted axis sharded: psum_scatter leaves s over the line; free
    # axis sharded: no collective, the free axis stays where it was
    want = [(), ()]
    want[1 if over_columns else 0] = ("rows",)
    assert _spec(out) == tuple(want)


def test_all_axes_on_the_contracted_axis_and_a_sketch_width_that_does_not_divide(grid):
    n, m = 2048, 16
    A_host = _operand((m, n), seed=2)
    A = par.distribute(A_host, par.col_sharded(grid))
    T = _transform("JLT", n, 32)
    out = T.apply(A, sk.ROWWISE)
    _close(out, _expected(T, A_host, True))
    assert _spec(out) == ((), ("rows", "cols"))
    T30 = _transform("CT", n, 30)          # 30 % 4 != 0: psum, s replicated
    out = T30.apply(A, sk.ROWWISE)
    _close(out, _expected(T30, A_host, True))
    assert _spec(out) == ((), ())


def test_sharded_equals_unsharded_for_one_seed(grid):
    n, s, m = 1024, 32, 48
    A_host = _operand((m, n), seed=3)
    T = _transform("JLT", n, s)
    local = np.asarray(T.apply(jnp.asarray(A_host), sk.ROWWISE))
    out = np.asarray(T.apply(par.distribute(A_host, par.grid2d(grid)), sk.ROWWISE))
    np.testing.assert_allclose(out, local, atol=2e-6 * np.abs(local).max())


# -- (3) the compiled program: a reduce-scatter, no gather of the operand ----


def test_hlo_reduces_and_never_gathers_the_operand(grid):
    n, s, m = 2048, 64, 256
    T = _transform("JLT", n, s)
    spec = (("rows",), ("cols",))
    A = jax.ShapeDtypeStruct((m, n), jnp.float32, sharding=par.grid2d(grid))
    kd = jax.ShapeDtypeStruct((2,), jnp.uint32)
    text = jax.jit(functools.partial(
        shard_apply.dense_mesh, mesh=grid, spec=spec, seq_axis=1, dist=T.dist,
        s_dim=s, scale=T.scale, plan=None, scatter=True)
    ).lower(kd, A).compile().as_text()
    # XLA's CPU pipeline may keep the reduce-scatter or decompose it into an
    # all-reduce and a slice; either is the reduction over the pair
    assert re.search(r"\breduce-scatter(-start)?\(", text) or re.search(
        r"\ball-reduce(-start)?\(", text), "no reduction over the mesh"
    operand_shard = (m // 2) * (n // 2)
    for match in re.finditer(
            r"=\s*\(?\w+\[([\d,]*)\][^=\n]*\ball-gather(-start)?\(", text):
        dims = [int(d) for d in match.group(1).split(",") if d]
        assert int(np.prod(dims)) < operand_shard, match.group(0)


# -- (4) the kernel body under the interpreter equals the XLA loop -----------


@pytest.mark.parametrize("rowwise", [True, False], ids=["rowwise", "columnwise"])
def test_kernel_interpreted_equals_xla_loop(grid, rowwise):
    from libskylark_tpu.sketch import pallas_dense as pd

    n, s, m = 1024, 32, 64
    seq_axis = 1 if rowwise else 0
    T = _transform("JLT", n, s)
    A_host = _operand((m, n) if rowwise else (n, m), seed=4)
    A = par.distribute(A_host, par.grid2d(grid))
    mesh, spec, why = shard_apply.layout_of(A)
    assert why is None and mesh is grid
    local = shard_apply._local_shape(A.shape, mesh, spec, seq_axis)
    assert local == ((m // 2, n // 2) if rowwise else (n // 2, m // 2))
    plan = shard_apply._kernel_plan(T, local, A.dtype, seq_axis, True, True)
    assert isinstance(plan, pd.Plan) and plan.interpret
    statics = dict(mesh=mesh, spec=spec, seq_axis=seq_axis, dist=T.dist,
                   s_dim=s, scale=T.scale, scatter=True)
    kd = T.allocation.key_data
    kernel = shard_apply._program()(kd, A, plan=plan, **statics)
    loop = shard_apply._program()(kd, A, plan=None, **statics)
    assert kernel.sharding == loop.sharding
    # the kernel's tolerance: bf16x3 against XLA's float32 (PERF.md §2)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(loop),
                               atol=1e-4 * np.abs(np.asarray(loop)).max())
    _close(kernel, _expected(T, A_host, rowwise), tol=1e-4)


def test_thin_callers_share_the_program_and_replicate(line):
    n, s, m = 2048, 16, 8
    T = _transform("JLT", n, s)
    A_host = _operand((m, n), seed=5)
    before = shard_apply._program().stats.executions
    out = shard_apply.rowwise(T, A_host, line)
    assert shard_apply._program().stats.executions == before + 1
    assert _spec(out) == ((), ())
    _close(out, _expected(T, A_host, True))
    # under a caller's trace it is part of the caller's program
    traced = jax.jit(lambda X: shard_apply.columnwise(T, X, line))(A_host.T)
    assert shard_apply._program().stats.executions == before + 1
    _close(traced, _expected(T, A_host.T, False))


# -- (5) one device: today's dispatch, bit for bit ---------------------------


@pytest.mark.parametrize("placed", ["default", "mesh_of_one"])
def test_one_device_keeps_its_dispatch(devices, monkeypatch, placed):
    def never(*a, **k):
        raise AssertionError("the mesh route took a one-device operand")

    monkeypatch.setattr(shard_apply, "apply_on_mesh", never)
    n, s, m = 512, 16, 8
    T = _transform("JLT", n, s)
    A = jnp.asarray(_operand((m, n), seed=6))
    if placed == "mesh_of_one":
        A = jax.device_put(A, NamedSharding(
            par.make_mesh((1,), devices=devices[:1]), P("rows", None)))
    assert not dense.on_mesh(A)
    out = T.apply(A, sk.ROWWISE)
    want = jnp.asarray(A) @ T.s_panel(0, n).T     # the xla_full route's product
    assert np.array_equal(np.asarray(out), np.asarray(want))


def test_a_tracer_is_never_on_a_mesh(grid):
    n, s, m = 1024, 32, 48
    T = _transform("JLT", n, s)
    A = par.distribute(_operand((m, n), seed=7), par.grid2d(grid))
    seen = []

    def f(X):
        seen.append(dense.on_mesh(X))
        return T.apply(X, sk.ROWWISE)

    out = jax.jit(f)(A)
    assert seen == [False]
    _close(out, _expected(T, np.asarray(A), True))


def test_mesh_applies_never_auto_materialize(grid):
    n, s, m = 1024, 32, 48
    T = _transform("JLT", n, s)
    A = par.distribute(_operand((m, n), seed=8), par.grid2d(grid))
    first = np.asarray(T.apply(A, sk.ROWWISE))
    for _ in range(4):
        again = np.asarray(T.apply(A, sk.ROWWISE))
    assert T._op_cache is None
    assert np.array_equal(first, again)


# -- (6), (7) the span and the counter ---------------------------------------


def _spans_of(call):
    from libskylark_tpu import telemetry
    from libskylark_tpu.telemetry import metrics, trace

    before = metrics._ENABLED
    trace.clear_finished()
    telemetry.set_enabled(True)
    try:
        out = call()
        return out, list(trace.finished_spans())
    finally:
        metrics._ENABLED = before
        trace.clear_finished()


def test_an_unserved_sharding_takes_the_xla_route_and_says_why(grid):
    from jax._src.sharding_impls import GSPMDSharding

    n, s, m = 1024, 32, 48
    T = _transform("JLT", n, s)
    A_host = _operand((m, n), seed=9)
    named = par.grid2d(grid)
    A = jax.device_put(A_host, GSPMDSharding(
        named._device_assignment, named._to_xla_hlo_sharding(2)))
    assert dense.on_mesh(A) and not shard_apply.serves(A)
    before = shard_apply._program().stats.executions
    out, spans = _spans_of(lambda: T.apply(A, sk.ROWWISE))
    assert shard_apply._program().stats.executions == before
    _close(out, _expected(T, A_host, True))
    (dispatch,) = [sp for sp in spans if sp.name == "sketch.dispatch"]
    assert dispatch.attrs["route"] == "xla: sharding is a GSPMDSharding"
    assert "path" not in dispatch.attrs
    (root,) = [sp for sp in spans if sp.name == "sketch.apply"]
    assert root.attrs["path"] == "xla_full"


def test_span_and_counter_carry_the_collective_bytes(grid):
    n, s, m = 1024, 32, 48
    T = _transform("JLT", n, s)
    A = par.distribute(_operand((m, n), seed=10), par.grid2d(grid))
    T.apply(A, sk.ROWWISE)                          # compiled ahead of the span
    labels = dict(family="JLT", collective="psum_scatter")
    counted = shard_apply._COLLECTIVE_BYTES.value(**labels)
    _, spans = _spans_of(lambda: T.apply(A, sk.ROWWISE))
    (dispatch,) = [sp for sp in spans if sp.name == "sketch.dispatch"]
    attrs = dispatch.attrs
    # a device's partial is (m/2 × s) float32; a reduce-scatter over its pair
    # sends half of it
    sent = (m // 2) * s * 4 // 2
    assert attrs["collective_bytes"] == sent
    assert shard_apply._COLLECTIVE_BYTES.value(**labels) - counted == sent
    assert {k: attrs[k] for k in (
        "path", "route", "family", "grid", "spec", "orientation",
        "local_shape", "kernel", "collective", "reduce_over", "exchange",
        "panels")} == {
        "path": "mesh", "route": "program", "family": "JLT", "grid": "2x2",
        "spec": "PartitionSpec('rows', 'cols')", "orientation": "rowwise",
        "local_shape": (m // 2, n // 2), "kernel": "xla_blocks",
        "collective": "psum_scatter", "reduce_over": ("cols",),
        "exchange": "single", "panels": 1}
    (root,) = [sp for sp in spans if sp.name == "sketch.apply"]
    assert root.attrs["path"] == "mesh"
    # one handover an apply: the engine.execute inside the dispatch span
    assert len([sp for sp in spans if sp.name == "engine.execute"]) == 1


@pytest.mark.parametrize("collective,p,sent", [
    ("none", 1, 0), ("psum_scatter", 2, 512), ("psum", 2, 1024),
    ("psum_scatter", 4, 768), ("psum", 4, 1536), ("ppermute_ring", 2, 512),
    ("ppermute_ring", 4, 768)])
def test_collective_bytes_from_the_shapes(collective, p, sent):
    assert shard_apply.collective_bytes(collective, p, 1024) == sent


# -- (8) the exchange behind the contraction: row panels, a ring of ppermutes --

M_TILE = 8      # the interpreted kernel's row tile in these tests


@pytest.fixture()
def interpreted(monkeypatch):
    """The mesh route under the interpreted kernel at ``M_TILE`` rows a tile
    and the "hbm" residency (a zero scratch cap, as tests/test_pallas_dense.py
    ``force_hbm``): what the dispatch plans on a TPU, driven on the CPU mesh."""
    from libskylark_tpu.sketch import pallas_dense as pd
    from libskylark_tpu.sketch import params

    monkeypatch.setattr(pd, "_SCRATCH_CAP_BYTES", 0)
    plan = shard_apply._kernel_plan
    monkeypatch.setattr(
        shard_apply, "_kernel_plan",
        lambda T, local, dtype, seq_axis, use_pallas, interpret: plan(
            T, local, dtype, seq_axis, True, True))
    before = params.get_pallas_m_tile()
    params.set_pallas_m_tile(M_TILE)
    yield
    params.set_pallas_m_tile(before)


def _laid(request, layout: str, shape, seed: int):
    """``(A_host, A)`` with the operand [MC,MR] over the 2 × 2 grid (the
    contracted axis over a pair: p = 2) or its contracted axis over the line
    of four (p = 4)."""
    A_host = _operand(shape, seed=seed)
    if layout == "grid":
        sharding = par.grid2d(request.getfixturevalue("grid"))
    else:
        sharding = NamedSharding(request.getfixturevalue("line"),
                                 P(None, "rows"))
    return A_host, par.distribute(A_host, sharding)


def _single(monkeypatch):
    """The parent's program: no local extent holds two panels."""
    monkeypatch.setattr(shard_apply, "_PANEL_TILES", 1 << 30)


@pytest.mark.parametrize("tiles", [16, 19], ids=["whole_panels", "short_last"])
@pytest.mark.parametrize("layout", ["grid", "line"], ids=["p2", "p4"])
def test_pipelined_equals_single(request, monkeypatch, interpreted, layout,
                                 tiles):
    """Row panels, each panel's chunks on a ring of ppermutes, against the
    one psum_scatter: the same bits over a pair (a + b in either order), the
    file's tolerance over four (the ring adds in another order)."""
    n, s = 1536, 64
    rows = tiles * M_TILE           # a device's free extent
    T = _transform("JLT", n, s)
    A_host, A = _laid(request, layout, (2 * rows if layout == "grid" else rows,
                                        n), seed=11)
    statics, attrs = shard_apply.route(T, A, 1)
    p = 2 if layout == "grid" else 4
    assert (attrs["exchange"], attrs["panels"], attrs["collective"]) == (
        "pipelined", -(-tiles // shard_apply._PANEL_TILES), "ppermute_ring")
    assert attrs["collective_bytes"] == rows * s * 4 * (p - 1) // p
    kd = T.allocation.key_data
    program = jax.jit(functools.partial(shard_apply.dense_mesh, **statics))
    pipelined = program(kd, A)
    _single(monkeypatch)
    assert shard_apply.route(T, A, 1)[1]["exchange"] == "single"
    single = jax.jit(functools.partial(shard_apply.dense_mesh, **statics))(kd, A)
    assert pipelined.sharding == single.sharding
    if p == 2:
        assert np.array_equal(np.asarray(pipelined), np.asarray(single))
    else:
        np.testing.assert_allclose(
            np.asarray(pipelined), np.asarray(single),
            atol=1e-5 * np.abs(np.asarray(single)).max())
    _close(pipelined, _expected(T, A_host, True), tol=1e-4)


@pytest.mark.parametrize("case", [
    "one_panel", "s_not_divisible", "columnwise", "xla_blocks",
    "replicated_result", "tracer"])
def test_what_keeps_the_single_collective(grid, monkeypatch, interpreted,
                                          case):
    """Fewer than two panels in the local free extent, a sketch width the
    pair does not divide, the columnwise orientation, the XLA block loop, the
    thin callers' replicated result and a caller's trace keep the parent's
    program: no collective-permute in what is lowered."""
    n, s, m = 1536, 64, 2 * 16 * M_TILE
    rowwise = case != "columnwise"
    if case == "one_panel":
        m = 2 * 15 * M_TILE
    if case == "s_not_divisible":
        s = 63
    T = _transform("JLT", n, s)
    A_host = _operand((m, n) if rowwise else (n, m), seed=12)
    if case in ("replicated_result", "tracer"):
        line = par.make_mesh((2,), devices=list(grid.devices.flat)[:2])
        call = functools.partial(shard_apply.rowwise, T, mesh=line,
                                 use_pallas=True, interpret=True)
        text = jax.jit(lambda X: call(X)).lower(A_host).as_text()
        if case == "replicated_result":
            _close(call(A_host), _expected(T, A_host, True), tol=1e-4)
    else:
        A = par.distribute(A_host, par.grid2d(grid))
        if case == "xla_blocks":
            monkeypatch.setattr(shard_apply, "_kernel_plan",
                                lambda *a, **k: None)
        statics, attrs = shard_apply.route(T, A, 1 if rowwise else 0)
        assert (attrs["exchange"], attrs["panels"]) == ("single", 1)
        assert attrs["collective"] == (
            "psum" if case == "s_not_divisible" else "psum_scatter")
        assert (statics["plan"] is None) == (case == "xla_blocks")
        text = jax.jit(functools.partial(
            shard_apply.dense_mesh, **statics)).lower(
            T.allocation.key_data, A).as_text()
    assert "collective_permute" not in text
    assert ("all_reduce" in text) != ("reduce_scatter" in text)


def test_span_and_counter_of_the_pipelined_route(grid, interpreted):
    n, s, rows = 1536, 64, 17 * M_TILE
    T = _transform("JLT", n, s)
    A_host = _operand((2 * rows, n), seed=13)
    A = par.distribute(A_host, par.grid2d(grid))
    T.apply(A, sk.ROWWISE)                          # compiled ahead of the span
    labels = dict(family="JLT", collective="ppermute_ring")
    counted = shard_apply._COLLECTIVE_BYTES.value(**labels)
    out, spans = _spans_of(lambda: T.apply(A, sk.ROWWISE))
    _close(out, _expected(T, A_host, True), tol=1e-4)
    assert _spec(out) == (("rows",), ("cols",))
    (dispatch,) = [sp for sp in spans if sp.name == "sketch.dispatch"]
    attrs = dispatch.attrs
    # the same halves travel: half of a device's (rows × s) float32 partial
    sent = rows * s * 4 // 2
    assert shard_apply._COLLECTIVE_BYTES.value(**labels) - counted == sent
    assert {k: attrs[k] for k in (
        "kernel", "operator_residency", "m_tile", "collective", "exchange",
        "panels", "collective_bytes", "reduce_over")} == {
        "kernel": "pallas_planes", "operator_residency": "hbm",
        "m_tile": M_TILE, "collective": "ppermute_ring",
        "exchange": "pipelined", "panels": 3, "collective_bytes": sent,
        "reduce_over": ("cols",)}
    assert len([sp for sp in spans if sp.name == "engine.execute"]) == 1


def test_hlo_of_the_pipelined_route_permutes_and_never_gathers(grid,
                                                               interpreted):
    n, s, rows = 1536, 64, 16 * M_TILE
    T = _transform("JLT", n, s)
    A = jax.ShapeDtypeStruct((2 * rows, n), jnp.float32,
                             sharding=par.grid2d(grid))
    statics = shard_apply._statics(T, A, grid, (("rows",), ("cols",)), 1,
                                   None, False, True)[1]
    text = jax.jit(functools.partial(shard_apply.dense_mesh, **statics)).lower(
        jax.ShapeDtypeStruct((2,), jnp.uint32), A).compile().as_text()
    assert len(re.findall(r"\bcollective-permute(-start)?\(", text)) == 2
    assert not re.search(r"\b(reduce-scatter|all-reduce)(-start)?\(", text)
    operand_shard = rows * (n // 2)
    for match in re.finditer(
            r"=\s*\(?\w+\[([\d,]*)\][^=\n]*\ball-gather(-start)?\(", text):
        dims = [int(d) for d in match.group(1).split(",") if d]
        assert int(np.prod(dims)) < operand_shard, match.group(0)
