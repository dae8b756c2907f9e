"""The FJLT/``wht`` apply as one compiled mix-and-sample program
(``sketch.fjlt_mix_sample``, sketch/fjlt.py, sketch/fut.py,
sketch/pallas_wht.py), on the CPU:

- *plain reference*: ``cellbench/references/srht.py`` (imports nothing of the
  program; D and idx from the published stream definition, the transform a
  butterfly of adds) — both orientations, ragged free extents, unequal
  Kronecker factors, as many samples as the axis is long, with repeats;
- *operator oracle*: ``FJLT.operator_panel`` (the closed-form sampled
  Hadamard rows) as a dense matmul;
- *dyadic bit-equality* with ``fut.fwht_sketch`` where tests/test_fwht.py
  promises it (n, s even powers of two, lattice data), on this backend's
  routes and on the v5e's (the block kernel interpreted, the bfloat16
  three-way split);
- the sampled last Kronecker factor against the full transform then gather,
  and its chunk of samples: a multiple of 8 whose rows fill no whole index
  tiles where the window holds one, the same samples whatever the chunk;
- the block kernel, interpreted, against its XLA twin;
- one program, no recompile, the span's attributes and the counter.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench.references import srht as reference
from libskylark_tpu import sketch as sk
from libskylark_tpu.base import randgen, threefry
from libskylark_tpu.base.context import Context
from libskylark_tpu.sketch import fjlt, fut, pallas_wht

import json
import pathlib

# the configuration's limit
REL_MAX = json.loads((pathlib.Path(__file__).parent.parent / "cellbench/configs"
                      / "fjlt_blendenpik_m1048576_n1024.json").read_text()
                     )["limits"]["rel_max"]


def _operand(n, m, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal((n, m)),
                       jnp.float32)


def _rel(got, ref):
    return float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))


SHAPES = [(1 << 10, 64), (1 << 10, 256), (1 << 11, 64), (1 << 11, 512),
          (1 << 12, 64), (1 << 12, 1024), (1 << 12, 4096)]


@pytest.mark.parametrize("n,s", SHAPES)
@pytest.mark.parametrize("m", [37, 130])
def test_program_against_the_plain_reference_both_orientations(n, s, m):
    seed = n + s + m
    A = _operand(n, m, seed)
    T = sk.FJLT(n, s, Context(seed), fut="wht")
    D, idx = reference.streams(seed, 0, n, s)
    ref = reference.apply_cols(A, D, idx)
    assert ref.shape == (s, m)
    assert _rel(T.apply(A, sk.COLUMNWISE), ref) < 5e-6
    assert _rel(T.apply(A.T, sk.ROWWISE).T, ref) < 5e-6
    if s == n:
        assert len(np.unique(np.asarray(idx))) < s      # repeats among them


@pytest.mark.parametrize("n,s", [(1 << 10, 64), (1 << 11, 512), (1 << 12, 4096)])
def test_program_against_the_operator_panel(n, s):
    A = _operand(n, 24, 3)
    T = sk.FJLT(n, s, Context(11), fut="wht")
    ref = jnp.asarray(T.operator_panel(0, n)) @ A
    assert _rel(T.apply(A, sk.COLUMNWISE), ref) < 5e-6
    # the same operator as the streams the transform hands out
    D, idx = reference.streams(11, 0, n, s)
    assert np.array_equal(np.asarray(T.diagonal()), np.asarray(D))
    assert np.array_equal(np.asarray(T.sample_indices()), np.asarray(idx))


@pytest.mark.parametrize("n,s", [(256, 64), (4096, 256), (4096, 1024)])
@pytest.mark.parametrize("rowwise", [False, True])
@pytest.mark.parametrize("kernel", ["xla", "pallas_blocks"])
def test_dyadic_bit_equality_with_fwht_sketch(n, s, rowwise, kernel,
                                              monkeypatch):
    """n, s even powers of two + lattice data: every intermediate is an
    exact dyadic rational, so the program, the fused serve composition and
    the operator-panel matmul agree bit for bit — on this backend's routes
    (``"xla"``) and on the ones a v5e takes (``"pallas_blocks"``: the block
    kernel, interpreted here, for a columnwise operand, in blocks the small
    axis admits; the bfloat16 three-way split for a rowwise one, which the
    kernel does not serve). The operand split in three bfloat16 parts is
    exact on the lattice and every sum is an integer under 2²⁴, so the
    order of accumulation cannot show."""
    A = jnp.asarray(np.random.default_rng(5).integers(-8, 9, (n, 12)),
                    jnp.float32)
    T = sk.FJLT(n, s, Context(7), fut="wht")
    if kernel == "pallas_blocks":
        block = min(n, 1024)            # 256: one block of two groups
        monkeypatch.setattr(pallas_wht, "mix_blocks", functools.partial(
            pallas_wht.mix_blocks.__wrapped__, interpret=True))
        monkeypatch.setattr(
            sk.FJLT, "mix_plan",
            lambda self, A, rowwise: (("xla_bf16x3", block, 128) if rowwise
                                      else ("pallas_blocks", block, 12)))
    got = T.apply(A.T if rowwise else A, sk.ROWWISE if rowwise else sk.COLUMNWISE)
    got = got.T if rowwise else got
    fused = fut.fwht_sketch(A, T.diagonal(), T.sample_indices(),
                            1.0 / math.sqrt(n), math.sqrt(n / s), axis=0)
    panel = jnp.asarray(T.operator_panel(0, n)) @ A
    assert np.array_equal(np.asarray(got), np.asarray(fused))
    assert np.array_equal(np.asarray(got), np.asarray(panel))
    assert np.array_equal(
        np.asarray(got), np.asarray(fjlt.srht_serve_apply(
            T.allocation.key_data, A, s_dim=s, rowwise=False)))


@pytest.mark.parametrize("n,block", [(1 << 10, 128), (1 << 11, 256),
                                     (1 << 12, 2048), (1 << 12, 4096)])
def test_sampled_last_factor_against_full_transform_then_gather(
        n, block, monkeypatch):
    X = _operand(n, 20, 9)
    idx = jnp.asarray(np.random.default_rng(1).integers(0, n, 300), jnp.int32)
    full = fut.wht(X, axis=0)[idx]
    got = fut.sample_outer(fut.wht_blocks(X, block), idx, block)
    assert _rel(got, full) < 2e-6
    # held a few samples at a time, the same rows
    monkeypatch.setattr(fut, "_SAMPLE_CHUNK_BYTES", 1 << 12)
    chunked = fut.sample_outer(fut.wht_blocks(X, block), idx, block)
    assert np.array_equal(np.asarray(chunked), np.asarray(got))


@pytest.mark.parametrize("w", [128, 256, 1024])
@pytest.mark.parametrize("a", [2, 8, 64, 128])
def test_the_sample_chunk_steps_off_whole_index_tiles(a, w):
    """The chunk is the byte rule's, up to the next multiple of 8 (at most 64
    samples past) whose ``chunk · a`` gathered rows the v5e compiler takes 256
    a step — at the cell (a = 64, w = 1024) 264 samples, not 256 = sixteen
    whole index tiles. Past a = 64 every multiple of 8 samples gathers whole
    tiles (a · 8 = 1024 · k) and the byte rule's chunk stands."""
    by_bytes = fut._SAMPLE_CHUNK_BYTES // (a * w * 4)
    chunk = fut._sample_chunk(a * w * 4, a)
    assert chunk % 8 == 0 and 0 <= chunk - by_bytes < 64
    window = range(by_bytes, by_bytes + 64, 8)
    fast = [c for c in window if fut._gathers_fast(c * a)]
    assert not fut._gathers_fast(by_bytes * a)          # whole tiles, all twelve
    assert bool(fast) == (a < 128)
    assert chunk == (fast[0] if fast else by_bytes)
    assert fut.sample_outer_chunk(a, w, 1 << 20) == chunk
    assert fut.sample_outer_chunk(a, w, 100) == 100     # one chunk holds them
    assert fut.sample_outer_chunk(1, w, 100) == 100     # one block: no factor
    if (a, w) == (64, 1024):
        assert chunk == 264


@pytest.mark.parametrize("s", [263, 264, 265, 4096])
def test_sampled_last_factor_chunked_is_the_unchunked_to_the_bit(
        s, monkeypatch):
    """A chunk only partitions the samples (the last one padded with sample 0,
    whose rows are gathered and dropped): the cell's a = 64 rows a sample,
    264 samples a chunk, against all samples at once — to the bit on this
    backend, whose ``reduce`` adds a sample's rows in one order whatever the
    chunk (the v5e's follows the chunk's shape: ``PERF.md`` §6, PR 50)."""
    n, block, w = 1 << 12, 64, 16
    a = n // block
    Y = fut.wht_blocks(_operand(n, w, 4), block)
    idx = jnp.asarray(np.random.default_rng(s).integers(0, n, s), jnp.int32)
    monkeypatch.setattr(fut, "_SAMPLE_CHUNK_BYTES", 256 * a * w * 4)
    assert fut._sample_chunk(a * w * 4, a) == 264
    chunked = jax.jit(lambda Y, idx: fut.sample_outer(Y, idx, block))(Y, idx)
    monkeypatch.setattr(fut, "_SAMPLE_CHUNK_BYTES", 1 << 40)
    assert fut._sample_chunk(a * w * 4, a) > s
    whole = jax.jit(lambda Y, idx: fut.sample_outer(Y, idx, block))(Y, idx)
    assert chunked.shape == (s, w)
    assert np.array_equal(np.asarray(chunked), np.asarray(whole))
    assert _rel(chunked, fut.wht(Y.reshape(a, block, w), axis=0)
                .reshape(n, w)[idx]) < 2e-6


@pytest.mark.parametrize("block,factors", [
    (1 << 14, (128, 128)), (1 << 11, (64, 32)), (1 << 7, (128,)),
    (1 << 10, (32, 32)), (1 << 20, (128, 128, 64)), (2, (2,))])
def test_block_factors(block, factors):
    assert fut.block_factors(block) == factors
    assert math.prod(factors) == block and max(factors) <= 128
    with pytest.raises(ValueError):
        fut.block_factors(block + 1 if block > 2 else 3)


@pytest.mark.parametrize("n", [1 << 9, 1 << 11, 1 << 12])
def test_wht_blocks_is_the_transform_and_exact_in_bf16(n):
    """No transposed copy, and the bfloat16 route splits only the operand:
    both are the dense Sylvester matmul, on lattice data bit for bit."""
    X = jnp.asarray(np.random.default_rng(2).integers(-4, 5, (n, 6)), jnp.float32)
    H = fut._hadamard_np(n)
    for split in (False, True):
        assert np.array_equal(np.asarray(fut.wht_blocks(X, n, split)),
                              H @ np.asarray(X))
    Y = _operand(n, 6, 4)
    assert _rel(fut.wht_blocks(Y, n, True), jnp.asarray(H) @ Y) < 2e-6
    half = fut.wht_blocks(Y, n // 2)
    assert _rel(half[: n // 2], jnp.asarray(fut._hadamard_np(n // 2)) @ Y[: n // 2]) < 2e-6


@pytest.mark.parametrize("kernel", ["xla_f32", "xla_bf16x3"])
@pytest.mark.parametrize("rowwise", [False, True])
def test_tiles_of_the_free_axis_and_a_ragged_rest(kernel, rowwise):
    n, s, m = 1 << 10, 256, 150           # two tiles of 64 and a rest of 22
    A = _operand(n, m, 6)
    T = sk.FJLT(n, s, Context(2), fut="wht")
    ref = jnp.asarray(T.operator_panel(0, n)) @ A
    got = fjlt.fjlt_mix_sample(
        T.allocation.key_data, A.T if rowwise else A, s_dim=s, rowwise=rowwise,
        kernel=kernel, block=256, tile=64)
    assert _rel(got.T if rowwise else got, ref) < 5e-6


def test_bf16_operand_control_fails_the_configurations_rel_max():
    n, s = 1 << 12, 1024
    A = _operand(n, 32, 8)
    T = sk.FJLT(n, s, Context(4), fut="wht")
    D, idx = reference.streams(4, 0, n, s)
    ref = reference.apply_cols(A, D, idx)
    assert _rel(T.apply(A, sk.COLUMNWISE), ref) < REL_MAX / 2
    assert _rel(reference.apply_cols(A, D, idx, "bf16x2"), ref) > REL_MAX
    assert _rel(reference.apply_cols(A, D, idx, "bf16"), ref) > 100 * REL_MAX
    assert _rel(T.apply(A.astype(jnp.bfloat16).astype(jnp.float32),
                        sk.COLUMNWISE), ref) > REL_MAX


# -- the block kernel, interpreted ------------------------------------------


@pytest.mark.parametrize("n,block,tile", [
    (2048, 1024, 128), (4096, 2048, 256), (4096, 4096, 128),
    # the butterflies' other shapes: two groups (one radix-2 stage, under
    # the plan's floor of eight) and four (one radix-4 stage, eight blocks)
    (256, 256, 128), (4096, 512, 128)])
def test_block_kernel_against_its_xla_twin(n, block, tile):
    A = _operand(n, 256, 3)
    D = jnp.asarray(np.random.default_rng(0).choice([-1.0, 1.0], n), jnp.float32)
    got = pallas_wht.mix_blocks(A, D, block=block, tile=tile, interpret=True)
    assert _rel(got, fut.wht_blocks(D[:, None] * A, block)) < 2e-6
    lattice = jnp.round(4 * A)
    assert np.array_equal(
        np.asarray(pallas_wht.mix_blocks(lattice, D, block=block, tile=tile,
                                         interpret=True)),
        np.asarray(fut.wht_blocks(D[:, None] * lattice, block)))


def test_block_kernel_plan_declines_off_the_tpu_and_odd_shapes():
    assert pallas_wht.plan((1 << 14, 256), jnp.float32) is None       # the CPU
    ok = functools.partial(pallas_wht.plan, interpret=True)
    assert ok((1 << 20, 1024), jnp.float32) == (16384, 256)
    assert ok((1 << 12, 384), jnp.float32) == (4096, 128)
    assert ok((1 << 12, 200), jnp.float32) is None        # no lane multiple
    assert ok((512, 256), jnp.float32) is None            # under eight groups
    assert ok((256, 128), jnp.float32) is None
    assert ok((3 << 10, 256), jnp.float32) is None        # no power of two
    assert ok((1 << 12, 256), jnp.bfloat16) is None


@pytest.fixture
def interpreted(monkeypatch):
    """Drive the kernel route through ``T.apply`` off the TPU."""
    monkeypatch.setattr(pallas_wht, "mix_blocks", functools.partial(
        pallas_wht.mix_blocks.__wrapped__, interpret=True))
    monkeypatch.setattr(
        sk.FJLT, "mix_plan",
        lambda self, A, rowwise: (("xla_f32", 1024, 128) if rowwise else
                                  ("pallas_blocks", 1024, 128)))


def test_kernel_route_through_apply(interpreted):
    n, s = 1 << 12, 512                   # four blocks: the gather has work
    A = _operand(n, 256, 12)
    T = sk.FJLT(n, s, Context(21), fut="wht")
    D, idx = reference.streams(21, 0, n, s)
    assert _rel(T.apply(A, sk.COLUMNWISE), reference.apply_cols(A, D, idx)) < 5e-6


# -- the route: what takes it, what keeps the eager composition -------------


def test_other_mixers_dtypes_and_axes_keep_the_eager_route():
    """Each mixer's own rule: ``wht`` the powers of two, ``dct`` / ``dht``
    the heights ``fut.dft_factors`` splits (tests/test_fjlt_dct_program.py);
    past it, and for another dtype, the eager composition."""
    A = _operand(1 << 10, 8, 1)
    for name in ("dct", "dht"):           # served since the blocked DFT
        assert sk.FJLT(1 << 10, 64, Context(1), fut=name).mix_plan(A, False) == (
            "xla_dft", fut.dft_factors(1 << 10), A.shape[1])   # whole rows
    T = sk.FJLT(1 << 10, 64, Context(1), fut="wht")
    assert T.mix_plan(A.astype(jnp.bfloat16), False) is None
    assert T.mix_plan(A, False) == ("xla_f32", 1 << 10, fjlt.MIX_TILE)
    assert fjlt._xla_plan(1 << 20, jnp.float32) == (
        "xla_f32", fjlt.MIX_BLOCK, fjlt.MIX_TILE)
    program = fjlt._mix_program()
    for name in ("dct", "dht"):           # 1009 is prime: no split, eager
        R = sk.FJLT(1009, 64, Context(1), fut=name)
        assert fut.dft_factors(1009) is None
        assert R.mix_plan(_operand(1009, 8), False) is None
        before = program.stats.executions
        assert R.apply(_operand(1009, 8), sk.COLUMNWISE).shape == (64, 8)
        assert program.stats.executions == before
    with pytest.raises(ValueError):       # the Hadamard mixer has no such height
        sk.FJLT(1000, 64, Context(1), fut="wht").apply(
            _operand(1000, 8), sk.COLUMNWISE)
    low = T.apply(A.astype(jnp.bfloat16), sk.COLUMNWISE)
    assert low.dtype == jnp.bfloat16 and low.shape == (64, 8)


def test_an_operand_on_several_devices_keeps_the_eager_composition():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("needs two devices")
    n, s = 1 << 10, 128
    A = _operand(n, 16, 5)
    T = sk.FJLT(n, s, Context(15), fut="wht")
    sharded = jax.device_put(
        A, NamedSharding(Mesh(np.asarray(devices[:2]), ("c",)), P(None, "c")))
    assert T.mix_plan(sharded, False) is None
    assert T.mix_plan(A, False) is not None
    program = fjlt._mix_program()
    before = program.stats.executions
    out = T.apply(sharded, sk.COLUMNWISE)
    assert program.stats.executions == before
    assert _rel(out, T.apply(A, sk.COLUMNWISE)) < 2e-6


@pytest.mark.parametrize("spec", [(None, "c"), ("c", None)])
def test_a_traced_operand_that_is_sharded_gets_the_same_sketch(spec):
    """Under a caller's jit the placement is unreadable and the XLA route
    serves (``mix_plan``): the result is the unsharded one, whichever axis
    the caller's operand is sharded along and the tile walk slices."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("needs two devices")
    n, s, m = 1 << 10, 128, 2 * fjlt.MIX_TILE + 2 * 20
    A = _operand(n, m, 12)
    T = sk.FJLT(n, s, Context(18), fut="wht")
    sharded = jax.device_put(
        A, NamedSharding(Mesh(np.asarray(devices[:2]), ("c",)), P(*spec)))
    inside = jax.jit(lambda x: T.apply(x, sk.COLUMNWISE))(sharded)
    assert _rel(inside, T.apply(A, sk.COLUMNWISE)) < 2e-6


def test_the_serve_program_is_the_same_program_a_lane():
    n, s = 1 << 10, 128
    T = sk.FJLT(n, s, Context(16), fut="wht")
    U = sk.FJLT(n, s, Context(17), fut="wht")
    A, B = _operand(n, 24, 6), _operand(n, 24, 7)
    lanes = jax.vmap(functools.partial(
        fjlt.srht_serve_apply, s_dim=s, rowwise=False))(
            jnp.stack([T.allocation.key_data, U.allocation.key_data]),
            jnp.stack([A, B]))
    assert np.array_equal(np.asarray(lanes[0]),
                          np.asarray(T.apply(A, sk.COLUMNWISE)))
    assert np.array_equal(np.asarray(lanes[1]),
                          np.asarray(U.apply(B, sk.COLUMNWISE)))
    with pytest.raises(ValueError, match="power-of-2"):
        fjlt.srht_serve_apply(T.allocation.key_data, A[:1000], s_dim=s, rowwise=False)


# -- the solvers that send it ------------------------------------------------


def test_the_least_squares_solvers_mix_a_power_of_two_height_with_hadamard():
    from libskylark_tpu.algorithms import regression

    assert fjlt.solver_fut(1 << 20) == "wht" and fjlt.solver_fut(1000) == "dct"
    assert fjlt.solver_fut(1_000_000) == "dct"
    params = regression.AcceleratedParams()
    assert regression._accel_transform(
        1 << 10, 8, Context(1), params)._fut_name == "wht"
    other = regression._accel_transform(1000, 8, Context(1), params)
    assert other._fut_name == "dct"
    # either mixer is the one compiled program at a height its rule takes
    assert other.mix_plan(_operand(1000, 8), False)[0] == "xla_dft"
    assert fut.dft_factors(1_000_000) == (100, 125, 80)


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


def test_blendenpik_r_opens_the_fut_span():
    from libskylark_tpu.algorithms import regression

    m, n = 1 << 11, 16
    A = _operand(m, n, 8)
    T = regression._accel_transform(m, n, Context(2),
                                    regression.AcceleratedParams())
    R, spans = _spans_of(lambda: regression._blendenpik_r(A, T))
    dispatch = [sp for sp in spans if sp.name == "sketch.dispatch"]
    assert [sp.attrs["path"] for sp in dispatch] == ["fut"]
    assert dispatch[0].attrs["elements"] == m * n
    assert dispatch[0].attrs["sampled"] == 4 * n * n
    # R preconditions A: A·R⁻¹ is near-orthonormal
    Q = jnp.linalg.solve(R.T, A.T).T
    assert float(jnp.linalg.cond(Q)) < 3.0


@pytest.mark.parametrize("solver", ["fast", "approximate"])
def test_the_solvers_run_the_program_inside_theirs(solver, monkeypatch):
    from libskylark_tpu.nla import least_squares

    calls = []
    inner = fjlt.fjlt_mix_sample

    def counted(key_data, A, **statics):
        calls.append((A.shape, statics["kernel"]))
        return inner(key_data, A, **statics)

    monkeypatch.setattr(fjlt, "fjlt_mix_sample", counted)
    m, n = 1 << 12, 12
    rng = np.random.default_rng(3)
    A = _operand(m, n, 10)
    x = jnp.asarray(rng.standard_normal((n, 2)), jnp.float32)
    B = A @ x + 1e-3 * _operand(m, 2, 11)
    exact = jnp.linalg.lstsq(A, B)[0]
    if solver == "fast":
        X, iters = least_squares.fast_least_squares(A, B, Context(31))
        assert int(iters) > 0
        assert _rel(X, exact) < 1e-4
        assert calls == [((m, n), "xla_f32")]
    else:
        X = least_squares.approximate_least_squares(A, B, Context(32))
        assert _rel(X, exact) < 5e-2
        assert calls == [((m, n), "xla_f32"), ((m, 2), "xla_f32")]


def test_under_a_callers_jit_it_is_part_of_the_callers_program():
    n, s = 1 << 10, 128
    A = _operand(n, 16, 2)
    T = sk.FJLT(n, s, Context(6), fut="wht")
    program = fjlt._mix_program()
    before = program.stats.executions
    inside = jax.jit(lambda x: T.apply(x, sk.COLUMNWISE))(A)
    assert program.stats.executions == before
    assert _rel(inside, T.apply(A, sk.COLUMNWISE)) < 1e-6
    assert program.stats.executions == before + 1


def test_one_program_an_apply_and_no_recompile_on_the_second():
    from libskylark_tpu import engine

    n, s = 1 << 11, 256
    A, B = _operand(n, 40, 1), _operand(n, 40, 2)
    T = sk.FJLT(n, s, Context(8), fut="wht")
    T.apply(A, sk.COLUMNWISE).block_until_ready()
    program = fjlt._mix_program()
    compiles, ran = engine.stats().compiles, program.stats.executions
    # another transform of the shape, another operand: the key is an argument
    U = sk.FJLT(n, s, Context(9), fut="wht")
    U.apply(B, sk.COLUMNWISE).block_until_ready()
    T.apply(B, sk.COLUMNWISE).block_until_ready()
    assert engine.stats().compiles == compiles
    assert program.stats.executions == ran + 2


def test_span_attributes_and_the_counter():
    from libskylark_tpu import telemetry
    from libskylark_tpu.telemetry import metrics, trace
    from libskylark_tpu.telemetry.names import METRICS

    assert METRICS["sketch.mixed_elements"] == "counter"
    n, s, m = 1 << 15, 128, 24            # past one block: factors (2, 128, 128)
    A = _operand(n, m, 1)
    T = sk.FJLT(n, s, Context(3), fut="wht")
    before_enabled = metrics._ENABLED
    counted = fjlt._MIXED.value(family="FJLT", kernel="xla_f32")
    trace.clear_finished()
    telemetry.set_enabled(True)
    try:
        T.apply(A, sk.COLUMNWISE).block_until_ready()
        spans = {sp.name: sp for sp in trace.finished_spans()}
    finally:
        metrics._ENABLED = before_enabled
        trace.clear_finished()
    dispatch, apply = spans["sketch.dispatch"], spans["sketch.apply"]
    assert dispatch.parent_id == apply.span_id
    assert dispatch.attrs == {
        "path": "fut", "family": "FJLT", "fut": "wht", "kernel": "xla_f32",
        "factors": (2, 128, 128), "tile": fjlt.MIX_TILE, "elements": n * m,
        "sampled": s * m, "sample_chunk": s}        # one chunk holds them all
    assert spans["stream.key"].attrs["cached"] in (True, False)
    assert fjlt._MIXED.value(family="FJLT", kernel="xla_f32") == counted + n * m


@pytest.mark.parametrize("route,n,m,a,width", [
    ("xla", 1 << 15, 130, 2, fjlt.MIX_TILE),        # the walk's tile of columns
    ("pallas_blocks", 1 << 12, 256, 4, 256)])       # the kernel route: all of them
def test_the_span_says_the_samples_a_chunk(route, n, m, a, width, monkeypatch,
                                           request):
    """Every ``wht`` dispatch carries ``sample_chunk``: the samples whose rows
    the sampled factor gathers at a time."""
    if route == "pallas_blocks":
        request.getfixturevalue("interpreted")       # blocks of 1024 rows
    s = 512
    monkeypatch.setattr(fut, "_SAMPLE_CHUNK_BYTES", 40 * a * width * 4)
    chunk = fut.sample_outer_chunk(a, width, s)
    assert 40 <= chunk < s and fut._gathers_fast(chunk * a)
    T = sk.FJLT(n, s, Context(31), fut="wht")
    A = _operand(n, m, 5)
    out, spans = _spans_of(
        lambda: T.apply(A, sk.COLUMNWISE).block_until_ready())
    dispatch = [sp for sp in spans if sp.name == "sketch.dispatch"]
    assert [sp.attrs["sample_chunk"] for sp in dispatch] == [chunk]
    assert dispatch[0].attrs["factors"][0] == a
    D, idx = reference.streams(31, 0, n, s)
    assert _rel(out, reference.apply_cols(A, D, idx)) < 5e-6


# -- the sample indices' draw ----------------------------------------------


def test_the_high_draw_cancels_for_every_power_of_two_span():
    """2³² mod 2²⁰ = 0: the multiplier is zero past 2¹⁶ too, so
    ``stream_at`` skips the high draw's cipher and still equals the table."""
    for k in (1, 8, 16, 17, 20, 31):
        assert threefry.randint_multiplier(1 << k) == 0
    assert threefry.randint_multiplier(3 << 18) != 0
    dist = randgen.UniformInt(0, (1 << 20) - 1)
    assert dist.live_draws() == (1,)
    key = jax.random.fold_in(jax.random.key(5), 1)
    table = randgen.stream_slice(key, dist, 0, 3 * randgen.CHUNK, dtype=jnp.int32)
    at = jnp.asarray([0, 1, 2047, 2048, 4095, 4096, 9000, 3 * randgen.CHUNK - 1])
    assert np.array_equal(np.asarray(randgen.stream_at(key, dist, at, jnp.int32)),
                          np.asarray(table)[np.asarray(at)])
    assert int(table.min()) >= 0 and int(table.max()) < 1 << 20
