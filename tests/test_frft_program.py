"""The Fastfood apply as one compiled, block-walking program
(``sketch.fastfood_features``, sketch/frft.py, sketch/pallas_wht.py), on the
CPU:

- *plain reference*: ``cellbench/references/fastfood_features.py`` (imports
  nothing of the program; the streams from the published definition, H a
  dense ±1 matrix) — N = 48 → NB 64 with S = 160 (a truncated last block) and
  S = 256 (whole blocks), ragged row counts, both orientations;
- *oracle*: the eager chain ``_chain_rows``, kept for it, and the serve
  tier's lane ``fastfood_serve_apply``;
- the stream bits unchanged inside the program;
- the block kernels, interpreted, against their XLA twins, and the kernel
  route through ``T.apply``;
- the routes: what takes the program, what keeps the chain and says why;
- one program a shape, no recompile, the span's attributes and the counter.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench.references import fastfood_features as reference
from libskylark_tpu import sketch as sk
from libskylark_tpu.base.context import Context
from libskylark_tpu.ml import kernels
from libskylark_tpu.sketch import frft, fut, pallas_wht
from libskylark_tpu.sketch.cos_turns import cos_turns

N, SIGMA = 48, 9.8


def examples(rows, n=N, seed=3):
    return jnp.asarray(np.random.default_rng(seed).standard_normal((rows, n)),
                       jnp.float32)


def rel(got, ref, s):
    """The largest error in units of the features' scale √(2/s)."""
    return float(jnp.max(jnp.abs(got - ref))) / math.sqrt(2.0 / s)


def fast_map(s, seed=5, n=N, sigma=SIGMA):
    return kernels.Gaussian(n, sigma).create_rft(s, Context(seed), "fast")


# -- against the plain reference and the chain ------------------------------


@pytest.mark.parametrize("s", [160, 256])
@pytest.mark.parametrize("rows", [37, 200])
@pytest.mark.parametrize("dimension", [sk.ROWWISE, sk.COLUMNWISE])
def test_program_against_the_plain_reference(s, rows, dimension):
    X = examples(rows, seed=s + rows)
    T = fast_map(s, seed=11)
    assert isinstance(T, sk.FastGaussianRFT) and (T._NB, T._numblks) == (64, -(-s // 64))
    ref = reference.features(X, reference.streams(11, 0, N, s), SIGMA)
    if dimension == sk.ROWWISE:
        got = T.apply(X, dimension)
    else:
        got = T.apply(X.T, dimension).T
    assert got.shape == (rows, s) and got.dtype == jnp.float32
    assert rel(got, ref, s) < 2e-5


@pytest.mark.parametrize("family,kw", [
    ("FastGaussianRFT", {"sigma": 3.0}), ("FastMaternRFT", {"nu": 1.5, "l": 2.0})])
@pytest.mark.parametrize("n,s", [(48, 160), (64, 64), (100, 300)])
def test_program_against_the_chain_kept_as_oracle(family, kw, n, s):
    X = examples(29, n, seed=n + s)
    T = getattr(sk, family)(n, s, Context(4), **kw)
    program = frft._features_program()
    ran = program.stats.executions
    got = T.apply(X, sk.ROWWISE)
    assert program.stats.executions == ran + 1
    assert rel(got, T._features_rows(X), s) < 2e-5
    assert rel(T.apply(X.T, sk.COLUMNWISE).T, T._features_rows(X), s) < 2e-5


def test_the_serve_lane_still_serves_the_transforms_features():
    from libskylark_tpu.sketch.frft import fastfood_serve_apply

    s, X = 160, examples(33)
    T = fast_map(s, seed=8)
    lane = fastfood_serve_apply(T._alloc.key_data, X, n_dim=N, s_dim=s,
                                sm_kind="gauss", sm_param=SIGMA)
    assert rel(lane, T.apply(X, sk.ROWWISE), s) < 2e-5
    # and bit-equal to the chain it shares with the transform's oracle
    assert np.array_equal(np.asarray(lane), np.asarray(T._features_rows(X)))


def test_the_streams_inside_the_program_are_the_transforms_bits():
    """The program rebuilds the transform around its key words: the same
    methods on the same sub-streams, so B, G, Π, Sm and the shifts are the
    eager ones to the bit."""
    from libskylark_tpu.sketch.rft import _ProgramAllocation
    from libskylark_tpu.sketch.transform import _REGISTRY

    T = sk.FastMaternRFT(N, 160, Context(6), nu=1.5, l=2.0)

    @jax.jit
    def inside(key_data):
        P = _REGISTRY["FastMaternRFT"]._from_parts(
            N, 160, _ProgramAllocation(key_data), {"nu": 1.5, "l": 2.0})
        return (P._B(jnp.float32), P._G(jnp.float32), P._perms(),
                P._Sm(jnp.float32), P.shifts(jnp.float32))

    eager = (T._B(jnp.float32), T._G(jnp.float32), T._perms(),
             T._Sm(jnp.float32), T.shifts(jnp.float32))
    for a, b in zip(inside(T._alloc.key_data), eager):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    parts = reference.streams(6, 0, N, 160)
    assert np.array_equal(np.asarray(eager[0]), np.asarray(parts["B"]))
    assert np.array_equal(np.asarray(eager[2]), np.asarray(parts["perms"]))


def test_inner_products_estimate_the_gaussian_kernel():
    n, s, sigma = 256, 4096, math.sqrt(2.0 * 256)
    X = examples(48, n, seed=2)
    Z = np.asarray(fast_map(s, 3, n, sigma).apply(X, sk.ROWWISE), np.float64)
    K = np.asarray(reference.gaussian_kernel(X, sigma), np.float64)
    z = np.abs(Z @ Z.T - K) / np.sqrt(2.0 * (1 + 0.5 * K ** 4 - K * K) / s)
    assert z.max() < 6.0 and abs((Z * Z).sum() / 48 - 1.0) < 0.05


# -- the block kernels, interpreted -----------------------------------------


@pytest.mark.parametrize("passes,tol", [(3, 2e-6), (1, 2e-2)])
def test_mix_chunk_against_its_xla_twin(passes, tol):
    NB, tile, steps, chunks = 1024, 128, 2, 3
    cols = tile * steps
    X = examples(NB, cols * chunks, seed=1)
    D = jnp.asarray(np.random.default_rng(0).choice([-1.0, 1.0], NB), jnp.float32)
    for c in range(chunks):
        got = pallas_wht.mix_chunk(X, D, jnp.asarray([7, c], jnp.int32), tile=tile,
                                   cols=cols, passes=passes, interpret=True)
        ref = fut.wht_blocks(D[:, None] * X[:, c * cols:(c + 1) * cols], NB)
        err = float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
        assert err < tol and (passes == 3 or err > 1e-4)


def test_mix_cos_rows_writes_its_slab_in_place_and_cuts_the_overhang():
    NB, tile, steps, chunks, nb = 1024, 128, 2, 2, 3
    cols = tile * steps
    m = cols * chunks - 37                       # the last tile overhangs Z
    rng = np.random.default_rng(0)
    X = examples(NB, cols * chunks, seed=1)
    g = jnp.asarray(rng.standard_normal(NB), jnp.float32)
    sm = jnp.full((NB,), 0.01 / (2 * math.pi), jnp.float32)
    sh = jnp.asarray(rng.uniform(0, 1, NB), jnp.float32)
    Z = jnp.full((m, nb * NB), 7.0, jnp.float32)
    want = np.full((m, nb * NB), 7.0, np.float32)
    for k, c in [(1, 0), (2, 1), (0, 1)]:
        Y = X[:, c * cols:(c + 1) * cols]
        Z = pallas_wht.mix_cos_rows(Y, g, sm, sh, Z, jnp.asarray([k, c], jnp.int32),
                                    tile=tile, outscale=0.5, interpret=True)
        ref = cos_turns(sm[:, None] * fut.wht_blocks(g[:, None] * Y, NB)
                        + sh[:, None], 0.5).T
        lo, hi = c * cols, min(m, (c + 1) * cols)
        want[lo:hi, k * NB:(k + 1) * NB] = np.asarray(ref)[:hi - lo]
        # the slab is written, every other entry kept
        assert np.abs(np.asarray(Z) - want).max() < 1e-6


@pytest.fixture
def interpreted(monkeypatch):
    """Drive the kernel route through ``T.apply`` off the TPU."""
    for name in ("mix_chunk", "mix_cos_rows"):
        monkeypatch.setattr(pallas_wht, name, functools.partial(
            getattr(pallas_wht, name).__wrapped__, interpret=True))
    monkeypatch.setattr(
        sk.FastRFT, "features_plan",
        lambda self, A, rowwise: (("pallas_wht", 128) if rowwise
                                  else ("xla_f32", 0)))


@pytest.mark.parametrize("n,s,rows", [
    (1000, 2048, 300),      # two whole blocks, 3 tiles: one chunk, cut at 300
    (1024, 1500, 128)])     # a truncated last block, one whole tile
def test_kernel_route_through_apply(interpreted, monkeypatch, n, s, rows):
    sigma = math.sqrt(2.0 * n)
    X = examples(rows, n, seed=rows)
    T = fast_map(s, 21, n, sigma)
    ref = reference.features(X, reference.streams(21, 0, n, s), sigma)
    assert rel(T.apply(X, sk.ROWWISE), ref, s) < 2e-5
    # more than one chunk of the free axis, and a walk whose last tiles lie
    # past the examples (the result is then the padded walk's, cut after)
    monkeypatch.setattr(frft, "_GATHER_COLS_MAX", 128)
    assert frft.walk_geometry(rows, 128) == (-(-rows // 128), 1)
    assert rel(T.apply(X, sk.ROWWISE), ref, s) < 2e-5


def test_the_bf16_regime_is_one_pass_and_fails_the_cells_limit(interpreted):
    """``set_pallas_precision("bf16")`` is the kernel route's lower regime:
    the benchmark's control ``program_bf16`` runs it and must be refused."""
    import json
    import pathlib

    from libskylark_tpu.sketch import params

    limit = json.loads((pathlib.Path(__file__).parent.parent / "cellbench/configs"
                        / "ffgrft_cifar10_d3072_s16384.json").read_text()
                       )["limits"]["rel_max"]
    n, s = 1024, 2048
    sigma = math.sqrt(2.0 * n)
    X = examples(128, n, seed=4)
    T = fast_map(s, 9, n, sigma)
    ref = reference.features(X, reference.streams(9, 0, n, s), sigma)
    assert rel(T.apply(X, sk.ROWWISE), ref, s) < limit
    params.set_pallas_precision("bf16")
    try:
        assert rel(T.apply(X, sk.ROWWISE), ref, s) > 10 * limit
    finally:
        params.set_pallas_precision("bf16x3")


def test_walk_geometry_and_the_tile():
    # the cell: 98 tiles of 512 in two chunks of 49, no padded row
    assert frft.mix_tile(4096, 50000) == 512
    assert frft.walk_geometry(50000, 512) == (2, 49)
    assert frft.mix_tile(4096, 200) == 128 and frft.mix_tile(4096, 20000) == 256
    assert frft.mix_tile(16384, 50000) == 256          # the VMEM plan's cap
    assert frft.walk_geometry(32768, 512) == (1, 64)
    # a prime count of tiles: chunks that do not divide it, a padded walk
    chunks, steps = frft.walk_geometry(97 * 512, 512)
    assert chunks * steps >= 97 and steps * 512 <= frft._GATHER_COLS_MAX
    T = fast_map(16384, 1, 3072, 78.0)
    assert T.kernel_tile(50000, interpret=True) == 512
    assert fast_map(64, 1).kernel_tile(50000, interpret=True) == 0    # NB 64
    assert sk.FastGaussianRFT(40000, 70000, Context(1)).kernel_tile(
        512, interpret=True) == 0                                     # NB 65536


# -- the routes ---------------------------------------------------------------


def _dispatch_of(call):
    from libskylark_tpu import telemetry
    from libskylark_tpu.telemetry import metrics, trace

    before = metrics._ENABLED
    trace.clear_finished()
    telemetry.set_enabled(True)
    try:
        out = call()
        jax.block_until_ready(out)
        spans = list(trace.finished_spans())
    finally:
        metrics._ENABLED = before
        trace.clear_finished()
    return out, spans


def test_span_attributes_and_the_counter():
    from libskylark_tpu.telemetry.names import METRICS

    assert METRICS["sketch.fastfood_features"] == "counter"
    s, rows = 160, 24
    X, T = examples(rows), fast_map(160, seed=3)
    counted = frft._FEATURES.value(family="FastGaussianRFT", route="fastfood_blocks")
    _, spans = _dispatch_of(lambda: T.apply(X, sk.ROWWISE))
    by_name = {sp.name: sp for sp in spans}
    dispatch, apply = by_name["sketch.dispatch"], by_name["sketch.apply"]
    assert dispatch.parent_id == apply.span_id
    assert by_name["sketch.plan"].parent_id == apply.span_id
    assert by_name["stream.key"].attrs["cached"] in (True, False)
    assert dispatch.attrs == {
        "path": "features", "family": "FastGaussianRFT",
        "route": "fastfood_blocks", "kernel": "xla_f32", "tile": 0, "blocks": 3,
        "block_len": 64, "elements": 2 * 3 * 64 * rows, "finisher": "cos_turns",
        "features": rows * s}
    assert frft._FEATURES.value(family="FastGaussianRFT",
                                route="fastfood_blocks") == counted + rows * s


@pytest.mark.parametrize("build,operand,reason", [
    (lambda: sk.FastGaussianRFT(N, 160, Context(2), sigma=SIGMA, fut="dct"),
     lambda: examples(16), "fut=dct"),
    (lambda: fast_map(160), lambda: examples(16).astype(jnp.bfloat16),
     "dtype=bfloat16"),
    (lambda: sk.FastRFT(N, 160, Context(2)), lambda: examples(16),
     "family=FastRFT"),
])
def test_declined_routes_keep_the_chain_and_say_why(build, operand, reason):
    T, X = build(), operand()
    program = frft._features_program()
    ran = program.stats.executions
    out, spans = _dispatch_of(lambda: T.apply(X, sk.ROWWISE))
    assert program.stats.executions == ran
    dispatch = next(sp for sp in spans if sp.name == "sketch.dispatch")
    assert dispatch.attrs["route"] == "chain" and dispatch.attrs["reason"] == reason
    assert dispatch.attrs["finisher"] == "cos" and dispatch.attrs["kernel"] == "xla"
    assert dispatch.attrs["features"] == 16 * 160
    assert np.array_equal(np.asarray(out), np.asarray(T._features_rows(X)))


def test_a_pinned_matmul_precision_keeps_the_chain_it_governs():
    T, X = fast_map(160), examples(16)
    with jax.default_matmul_precision("float32"):
        out, spans = _dispatch_of(lambda: T.apply(X, sk.ROWWISE))
    dispatch = next(sp for sp in spans if sp.name == "sketch.dispatch")
    assert dispatch.attrs["route"] == "chain"
    assert dispatch.attrs["reason"] == "precision=pinned"
    assert rel(out, T.apply(X, sk.ROWWISE), 160) < 2e-5


def test_an_operand_on_several_devices_keeps_the_chain():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("one device")
    X = jax.device_put(examples(16), NamedSharding(
        Mesh(np.array(devices[:2]), ("r",)), PartitionSpec("r", None)))
    T = fast_map(160)
    assert T.features_plan(X, True) == "devices=2"
    out, spans = _dispatch_of(lambda: T.apply(X, sk.ROWWISE))
    dispatch = next(sp for sp in spans if sp.name == "sketch.dispatch")
    assert dispatch.attrs["reason"] == "devices=2"
    assert rel(out, T._features_rows(examples(16)), 160) < 2e-5


def test_the_plan_off_the_tpu_is_the_xla_route_both_ways():
    T, X = fast_map(160), examples(16)
    assert T.features_plan(X, True) == ("xla_f32", 0)
    assert T.features_plan(X.T, False) == ("xla_f32", 0)


def test_under_a_callers_jit_it_is_part_of_the_callers_program():
    X, T = examples(20), fast_map(160, seed=6)
    program = frft._features_program()
    before = program.stats.executions
    inside = jax.jit(lambda x: T.apply(x, sk.ROWWISE))(X)
    assert program.stats.executions == before
    assert rel(inside, T.apply(X, sk.ROWWISE), 160) < 1e-6
    assert program.stats.executions == before + 1


def test_one_program_a_shape_and_no_recompile_on_a_repeated_apply():
    from libskylark_tpu import engine

    A, B = examples(40, seed=1), examples(40, seed=2)
    T = fast_map(224, seed=8)
    T.apply(A, sk.ROWWISE).block_until_ready()
    program = frft._features_program()
    compiles, ran = engine.stats().compiles, program.stats.executions
    # another map of the shape, another operand: the key is an argument
    U = fast_map(224, seed=9)
    U.apply(B, sk.ROWWISE).block_until_ready()
    T.apply(B, sk.ROWWISE).block_until_ready()
    assert engine.stats().compiles == compiles
    assert program.stats.executions == ran + 2
    # another shape is one more compile, once
    T.apply(examples(41), sk.ROWWISE).block_until_ready()
    T.apply(examples(41, seed=5), sk.ROWWISE).block_until_ready()
    assert engine.stats().compiles == compiles + 1


def test_krr_with_use_fast_runs_the_program():
    """The call ``ml/krr.py`` makes: ``create_rft(..., "fast")`` applied
    rowwise to the training examples."""
    k = kernels.Gaussian(N, SIGMA)
    T = k.create_rft(160, Context(12), "fast")
    program = frft._features_program()
    ran = program.stats.executions
    T.apply(examples(32), sk.ROWWISE).block_until_ready()
    assert program.stats.executions == ran + 1
