"""The FJLT apply with the default mixer (``fut="dct"``; ``"dht"`` beside it)
as the one compiled mix-and-sample program (``sketch.fjlt_mix_sample`` on the
``"xla_dft"`` route: sketch/fjlt.py, sketch/fut.py ``dft_factors`` /
``dft_tables`` / ``dft_source_rows`` / ``dft_blocks`` / ``sample_outer_dft``),
on the CPU:

- the blocked transform against the cosine sum of the definition and against
  ``fut.dct`` / ``fut.dht`` — heights of one, two and three factors, factors
  that are no multiple of 8 (the stages' arrays are padded to whole (8, 128)
  tiles: ``fut.dft_pads``) and factors that are, a height the rule declines;
- the sampled outer factor against the full transform then a gather;
- *plain reference*: ``cellbench/references/dct_fjlt.py`` (imports nothing of
  the program; a float64 DCT on the host) — both orientations, ragged free
  extents, under a caller's ``jit``, and the eager composition
  ``fut.sign_mix_sample`` beside it;
- one program, no recompile, the span's attributes and the counter, the
  solvers' programs, the sampled coordinates on a span that is no power of two.
"""

import json
import math
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench.references import dct_fjlt as reference
from libskylark_tpu import sketch as sk
from libskylark_tpu.base import randgen, threefry
from libskylark_tpu.base.context import Context
from libskylark_tpu.sketch import fjlt, fut

# the configuration's limit
REL_MAX = json.loads((pathlib.Path(__file__).parent.parent / "cellbench/configs"
                      / "fjlt_blendenpik_dct_m1000000_n1024.json").read_text()
                     )["limits"]["rel_max"]


def _operand(n, m, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal((n, m)),
                       jnp.float32)


def _rel(got, ref):
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def _definition(x, mixer):
    """The transform of the columns of x (n, w) by its sum, float64, the
    phase reduced in int64 before the cosine."""
    n = x.shape[0]
    k = np.arange(n, dtype=np.int64)[:, None]
    j = np.arange(n, dtype=np.int64)[None, :]
    if mixer == "dct":                   # FFTW REDFT10
        C = 2.0 * np.cos(np.pi * ((k * (2 * j + 1)) % (4 * n)) / (2.0 * n))
    else:                                # cas(2πjk/n)
        t = 2.0 * np.pi * ((k * j) % n) / n
        C = np.cos(t) + np.sin(t)
    return C @ np.asarray(x, np.float64)


def _blocked(X, factors, mixer, idx=None):
    n = X.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32) if idx is None else idx
    source = fut.dft_source_rows(n, factors, mixer)
    Z = fut.dft_blocks(X[source], factors, fut.dft_tables(factors))
    return fut.sample_outer_dft(Z, idx, n, factors, mixer, 1.0)


# -- the rule ---------------------------------------------------------------


@pytest.mark.parametrize("n,factors", [
    (1_000_000, (100, 125, 80)), (1000, (10, 20, 5)), (999, (9, 37, 3)),
    (1 << 20, (128, 128, 64)), (1 << 22, (256, 128, 128)), (2, (1, 2, 1)),
    (1009, None), (2 * 521, None), (3, (1, 3, 1)), ((1 << 22) + 2, None)])
def test_dft_factors(n, factors):
    assert fut.dft_factors(n) == factors
    if factors:
        r, f1, f2 = factors
        assert r * f1 * f2 == n and 2 <= f1 <= 128 and f2 <= 128 and r <= 256
        assert r * n < 1 << 31 and 32 * n < 1 << 31     # the int32 phases


def test_dft_tables_are_the_dft_factors_and_cached():
    F1, T2 = fut.dft_tables((3, 6, 5))
    # f1 = 6 → 8 columns, h = 4 → 8 rows a part, f2 = 5 → 8 rows a part
    assert fut.dft_pads((3, 6, 5)) == (8, 8, 8, 5)
    assert F1.shape == (16, 8) and T2.shape == (8, 16, 10)
    assert F1.dtype == T2.dtype == np.float32
    k, a = np.arange(4)[:, None], np.arange(6)[None, :]
    np.testing.assert_allclose(F1[:4, :6], np.cos(2 * np.pi * k * a / 6), atol=1e-7)
    np.testing.assert_allclose(F1[8:12, :6], -np.sin(2 * np.pi * k * a / 6), atol=1e-7)
    # κ1 = 0: no twiddle, the plain DFT_5 as [[re, −im], [im, re]]
    q = np.arange(5)
    W = np.exp(-2j * np.pi * np.outer(q, q) / 5)
    np.testing.assert_allclose(T2[0, :5, :5], W.real, atol=1e-7)
    np.testing.assert_allclose(T2[0, :5, 5:], -W.imag, atol=1e-7)
    np.testing.assert_allclose(T2[0, 8:13, :5], W.imag, atol=1e-7)
    # κ1 = 2: the twiddle ω_30^{2b} folded in
    tw = W * np.exp(-2j * np.pi * 2 * q / 30)[None, :]
    np.testing.assert_allclose(T2[2, 8:13, 5:], tw.real, atol=1e-7)
    assert fut.dft_tables((3, 6, 5))[0] is F1
    assert len(fut.dft_tables((4, 6, 1))) == 1


@pytest.mark.parametrize("factors,pads", [
    # the cell: 125 → 128, h = 63 → 64, and 80·100·128 rows would fill whole
    # index tiles of 1024 where 81·100·128 do not
    ((100, 125, 80), (128, 64, 80, 81)),
    ((40, 75, 32), (80, 40, 32, 33)),        # chip_smoke's DCT leg
    ((128, 128, 64), (128, 72, 64, 64)),     # a block more would not help
    ((7, 14, 11), (16, 8, 12, 11)),          # h = 8 whole, f1 and 2·f2 = 22 not
    ((64, 125, 125), (128, 64, 128, 125)),   # 2·f2 = 250 → 256
    ((20, 50, 1), (56, 32, 1, 1)),           # one inner stage
    ((4, 64, 4), (64, 40, 4, 5)),            # 4·4·64 rows are an index tile
    ((16, 64, 1), (64, 40, 1, 1)),           # no block to add to one stage
    ((1, 2, 1), (8, 8, 1, 1))])
def test_the_pads_of_the_tables_are_exactly_zero(factors, pads):
    """Every digit that stands next to the free axis fills whole 8-row tiles
    (2·f2p rows for (re | im, κ2)); what the tables hold there is 0.0, so the
    padded layout adds zeros up and drops nothing. The gathered rows fill no
    whole number of 1024-index tiles wherever a block more can see to it."""
    r, f1, f2 = factors
    f1p, hp, f2p, blocks = fut.dft_pads(factors)
    assert (f1p, hp, f2p, blocks) == pads
    h = f1 // 2 + 1
    assert f1p % 8 == 0 and hp % 8 == 0 and f1 <= f1p < f1 + 8 and h <= hp < h + 8
    assert (2 * f2p) % 8 == 0 and f2 <= f2p < f2 + 4 if f2 > 1 else f2p == 1
    assert blocks in (f2, f2 + 1)
    assert fut._gathers_fast(blocks * r * f1p) or f2 == 1 or not (
        fut._gathers_fast(f2 * r * f1p) or fut._gathers_fast((f2 + 1) * r * f1p))
    tables = fut.dft_tables(factors)
    F1 = tables[0].reshape(2, hp, f1p)
    assert not F1[:, h:].any() and not F1[:, :, f1:].any()
    assert np.abs(F1[:, :h, :f1]).sum(axis=(0, 2)).min() >= 1.0   # no live κ1 is
    if f2 > 1:
        T2 = tables[1].reshape(hp, 2, f2p, 2, blocks)
        assert not T2[h:].any() and not T2[:, :, f2:].any()
        assert not T2[..., f2:].any()
        assert np.abs(T2[:h, :, :f2]).sum(axis=(1, 3, 4)).min() >= 1.0


@pytest.mark.parametrize("n,factors,slabs", [
    (12, (1, 4, 3), 1), (15, (5, 3, 1), 5), (1000, (4, 50, 5), 4),
    (96, (3, 8, 4), 3), (1000, (8, 125, 1), 8),
    (1024, (4, 64, 4), 4),          # a block more: 5·4·64 rows
    (1024, (16, 64, 1), 16),
    # the walk over the sampled digit: ρ of the R slabs a pass
    (15, (5, 3, 1), 1), (1000, (4, 50, 5), 2), (1000, (4, 50, 5), 1),
    (96, (3, 8, 4), 1), (1000, (8, 125, 1), 4),
    (1024, (4, 64, 4), 2),          # 4·2·64 rows are no index tile: 4 blocks
    (1024, (16, 64, 1), 8), (6000, (16, 25, 15), 4)])
def test_source_rows_are_makhouls_order_with_the_stage_digit_last(
        n, factors, slabs):
    """A pass over ρ = ``slabs`` slabs of the sampled digit from ``first``
    names, slab by slab, the rows the walk over all R names for those slabs:
    the passes together are Makhoul's order with the digit a last."""
    r, f1, f2 = factors
    f1p, _, _, blocks = fut.dft_pads((slabs, f1, f2))
    x = np.arange(n)
    v = np.concatenate([x[::2], x[1::2][::-1]])          # Makhoul's order
    D = np.random.default_rng(n).choice([-1.0, 1.0], n).astype(np.float32)
    for mixer, order in (("dct", v), ("dht", x)):
        whole = order.reshape(f1, f2, r)                 # [a, b, r]
        for first in range(0, r, slabs):
            got = np.asarray(fut.dft_source_rows(
                n, factors, mixer, slabs, jnp.int32(first)))
            assert got.shape == (blocks * slabs * f1p,)
            assert got.dtype == np.int32
            rows = got.reshape(blocks, slabs, f1p)       # [b, r − first, a]
            live = rows[:f2]
            assert np.array_equal(
                live[:, :, :f1],
                whole[:, :, first:first + slabs].transpose(1, 2, 0))
            # a pad names a row of the operand (its column of the factor is
            # zero): the slab's last again, the first block's slabs again
            assert rows.min() >= 0 and rows.max() < n
            assert np.array_equal(
                live[:, :, f1:],
                np.repeat(live[:, :, f1 - 1:f1], f1p - f1, axis=2))
            assert np.array_equal(rows[f2:], live[:blocks - f2])
            # the signs of those rows by a fold of the sign vector itself,
            # no gather; zero at the pads
            signs = np.asarray(fut.dft_source_signs(
                jnp.asarray(D), factors, mixer, slabs))[:, first:first + slabs]
            assert signs.shape == (blocks, slabs, f1p)
            assert np.array_equal(signs[:f2, :, :f1], D[live[:, :, :f1]])
            assert not signs[f2:].any() and not signs[:, :, f1:].any()
    if slabs == r:                  # the default: every slab, from the first
        assert np.array_equal(fut.dft_source_rows(n, factors, "dht"), got)


def test_cis_turns_to_an_ulp_whatever_the_period():
    for period in (7, 4_000_000, 4 * (1 << 22)):
        p = np.unique(np.concatenate([
            np.arange(0, min(period, 64)), np.arange(max(0, period - 64), period),
            np.random.default_rng(period).integers(0, period, 4096),
            (np.arange(9) * period) // 8 % period]))
        cos, sin = fut._cis_turns(jnp.asarray(p, jnp.int32), period)
        t = 2.0 * np.pi * p.astype(np.float64) / period
        assert np.max(np.abs(np.asarray(cos, np.float64) - np.cos(t))) < 1.5e-7
        assert np.max(np.abs(np.asarray(sin, np.float64) - np.sin(t))) < 1.5e-7


# -- the blocked transform ----------------------------------------------------


@pytest.mark.parametrize("n,factors", [
    (1000, None),                # the rule's own split
    (1000, (20, 50, 1)),         # one inner stage
    (1000, (10, 10, 10)),        # three factors
    (999, (3, 9, 37)),           # odd, no factor a multiple of 8
    (1155, (7, 15, 11)),
    (2058, (6, 7, 49)),
    (1024, (4, 16, 16)),
    (250, (1, 125, 2)),          # no outer factor: every output's own row
    (12, None),
    (1024, (2, 16, 32)),         # f1 a multiple of 8, h = 9 is not
    (1344, (4, 14, 24)),         # h = 8 is, f1 is not
    (2000, (10, 8, 25)),         # 2·f2 = 50 is no multiple of 8
    (1250, (10, 125, 1)),        # one inner stage whose rows are padded
    (1920, (8, 16, 15)),         # f1 a multiple of 8 under an odd f2
    (9600, (4, 75, 32)),         # chip_smoke's inner factors: 75 → 80, 38 → 40
    (1024, (4, 64, 4)),          # 4·4·64 rows are an index tile: a block more
    (1024, (16, 64, 1))])        # one stage of whole tiles: nothing padded but h
@pytest.mark.parametrize("mixer", ["dct", "dht"])
def test_blocked_transform_against_the_definition_and_the_eager_one(
        n, factors, mixer):
    factors = factors or fut.dft_factors(n)
    X = _operand(n, 5, n)
    got = _blocked(X, factors, mixer)
    assert got.shape == (n, 5)
    assert _rel(got, _definition(X, mixer)) < 6e-7
    eager = fut.dct(X, 0) if mixer == "dct" else fut.dht(X, 0)
    assert _rel(got, eager) < 2e-6


@pytest.mark.parametrize("n,factors,mixer", [
    (1000, (8, 5, 25), "dct"), (1536, (24, 8, 8), "dct"),
    (2000, (250, 8, 1), "dct"),
    (2000, (16, 125, 1), "dct"),      # f2 = 1 under padded rows: (r, re|im, κ1)
    (2000, (16, 125, 1), "dht"), (1155, (7, 15, 11), "dht")])
def test_sampled_outer_factor_against_full_transform_then_gather(
        n, factors, mixer):
    X = _operand(n, 9, 3)
    idx = jnp.asarray(np.random.default_rng(n).integers(0, n, 300), jnp.int32)
    idx = idx.at[:4].set(jnp.asarray([0, n - 1, n // 2, 1]))
    full = np.asarray(_blocked(X, factors, mixer))
    sampled = np.asarray(_blocked(X, factors, mixer, idx))
    np.testing.assert_allclose(sampled, full[np.asarray(idx)], rtol=0, atol=1e-5)
    # the mirrored half (κ1 > f1/2) is read through the conjugate
    r, f1, f2 = factors
    mirrored = np.asarray(idx) % (f1 * f2) % f1 > f1 // 2
    assert mirrored.any() and not mirrored.all()


def test_the_gathered_rows_are_held_a_chunk_at_a_time(monkeypatch):
    n, factors = 1000, (8, 5, 25)
    X = _operand(n, 40, 4)
    idx = jnp.asarray(np.random.default_rng(1).integers(0, n, 77), jnp.int32)
    whole = _blocked(X, factors, "dct", idx)
    monkeypatch.setattr(fut, "_SAMPLE_CHUNK_BYTES", 8 * 2 * 8 * 40 * 4)
    # the same rows against the same weights; the sum's order is the chunk's
    assert _rel(_blocked(X, factors, "dct", idx), whole) < 1e-6


# -- the program --------------------------------------------------------------


SHAPES = [(1000, 64), (1000, 256), (3000, 512), (999, 64), (6000, 6000)]


@pytest.mark.parametrize("n,s", SHAPES)
@pytest.mark.parametrize("m", [37, 130])
def test_program_against_the_plain_reference_both_orientations(n, s, m):
    seed = n + s + m
    A = _operand(n, m, seed)
    T = sk.FJLT(n, s, Context(seed))                    # the default mixer
    assert T.mix_plan(A, False)[0] == "xla_dft"
    D, idx = reference.streams(seed, 0, n, s)
    ref = reference.apply_cols(A, D, idx)
    assert ref.shape == (s, m)
    assert _rel(T.apply(A, sk.COLUMNWISE), ref) < REL_MAX
    assert _rel(T.apply(A.T, sk.ROWWISE).T, ref) < REL_MAX
    if s == n:
        assert len(np.unique(np.asarray(idx))) < s      # repeats among them


@pytest.mark.parametrize("mixer", ["dct", "dht"])
@pytest.mark.parametrize("n", [1000, 1155, 1 << 10])
def test_program_agrees_with_the_eager_composition(mixer, n):
    s, m = 128, 2 * fjlt.dft_tile(n) + 20         # two tiles and a ragged rest
    A = _operand(n, m, n)
    T = sk.FJLT(n, s, Context(n), fut=mixer)
    eager = fut.sign_mix_sample(
        T._fut.apply, A, T.diagonal(), T.sample_indices(), T._fut.scale(),
        math.sqrt(n / s), 0)
    assert _rel(T.apply(A, sk.COLUMNWISE), eager) < 2e-6
    assert _rel(T.apply(A.T, sk.ROWWISE).T, eager) < 2e-6


@pytest.mark.parametrize("factors", [
    (10, 10, 10), (8, 5, 25), (1, 20, 50),
    (5, 8, 25),                  # f1 a multiple of 8, 2·f2 = 50 is not
    (8, 125, 1),                 # one stage, its rows padded 63 → 64
    (20, 2, 25),                 # the shortest stage one
    (25, 40, 1)])                # one stage, f1 a multiple of 8, h = 21 not
def test_forced_splits_through_the_program(factors):
    """A split the rule would not choose, through the program in tiles of 64
    with a ragged last one (140 = 2·64 + 12), both orientations."""
    n, s, m = 1000, 96, 140
    A = _operand(n, m, 2)
    T = sk.FJLT(n, s, Context(4))
    D, idx = reference.streams(4, 0, n, s)
    ref = reference.apply_cols(A, D, idx)
    statics = dict(s_dim=s, kernel="xla_dft", tile=64, fut="dct",
                   factors=factors)
    got = fjlt.fjlt_mix_sample(T.allocation.key_data, A, rowwise=False,
                               **statics)
    assert _rel(got, ref) < REL_MAX
    got = fjlt.fjlt_mix_sample(T.allocation.key_data, A.T, rowwise=True,
                               **statics)
    assert _rel(got.T, ref) < REL_MAX


@pytest.mark.parametrize("m", [40, 140])       # under the tile; 2·64 + 12
@pytest.mark.parametrize("rowwise", [False, True])
@pytest.mark.parametrize("mixer", ["dct", "dht"])
def test_program_against_the_dense_operator(mixer, rowwise, m):
    """Both mixers, both orientations, a width under the tile and a ragged
    last tile, on a split none of whose digits fills whole tiles (1155 =
    7·15·11), against the float64 cosine / Hartley sum of the definition."""
    n, s = 1155, 96
    A = _operand(n, m, 5)
    T = sk.FJLT(n, s, Context(12), fut=mixer)
    kernel, factors, _ = T.mix_plan(A, rowwise)
    assert kernel == "xla_dft" and all(d % 4 for d in factors[1:])
    D = np.asarray(T.diagonal(), np.float64)[:, None]
    scale = math.sqrt(n / s) * T._fut.scale()
    ref = scale * _definition(D * np.asarray(A, np.float64), mixer)[
        np.asarray(T.sample_indices())]
    got = fjlt.fjlt_mix_sample(
        T.allocation.key_data, A.T if rowwise else A, s_dim=s, rowwise=rowwise,
        kernel=kernel, tile=64, fut=mixer, factors=factors)
    assert _rel(got.T if rowwise else got, ref) < REL_MAX


@pytest.mark.parametrize("mixer", ["dct", "dht"])
@pytest.mark.parametrize("n,factors,m,rowwise,held,tile,slabs", [
    # whole rows of a columnwise operand (a ragged 37 of them), 2 passes
    (1000, (10, 20, 5), 37, False, (5, 37, False), 37, 5),
    (1000, (10, 20, 5), 37, False, (1, 37, False), 37, 1),      # and 10
    # a rowwise operand: its tile is a transposed copy, 128 + a ragged 12
    (1000, (10, 20, 5), 140, True, (2, 128, True), 128, 2),
    # a free axis too wide for whole rows of even one slab: cut as well
    (1000, (10, 20, 5), 800, False, (2, 128, True), 128, 2),
    # 5 blocks at ρ = 4 (an index tile), 4 at ρ = 2; one inner stage
    (1024, (4, 64, 4), 24, False, (2, 24, False), 24, 2),
    (1024, (16, 64, 1), 24, True, (8, 24, True), 128, 8),
    (6000, (16, 25, 15), 50, False, (4, 50, False), 50, 4)])
def test_the_walk_over_the_sampled_digit_in_several_passes(
        monkeypatch, mixer, n, factors, m, rowwise, held, tile, slabs):
    """The budget shrunk to what ``held`` = (ρ, w, copied) takes: the plan's
    two tile extents follow, and the outer sum added up ρ slabs at a time is
    the float64 sum of the definition within the configuration's limit."""
    monkeypatch.setattr(fjlt, "_DFT_TEMP_BYTES",
                        fjlt._dft_pass_bytes(n, factors, *held))
    s = 96
    A = _operand(n, m, n + m)
    T = sk.FJLT(n, s, Context(n + slabs), fut=mixer)
    if factors == fut.dft_factors(n):
        assert T.mix_plan(A.T if rowwise else A, rowwise) == (
            "xla_dft", factors, tile)
    assert fjlt.dft_slabs(n, factors, tile, m, rowwise) == slabs < factors[0]
    D = np.asarray(T.diagonal(), np.float64)[:, None]
    scale = math.sqrt(n / s) * T._fut.scale()
    ref = scale * _definition(D * np.asarray(A, np.float64), mixer)[
        np.asarray(T.sample_indices())]
    got = fjlt.fjlt_mix_sample(
        T.allocation.key_data, A.T if rowwise else A, s_dim=s, rowwise=rowwise,
        kernel="xla_dft", tile=tile, fut=mixer, factors=factors)
    assert _rel(got.T if rowwise else got, ref) < REL_MAX
    if mixer == "dct":                       # and the plain reference's DCT
        plain = reference.apply_cols(A, *reference.streams(n + slabs, 0, n, s))
        assert _rel(got.T if rowwise else got, plain) < REL_MAX


@pytest.mark.parametrize("n,m,rowwise,tile,slabs", [
    (1_000_000, 1024, False, 1024, 50),      # the cell: whole rows, two passes
    (1_000_000, 602, False, 602, 100),       # the widest rows one pass takes
    (1_000_000, 603, False, 603, 50),
    (1_000_000, 128, False, 128, 100),
    (1_000_000, 4096, False, 4096, 10),
    (1_000_000, 60_000, False, 60_000, 1),   # one slab of whole rows fits
    (1_000_000, 61_000, False, 512, 100),    # none does: the free axis is cut
    (1_000_000, 1024, True, 512, 100),       # a rowwise tile is transposed
    (1 << 21, 512, False, 512, 64), (1 << 21, 512, True, 256, 128),
    (1 << 22, 256, False, 256, 128), (1 << 22, 256, True, 128, 256),
    (1_200_000, 1024, True, 384, 100),
    (500_000, 2048, False, 2048, 40), (500_000, 2048, True, 512, 80),
    (96_000, 384, False, 384, 40),           # chip_smoke's DCT leg: one pass
    (1000, 40, False, 40, 10), (1000, 700, True, 512, 10),
    (2, 3, False, 3, 1)])                    # a short axis
def test_the_tile_follows_the_axis(n, m, rowwise, tile, slabs):
    """Columnwise, whole rows of the operand wherever one slab of the
    sampled digit fits the budget, else — and rowwise, whose tile is
    transposed first — the cut tile: the widest multiple of 128 columns, to
    512, that fits as a copy, all R slabs in its one pass. Then the most
    slabs a pass, a divisor of R, whose temporaries — two of the stages'
    padded arrays at a time and, while another pass will read it, the copy
    — stay under the budget."""
    r, f1, f2 = factors = fut.dft_factors(n)
    # what the plan reads of an operand, without its gigabytes
    A = types.SimpleNamespace(shape=(m, n) if rowwise else (n, m),
                              dtype=jnp.dtype("float32"), devices=lambda: {0})
    assert sk.FJLT(n, 64, Context(0)).mix_plan(A, rowwise) == (
        "xla_dft", factors, tile)
    assert fjlt.dft_slabs(n, factors, tile, m, rowwise) == slabs
    assert r % slabs == 0
    w, copied = min(m, tile), rowwise or m > tile
    held = fjlt._dft_pass_bytes(n, factors, slabs, w, copied)
    f1p, _, _, blocks = fut.dft_pads((slabs, f1, f2))
    assert 2 * 4 * w * blocks * slabs * f1p <= held <= fjlt._DFT_TEMP_BYTES
    more = [d for d in range(slabs + 1, r + 1) if r % d == 0]
    assert not more or fjlt._dft_pass_bytes(
        n, factors, more[0], w, copied) > fjlt._DFT_TEMP_BYTES
    if tile < m:
        assert tile == fjlt.dft_tile(n) and tile % 128 == 0 and tile <= 512


def test_bf16_table_control_fails_the_configurations_rel_max():
    """A cosine table rounded to bfloat16 — what a DFT factor contracted in
    one bfloat16 pass would serve — and an operand cut to two of its three
    bfloat16 parts are refused by the limit the program passes."""
    n, s = 3000, 256
    A = _operand(n, 32, 9)
    T = sk.FJLT(n, s, Context(21))
    D, idx = reference.streams(21, 0, n, s)
    ref = reference.apply_cols(A, D, idx)
    assert _rel(T.apply(A, sk.COLUMNWISE), ref) < REL_MAX
    table = reference.cosine_sum_cols(A, D, idx, "highest", "bf16")
    assert _rel(table, ref) > 100 * REL_MAX
    assert _rel(reference.apply_cols(A, D, idx, "bf16x2"), ref) > 1.5 * REL_MAX


def test_a_height_the_rule_declines_and_other_dtypes_keep_the_eager_route():
    A = _operand(1009, 8, 1)                            # a prime past 256
    T = sk.FJLT(1009, 64, Context(1))
    assert T.mix_plan(A, False) is None
    program = fjlt._mix_program()
    before = program.stats.executions
    out = T.apply(A, sk.COLUMNWISE)
    assert program.stats.executions == before and out.shape == (64, 8)
    D, idx = reference.streams(1, 0, 1009, 64)
    assert _rel(out, reference.apply_cols(A, D, idx)) < 5e-6
    U = sk.FJLT(1000, 64, Context(1))
    B = _operand(1000, 8, 2)
    assert U.mix_plan(B.astype(jnp.bfloat16), False) is None
    assert U.mix_plan(B, True) == ("xla_dft", (10, 20, 5), fjlt.dft_tile(1000))
    low = U.apply(B.astype(jnp.bfloat16), sk.COLUMNWISE)
    assert low.dtype == jnp.bfloat16 and low.shape == (64, 8)


def test_an_operand_on_several_devices_keeps_the_eager_composition():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("needs two devices")
    n, s = 1000, 128
    A = _operand(n, 16, 5)
    T = sk.FJLT(n, s, Context(15))
    sharded = jax.device_put(
        A, NamedSharding(Mesh(np.asarray(devices[:2]), ("c",)), P(None, "c")))
    assert T.mix_plan(sharded, False) is None
    assert T.mix_plan(A, False) is not None
    program = fjlt._mix_program()
    before = program.stats.executions
    out = T.apply(sharded, sk.COLUMNWISE)
    assert program.stats.executions == before
    assert _rel(out, T.apply(A, sk.COLUMNWISE)) < 2e-6
    # an operand that lies on another device than the first: its own tables
    moved = jax.device_put(A, devices[1])
    assert np.array_equal(np.asarray(T.apply(moved, sk.COLUMNWISE)),
                          np.asarray(T.apply(A, sk.COLUMNWISE)))


def test_under_a_callers_jit_it_is_part_of_the_callers_program():
    n, s = 1000, 128
    A = _operand(n, 16, 2)
    T = sk.FJLT(n, s, Context(6))
    program = fjlt._mix_program()
    before = program.stats.executions
    inside = jax.jit(lambda x: T.apply(x, sk.COLUMNWISE))(A)
    assert program.stats.executions == before
    assert _rel(inside, T.apply(A, sk.COLUMNWISE)) < 1e-6
    assert program.stats.executions == before + 1


def test_one_program_an_apply_and_no_recompile_on_the_second():
    from libskylark_tpu import engine

    n, s = 3000, 256
    A, B = _operand(n, 40, 1), _operand(n, 40, 2)
    T = sk.FJLT(n, s, Context(8))
    T.apply(A, sk.COLUMNWISE).block_until_ready()
    program = fjlt._mix_program()
    compiles, ran = engine.stats().compiles, program.stats.executions
    tables = fjlt._dft_tables_on(fut.dft_factors(n), next(iter(A.devices())))
    # another transform of the shape, another operand: the key is an argument,
    # the tables the same device arrays
    U = sk.FJLT(n, s, Context(9))
    U.apply(B, sk.COLUMNWISE).block_until_ready()
    T.apply(B, sk.COLUMNWISE).block_until_ready()
    assert engine.stats().compiles == compiles
    assert program.stats.executions == ran + 2
    assert fjlt._dft_tables_on(fut.dft_factors(n), next(iter(A.devices())))[0] is tables[0]


def test_span_attributes_and_the_counter():
    from libskylark_tpu import telemetry
    from libskylark_tpu.telemetry import metrics, trace

    n, s, m = 6000, 128, 24
    A = _operand(n, m, 1)
    T = sk.FJLT(n, s, Context(3))
    factors = fut.dft_factors(n)
    wide = jnp.tile(A.T, (30, 1))           # rowwise: the tile is cut
    hadamard = sk.FJLT(1024, s, Context(3), fut="wht")
    before_enabled = metrics._ENABLED
    counted = fjlt._MIXED.value(family="FJLT", kernel="xla_dft")
    trace.clear_finished()
    telemetry.set_enabled(True)
    try:
        T.apply(A, sk.COLUMNWISE).block_until_ready()
        spans = {sp.name: sp for sp in trace.finished_spans()}
        T.apply(wide, sk.ROWWISE).block_until_ready()
        hadamard.apply(A[:1024], sk.COLUMNWISE).block_until_ready()
        _, row, wht = [sp.attrs for sp in trace.finished_spans()
                       if sp.name == "sketch.dispatch"]
    finally:
        metrics._ENABLED = before_enabled
        trace.clear_finished()
    dispatch, apply = spans["sketch.dispatch"], spans["sketch.apply"]
    assert dispatch.parent_id == apply.span_id
    # whole rows of the columnwise operand, every slab of the sampled digit
    assert dispatch.attrs == {
        "path": "fut", "family": "FJLT", "fut": "dct", "kernel": "xla_dft",
        "factors": factors, "tile": m, "slabs": factors[0],
        "elements": n * m, "sampled": s * m}
    assert factors[0] * factors[1] * factors[2] == n
    assert "sketch.plan" not in spans
    assert (row["tile"], row["slabs"]) == (fjlt.dft_tile(n), factors[0])
    assert "slabs" not in wht               # the Hadamard route has none
    assert fjlt._MIXED.value(family="FJLT", kernel="xla_dft") == (
        counted + n * m + n * 30 * m)


def test_the_kernel_name_is_declared():
    import inspect

    from libskylark_tpu.telemetry import names

    assert names.METRICS["sketch.mixed_elements"] == "counter"
    assert '"xla_dft"' in inspect.getsource(names)


# -- the solvers that send it -------------------------------------------------


@pytest.mark.parametrize("solver", ["fast", "approximate"])
def test_the_solvers_run_the_program_inside_theirs(solver, monkeypatch):
    from libskylark_tpu.nla import least_squares

    calls = []
    inner = fjlt.fjlt_mix_sample

    def counted(key_data, A, *tables, **statics):
        calls.append((A.shape, statics["kernel"], statics["fut"], tables))
        return inner(key_data, A, *tables, **statics)

    monkeypatch.setattr(fjlt, "fjlt_mix_sample", counted)
    m, n = 5000, 12                         # no power of two: the DCT
    rng = np.random.default_rng(3)
    A = _operand(m, n, 10)
    x = jnp.asarray(rng.standard_normal((n, 2)), jnp.float32)
    B = A @ x + 1e-3 * _operand(m, 2, 11)
    exact = jnp.linalg.lstsq(A, B)[0]
    if solver == "fast":
        X, iters = least_squares.fast_least_squares(A, B, Context(31))
        assert int(iters) > 0
        assert _rel(X, exact) < 1e-4
        assert calls == [((m, n), "xla_dft", "dct", ())]
    else:
        X = least_squares.approximate_least_squares(A, B, Context(32))
        assert _rel(X, exact) < 5e-2
        assert calls == [((m, n), "xla_dft", "dct", ()),
                         ((m, 2), "xla_dft", "dct", ())]


# -- the sample indices' draw -------------------------------------------------


def test_the_programs_samples_on_a_span_that_is_no_power_of_two():
    """N = 1,000,000: 2³² mod N ≠ 0, so the high word of each draw counts
    (``UniformInt``'s other path); the coordinates the program reads are the
    plain reference's, and a wrong multiplier would not be."""
    n, s = 1_000_000, 4096 + 100            # past one chunk of the stream
    assert threefry.randint_multiplier(n) == (1 << 32) % n != 0
    dist = randgen.UniformInt(0, n - 1)
    assert dist.live_draws() != (1,)
    T = sk.FJLT(n, s, Context(77))
    D, idx = reference.streams(77, 0, n, s)
    got = np.asarray(T.sample_indices())
    assert np.array_equal(got, np.asarray(idx))
    assert np.array_equal(np.asarray(T.diagonal()), np.asarray(D))
    assert got.min() >= 0 and got.max() < n and got.max() > n - n // 16
    # what the compiled program draws from the key words is the same stream
    key = jax.random.wrap_key_data(threefry.fold_in(T.allocation.key_data, 1))
    inside = randgen.stream_slice(key, dist, 0, s, dtype=jnp.int32)
    assert np.array_equal(np.asarray(inside), got)
    # low word alone (the power-of-two shortcut) is another stream
    low_only = np.asarray(reference.streams(77, 0, 1 << 20, s)[1])
    assert not np.array_equal(low_only % n, got)
