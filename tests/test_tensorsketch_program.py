"""The TensorSketch apply as one compiled program (sketch/ppt.py,
``sketch.tensorsketch_features``): against the plain reference of
cellbench/references/tensorsketch_features.py and against the eager chain
(``PPT._sketch_columns``) at small sizes, seeded, on the CPU.

- the program, rowwise and columnwise, whole and walked in row blocks that
  do not divide the rows, against both;
- the tensor-power statement of the definition (the CountSketch of x'^{⊗q});
- the spectral product over a packed K alone, against a float64 product;
- the products formed by bucket class (PR 63): every radix, both ways, each
  degree, against the chain; the edge bins; an overfull class;
- the lower-precision controls fail the tolerance the sound program holds;
- each broken variant of the map — a sketch dropped, a sketch shared, a
  truncated spectrum, the homogeneity term missing — fails the cell's check;
- what the program declines keeps the chain and says so in the span;
- the span's attributes and the counter; the streams' bits.

Tolerances, each with its reason: ``REL`` 2e-6 of the result's largest
entry — every product carries float32 on both sides (the spectral ones as
six bfloat16 partial products over a packed K), the operator's entries
are right to an ulp or two, and three spectra's product and two stages of
at most 256 terms each add up a few 1e-7 (read: 1.0e-7…4.4e-7 on these
shapes); a control one bfloat16 part wide reads 1e-3…5e-3, three orders
above.
"""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench.drivers import tensorsketch_apply as driver
from cellbench.references import tensorsketch_features as reference
from libskylark_tpu import sketch as sk
from libskylark_tpu.base.context import Context
from libskylark_tpu.ml import kernels
from libskylark_tpu.sketch import ppt

REL = 2e-6
SHAPES = [(20, 64, 2), (33, 256, 3), (784, 1024, 3)]
CONFIG = json.loads((pathlib.Path(__file__).resolve().parent.parent / "cellbench"
                     / "configs" / "ppt_mnist_d784_s16384_q3.json").read_text())


def _map(d, s, q, seed=7, c=1.3):
    return sk.PPT(d, s, Context(seed), q=q, c=c, gamma=0.7 / d)


def _examples(rows, d, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal((rows, d)),
                       jnp.float32)


def _spec(T):
    return (T.sketch_type, T.input_dim, T.sketch_dim,
            tuple(sorted(T._extra_params().items())))


def _rel(got, want):
    want = np.asarray(want)
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


# -- the program against the reference and the chain --------------------------


@pytest.mark.parametrize("d,s,q", SHAPES)
@pytest.mark.parametrize("rows,row_block", [(37, 0), (37, 16), (50, 24), (8, 8)])
def test_rowwise_program_matches_reference_and_chain(d, s, q, rows, row_block):
    """Whole and in row blocks that do not divide the rows (the last block is
    drawn back to end with the operand): the same features either way."""
    _rowwise_against_both(d, s, q, rows, row_block)


# what the walk adapts on — rows % 8, block % 8, the split's N1 % 128 — and
# the degrees and splits beside the cell's: (d, s, q, rows, row_block)
WALKS = [
    (20, 16384, 3, 40, 16),   # whole tiles, N1 = 128: the turned store, drawn back
    (20, 16384, 2, 32, 8),    # the same, the blocks dividing the rows
    (20, 32768, 2, 24, 16),   # the turned store on a split that is not square
    (20, 16384, 3, 40, 12),   # rows whole tiles, the block not: the plain store
    (20, 16384, 3, 37, 16),   # the block whole tiles, the rows not
    (33, 256, 3, 40, 16),     # whole tiles, N1 = 16: the plain store
    (33, 256, 3, 40, 12),     # neither a multiple of 8
    (33, 256, 1, 40, 16),     # q = 1: no product
    (33, 256, 2, 40, 16),
    (33, 256, 4, 40, 16),
    (20, 8192, 3, 24, 16),    # S = 8192 splits 64 · 128
    (20, 8192, 4, 21, 0),
]


def _rowwise_against_both(d, s, q, rows, row_block):
    T, X = _map(d, s, q), _examples(rows, d)
    out = ppt.tensorsketch_features(T._alloc.key_data, X, spec=_spec(T),
                                    rowwise=True, row_block=row_block)
    assert out.shape == (rows, s) and out.dtype == jnp.float32
    parts = reference.streams(7, 0, d, s, q)
    assert _rel(out, reference.features(X, parts, 0.7 / d, 1.3)) < REL
    assert _rel(out, T._sketch_columns(X.T).T) < REL
    return out


@pytest.mark.parametrize("d,s,q,rows,row_block", WALKS)
def test_every_walk_matches_reference_and_chain(d, s, q, rows, row_block):
    """Each store the walk chooses from its shapes, each degree, each kind
    of split: the reference's and the chain's features; and a block drawn
    back writes the rows it shares with its neighbour to the same bits as
    the whole operand in one block does."""
    out = _rowwise_against_both(d, s, q, rows, row_block)
    if row_block and rows % row_block:
        T = _map(d, s, q)
        whole = ppt.tensorsketch_features(T._alloc.key_data, _examples(rows, d),
                                          spec=_spec(T), rowwise=True)
        assert _rel(out, whole) < REL / 4


@pytest.mark.parametrize("d,s,q,rows,row_block", [WALKS[0], WALKS[5], WALKS[7],
                                                  WALKS[9], WALKS[10]])
def test_program_is_the_parents_formula(d, s, q, rows, row_block):
    """Against the block formula of PR 51 kept here word for word (halves
    joined by ``concatenate``, the Nyquist bin read off the running product,
    stage two a plain ``dot``, the digits turned by a transpose): the same
    passes in the same order. Since PR 54 the two sides no longer share
    the spectral product — the formula's is ``jnp.dot(…, HIGHEST)``, on a
    CPU one float32 product of N terms, the program's the six bfloat16
    partial products over the packed K added up in float32, 6N terms in
    another order — so they agree to a float32 sum's rounding on either
    side, not to a differently blocked sum's: 1.3e-7…3.2e-7 on these shapes
    where REL/8 = 2.5e-7 stood; held to REL/4, a quarter of what either is
    held to against the reference."""
    T, X = _map(d, s, q), _examples(rows, d)
    out = ppt.tensorsketch_features(T._alloc.key_data, X, spec=_spec(T),
                                    rowwise=True, row_block=row_block)
    hi = jax.lax.Precision.HIGHEST
    W = jnp.stack([ppt.spectral_operator(
        c.bucket_indices(), jnp.float32(np.sqrt(0.7 / d)) * c.values(jnp.float32), s)
        for c in T._cwts])
    bias = ppt.spectral_operator(T._hash_idx(), jnp.float32(np.sqrt(1.3))
                                 * T._hash_val(jnp.float32), s)
    n1, n2 = ppt.split(s)
    M1, Tc, Ts, M2 = ppt._inverse_factors(n1, n2)[:4]
    first = jnp.arange(s // 2, dtype=jnp.int32)[None, :] == 0
    re = im = None
    for k in range(q):
        F = jnp.dot(X, W[k], precision=hi) + bias[k]
        fre, fim = F[:, :s // 2], F[:, s // 2:]
        if re is None:
            re, im = fre, fim
            continue
        both = im * fim
        re, im = (re * fre - jnp.where(first, 0.0, both),
                  jnp.where(first, both, re * fim + im * fre))
    nyquist = im[:, 0] * jnp.float32(1.0 / s)
    U = jnp.concatenate([jnp.where(first, 0.5 * re, re),
                         jnp.where(first, 0.0, im)], axis=1)
    R = jnp.einsum("uk,bkc->buc", M1, U.reshape(rows, n1, n2), precision=hi)
    Rre, Rim = R[:, :n1], R[:, n1:]
    sign = (1 - 2 * (jnp.arange(n1, dtype=jnp.int32) & 1)).astype(jnp.float32)
    low = jnp.arange(n2, dtype=jnp.int32)[None, None, :] == 0
    ny = nyquist[:, None, None] * sign[None, :, None]
    V = jnp.concatenate([Rre * Tc[None] - Rim * Ts[None] + jnp.where(low, ny, 0.0),
                         Rre * Ts[None] + Rim * Tc[None]], axis=2)
    Z = jnp.dot(V.reshape(-1, 2 * n2), M2, precision=hi).reshape(rows, n1, n2)
    assert _rel(out, Z.transpose(0, 2, 1).reshape(rows, s)) < REL / 4


@pytest.mark.parametrize("d,s,q", SHAPES + [(20, 16384, 3), (20, 8192, 2)])
def test_apply_is_the_program_both_ways(d, s, q):
    """``apply`` rowwise and columnwise (column blocks, a block transposed on
    its way in and out) gives what the direct call gives."""
    T, X = _map(d, s, q), _examples(21, d)
    want = reference.features(X, reference.streams(7, 0, d, s, q), 0.7 / d, 1.3)
    assert _rel(T.apply(X, sk.ROWWISE), want) < REL
    assert _rel(T.apply(X.T, sk.COLUMNWISE).T, want) < REL
    walked = ppt.tensorsketch_features(T._alloc.key_data, X.T, spec=_spec(T),
                                       rowwise=False, row_block=8)
    assert walked.shape == (s, 21) and _rel(walked.T, want) < REL


def test_kernel_create_rft_reaches_the_program():
    T = kernels.Polynomial(33, q=3, c=1.0, gamma=1 / 33).create_rft(256, Context(5))
    X = _examples(19, 33)
    program = ppt._features_program()
    ran = program.stats.executions
    out = T.apply(X, sk.ROWWISE)
    assert program.stats.executions == ran + 1
    assert _rel(out, reference.features(X, reference.streams(5, 0, 33, 256, 3),
                                        1 / 33, 1.0)) < REL
    # under a caller's trace the same function is part of the caller's program
    inside = jax.jit(lambda A: T.apply(A, sk.ROWWISE))(X)
    assert program.stats.executions == ran + 1
    assert _rel(inside, out) < REL


def test_tensor_power_statement():
    """The FFT form is the CountSketch of the explicit tensor power x'^{⊗3},
    x' = (√γ·x, √c): bucket Σ h_k(j_k) mod S, sign ∏ s_k(j_k) — 7³ terms a
    row in float64 on the host."""
    d, s, q = 6, 64, 3
    T, X = _map(d, s, q), _examples(5, d)
    parts = reference.streams(7, 0, d, s, q)
    power = reference.tensor_power_sketch(X, parts, 0.7 / d, 1.3)
    assert _rel(reference.features(X, parts, 0.7 / d, 1.3), power) < REL
    assert _rel(T.apply(X, sk.ROWWISE), power) < REL


@pytest.mark.parametrize("d,s,q", SHAPES)
@pytest.mark.parametrize("control", ["program_bf16", "reference_bf16"])
def test_lower_precision_controls_fail_the_tolerance(d, s, q, control):
    T, X = _map(d, s, q), _examples(37, d)
    parts = reference.streams(7, 0, d, s, q)
    want = reference.features(X, parts, 0.7 / d, 1.3)
    if control == "program_bf16":
        low = ppt.tensorsketch_features(T._alloc.key_data, X, spec=_spec(T),
                                        rowwise=True, grade="bf16")
    else:
        low = reference.features(X, parts, 0.7 / d, 1.3, "bf16")
    assert _rel(low, want) > 100 * REL


# -- the spectral product over a packed K ---------------------------------------


@pytest.mark.parametrize("n", [20, 33, 128, 784, 800])
def test_packed_product_is_the_float32_grade_product(n):
    """One bfloat16 product over the six partial products laid along K
    against a float64 product: as close as ``jnp.dot(…, HIGHEST)`` (a
    float32 product on a CPU; no more than twice its error — the two add
    N and 6N terms up in float32 in different orders) and at least 100 ×
    under the one-part control; the split's three parts add back up to the
    operand bit for bit; and the K a product contracts is 6N (N for the
    control), in :func:`ppt.k_tiles` 128-deep tiles."""
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.standard_normal((48, n)), jnp.float32)
    w = ppt.spectral_operator(
        jnp.asarray(rng.integers(0, 256, n), jnp.int32),
        jnp.asarray(rng.choice([-1.0, 1.0], n) / np.sqrt(n), jnp.float32), 256)
    for a in (x, w):
        hi, mid, lo = (p.astype(jnp.float32) for p in ppt.bf16_parts(a))
        assert np.array_equal(np.asarray((hi + mid) + lo), np.asarray(a))
        assert float(jnp.abs(mid).max()) <= 2.0 ** -8 * float(jnp.abs(a).max())
    want = np.asarray(x, np.float64) @ np.asarray(w, np.float64)

    def product(grade):
        xc, wc = ppt.packed(x, 0, grade), ppt.packed(w, 1, grade)
        assert xc.dtype == wc.dtype == jnp.bfloat16
        assert xc.shape[1] == wc.shape[0] <= 128 * ppt.k_tiles(n, grade)
        return xc.shape[1], _rel(jnp.dot(xc, wc, preferred_element_type=jnp.float32),
                                 want)

    (k, packed), (k1, one_part) = product("float32"), product("bf16")
    assert (k, k1) == (6 * n, n)
    highest = _rel(jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST), want)
    assert packed <= 2 * highest and 100 * packed <= one_part, (packed, highest,
                                                                one_part)


@pytest.mark.parametrize("n,tiles,one_part", [
    (784, 37, 7), (768, 36, 6), (800, 38, 7), (20, 1, 1), (21, 1, 1), (22, 2, 1),
    (128, 6, 1), (33, 2, 1), (234, 11, 2), (235, 12, 2)])
def test_k_tiles(n, tiles, one_part):
    """⌈6N/128⌉ — never more than six products each padded by itself, and
    fewer wherever the last tile of N is under five sixths full (ISSUE 54
    wrote "equal only where N is a multiple of 128": N mod 128 ≥ 107 is
    equal too)."""
    assert ppt.k_tiles(n) == tiles <= 6 * -(-n // 128)
    assert (ppt.k_tiles(n) < 6 * -(-n // 128)) == (0 < n % 128 < 107)
    assert ppt.k_tiles(n, "bf16") == one_part


def test_bf16_regime_reaches_the_program():
    from libskylark_tpu.sketch import params

    T, X = _map(33, 256, 3), _examples(16, 33)
    sound = T.apply(X, sk.ROWWISE)
    before = params.get_pallas_precision()
    params.set_pallas_precision("bf16")
    try:
        plan = T.features_plan(X, True)
        assert (plan["grade"], plan["product"], plan["k_tiles"]) == (
            "bf16", "packed_k", 1)
        low = T.apply(X, sk.ROWWISE)
    finally:
        params.set_pallas_precision(before)
    assert _rel(low, sound) > 100 * REL


# -- the spectral products formed by bucket class (PR 63) ---------------------

# N no multiple of R, S = 512 … 4096 (splits 16·32, 32·32, 32·64, 64·64)
CLASS_SHAPES = [(33, 512), (61, 1024), (130, 2048), (100, 4096)]


def _by_class(T, X, radix, rowwise=True, **statics):
    """The program at ``radix``, compiled as ``apply`` compiles it."""
    import functools

    return jax.jit(functools.partial(
        ppt.tensorsketch_features, spec=_spec(T), rowwise=rowwise, radix=radix,
        **statics))(T._alloc.key_data, X if rowwise else X.T)


@pytest.mark.parametrize("q", [1, 2, 3])
@pytest.mark.parametrize("rowwise", [True, False])
@pytest.mark.parametrize("radix", [1, 2, 4, 8])
def test_class_products_match_the_chain(radix, rowwise, q):
    """The program at every radix, rowwise and columnwise, each degree: the
    eager chain's features to the file's float32 tolerance — every bin still
    the sum of all N (+ 1) terms, a bin one float32 addition a doubling of R
    further from them. The shapes turn with the case so that each split and
    each class capacity meets each radix."""
    d, s = CLASS_SHAPES[(q + radix.bit_length()) % len(CLASS_SHAPES)]
    T, X = _map(d, s, q), _examples(21, d)
    out = _by_class(T, X, radix, rowwise, row_block=8)
    want = T._sketch_columns(X.T)
    assert out.shape == ((21, s) if rowwise else (s, 21))
    assert _rel(out if rowwise else out.T, want.T) < REL


@pytest.mark.parametrize("c", [0.0, 1.3])
@pytest.mark.parametrize("radix", [2, 4, 8])
def test_class_products_edge_bins(radix, c):
    """A unit input in a bucket of each class (of each sketch in turn), so
    that every spectrum is a single tone and every bin weighs the same: a
    group's first bin at another weight than ½, a midpoint left out or at
    the wrong angle is an error of 1/S of the largest entry, alone — and with
    c = 0 no homogeneity term stands beside it."""
    d, s, q = 130, 2048, 3
    T = _map(d, s, q, c=c)
    picked = []
    for cwt in T._cwts:
        h = np.asarray(cwt.bucket_indices())
        picked += [int(np.flatnonzero(h % radix == p)[0]) for p in range(radix)]
    X = jnp.zeros((len(picked), d), jnp.float32).at[
        jnp.arange(len(picked)), jnp.asarray(picked)].set(1.0)
    want = T._sketch_columns(X.T).T
    assert _rel(_by_class(T, X, radix), want) < REL
    # the controls: the midpoints dropped, the first bins at full weight
    factors = ppt._inverse_factors

    def without_midpoints(n1, n2, r=1):
        M1, Tc, Ts, M2, mid = factors(n1, n2, r)
        return M1, Tc, Ts, M2, 0.0 * mid

    try:
        ppt._inverse_factors = without_midpoints
        assert _rel(_by_class(T, X, radix), want) > 100 * REL
    finally:
        ppt._inverse_factors = factors


@pytest.mark.parametrize("radix", [2, 4, 8])
def test_class_products_bf16_grade_stays_one_pass(radix):
    """The ``"bf16"`` grade (the cell's ``program_bf16`` control) takes the
    same classes with one term a product, K = c: a one-part error, two to
    three orders above the tolerance."""
    d, s, q = 130, 2048, 3
    T, X = _map(d, s, q), _examples(21, d)
    low = _by_class(T, X, radix, grade="bf16")
    assert 100 * REL < _rel(low, T._sketch_columns(X.T).T) < 3e-2
    assert ppt.k_tiles(d, "bf16", radix) == radix * -(-ppt.class_cols(d, radix) // 128)


def test_class_slots_and_operator():
    """Every input has one slot of its class's columns, in input order; a
    pad column's row of the operator is zero; the 0/1 matrix orders a
    block's columns as the operator's rows lie."""
    rng = np.random.default_rng(5)
    n, s, radix = 61, 1024, 4
    h = jnp.asarray(rng.integers(0, s, n), jnp.int32)
    v = jnp.asarray(rng.choice([-1.0, 1.0], n), jnp.float32)
    cols = ppt.class_cols(n, radix)
    slot = np.asarray(ppt.class_slots(h, radix))
    assert len(set(slot)) == n and slot.min() >= 0
    assert np.array_equal(slot // cols, np.asarray(h) % radix)
    for p in range(radix):
        mine = slot[np.asarray(h) % radix == p]
        assert np.array_equal(mine, p * cols + np.arange(len(mine)))
    W, order, mid = ppt.class_operator(h, v, s, radix)
    assert len(W) == radix and all(len(w) == 2 for w in W)
    assert all(part.shape == (6 * cols, s // (2 * radix))
               and part.dtype == jnp.bfloat16 for w in W for part in w)
    assert order.shape == (n, radix * cols)
    assert np.array_equal(np.asarray(order.astype(jnp.float32)).argmax(1), slot)
    assert float(order.astype(jnp.float32).sum()) == n
    free = np.setdiff1d(np.arange(radix * cols), slot)
    # the hi·hi term's rows are the last c of a packed operator
    hi = np.concatenate([np.asarray(w[0][-cols:].astype(jnp.float32)) for w in W])
    assert np.all(hi[free] == 0.0) and np.all(np.abs(hi[slot]).max(1) > 0.0)
    # the midpoints' operator: ±v on an input's own class, by its bucket's
    # next bit
    want = np.zeros((n, radix), np.float32)
    want[np.arange(n), np.asarray(h) % radix] = np.asarray(v) * (
        1 - 2 * ((np.asarray(h) // radix) & 1))
    assert np.array_equal(np.asarray(mid), want)
    # an input past its class's capacity has no slot (and no transform with
    # one takes the classes: test_overfull_class_takes_radix_1)
    crowded = np.asarray(ppt.class_slots(h // radix * radix, radix))
    assert (crowded == -1).sum() == n - cols


@pytest.mark.parametrize("n,radix,cols,tiles", [
    (784, 1, 784, 37), (784, 2, 448, 42), (784, 4, 256, 48), (784, 8, 144, 56),
    (130, 4, 64, 12), (33, 2, 40, 4), (440, 4, 168, 32)])
def test_class_cols_and_k_tiles(n, radix, cols, tiles):
    """N/R and four standard deviations, in sublanes, filled up to the MXU
    tile the packed K ends in; R·⌈6c/128⌉ tiles, each over S/R columns — at
    784 inputs 37 × S, 21 × S, 12 × S, 7 × S tile-columns a sketch."""
    assert ppt.class_cols(n, radix) == cols and cols % 8 == 0
    assert ppt.k_tiles(n, "float32", radix) == tiles == radix * -(-6 * cols // 128)
    if radix > 1:
        mean = n / radix
        assert cols >= mean + 4 * np.sqrt(mean * (1 - 1 / radix))
        assert -(-6 * (cols + 8) // 128) > tiles // radix   # the tile is full


@pytest.mark.parametrize("n,s,want", [
    (784, 16384, ppt._RADIX_MAX), (784, 1024, ppt._RADIX_MAX), (20, 16384, 1),
    (33, 256, 1), (61, 1024, 1), (784, 514, 1), (784, 24, 1)])
def test_radix_is_the_shapes(n, s, want):
    """A function of N and S alone: 1 where a class would be mostly padding,
    where S has no split or its N1 no whole groups."""
    assert ppt.radix(n, s) == want


def test_overfull_class_takes_radix_1(monkeypatch):
    """A transform whose buckets crowd one class (forced: every bucket a
    multiple of R) overflows ``class_cols``: it takes radix 1 — the whole
    product, today's program to the bit, never a dropped input — and the
    plan and the counter say so; the same shapes with the stream's own
    buckets take the classes."""
    d, s, q = 130, 2048, 3
    R = ppt.radix(d, s)
    assert R > 1
    X = _examples(23, d)                 # a shape no other test compiles
    sound = _map(d, s, q)
    assert sound.radix() == R
    plan = sound.features_plan(X, True)
    assert (plan["radix"], plan["class_cols"], plan["k_tiles"]) == (
        R, ppt.class_cols(d, R), ppt.k_tiles(d, "float32", R))
    counted = ppt._ROWS.value(family="PPT", route="program", radix=str(R))
    assert _rel(sound.apply(X, sk.ROWWISE), sound._sketch_columns(X.T).T) < REL
    assert ppt._ROWS.value(family="PPT", route="program",
                           radix=str(R)) == counted + 23

    cwt = type(sound._cwts[0])
    own = cwt.bucket_indices
    monkeypatch.setattr(cwt, "bucket_indices", lambda self: own(self) // R * R)
    T = _map(d, s, q)
    assert T.radix() == 1
    plan = T.features_plan(X, True)
    assert (plan["radix"], plan["class_cols"], plan["k_tiles"]) == (
        1, d, ppt.k_tiles(d))
    counted = ppt._ROWS.value(family="PPT", route="program", radix="1")
    out = T.apply(X, sk.ROWWISE)
    assert ppt._ROWS.value(family="PPT", route="program", radix="1") == counted + 23
    assert np.array_equal(np.asarray(out), np.asarray(_by_class(T, X, 1)))
    assert _rel(out, T._sketch_columns(X.T).T) < REL


@pytest.mark.parametrize("grade", ["float32", "bf16"])
@pytest.mark.parametrize("d,s,q,rows,row_block", [WALKS[0], WALKS[5], WALKS[10]])
def test_radix_1_is_the_parents_program(d, s, q, rows, row_block, grade,
                                        monkeypatch):
    """At radix 1 the block is PR 62's, kept here word for word
    (``_parent_block``) and run in its place: the same bits, both grades."""
    T, X = _map(d, s, q), _examples(rows, d)
    out = _by_class(T, X, 1, row_block=row_block, grade=grade)

    def parents(Xb, operators, factors, grade, radix):
        return _parent_block(Xb, operators[:2], factors[:4], grade)

    monkeypatch.setattr(ppt, "_block_features", parents)
    parent = _by_class(T, X, 1, row_block=row_block, grade=grade)
    assert np.array_equal(np.asarray(out), np.asarray(parent))


def _parent_block(Xb, operators, factors, grade):
    """``ppt._block_features`` of PR 62 (commit 04c4efe), word for word."""
    W, bias = operators
    M1, Tc, Ts, M2 = factors
    n1, n2 = Tc.shape
    B, s = Xb.shape[0], n1 * n2
    first = jnp.arange(s // 2, dtype=jnp.int32)[None, :] == 0
    x = jax.lax.optimization_barrier(ppt.packed(Xb, 0, grade))
    re = im = nyquist = None
    for k in range(len(W)):
        F = jnp.dot(x, W[k], precision=jax.lax.Precision.DEFAULT,
                    preferred_element_type=jnp.float32) + bias[k]
        fre, fim = F[:, :s // 2], F[:, s // 2:]
        if re is None:
            re, im, nyquist = fre, fim, fim[:, 0]
            continue
        nyquist = nyquist * fim[:, 0]
        both = im * fim
        re, im = (re * fre - jnp.where(first, 0.0, both),
                  jnp.where(first, both, re * fim + im * fre))
    U = jax.lax.dynamic_update_slice(
        jax.lax.empty((B, s), jnp.float32), jnp.where(first, 0.5 * re, re),
        (0, 0))
    U = jax.lax.dynamic_update_slice(U, jnp.where(first, 0.0, im), (0, s // 2))
    lo = 8 if B % 8 == 0 else 1
    X = U.reshape(B // lo, lo, n1, n2).transpose(0, 2, 1, 3)
    R = jnp.einsum("uk,hklc->hulc", ppt._grade(M1, grade), ppt._grade(X, grade),
                   precision=jax.lax.Precision.HIGHEST)
    Rre, Rim = R[:, :n1], R[:, n1:]
    sign = (1 - 2 * (jnp.arange(n1, dtype=jnp.int32) & 1)).astype(jnp.float32)
    low = jnp.arange(n2, dtype=jnp.int32)[None, None, None, :] == 0
    tc, ts = Tc[None, :, None, :], Ts[None, :, None, :]
    ny = ((nyquist * jnp.float32(1.0 / s)).reshape(B // lo, 1, lo, 1)
          * sign[None, :, None, None])
    V = jnp.concatenate([Rre * tc - Rim * ts + jnp.where(low, ny, 0.0),
                         Rre * ts + Rim * tc], axis=3)
    return jnp.einsum("hulk,kt->htlu", ppt._grade(V, grade), ppt._grade(M2, grade),
                      precision=jax.lax.Precision.HIGHEST)


# -- the cell's check, on sound and on broken maps ----------------------------


def _state(rows=301, d=33, s=256, seed=2 ** 31 + 77):
    cfg = dict(CONFIG, n=d, s=s, gamma=1.0 / d, rows_per_panel=rows, check_rows=64)
    return driver.setup(cfg, {}, seed)


def _checked(state, out):
    """The numbers of the check that pass their limits — the cell's own, but
    for ``norm_dev``, statistical and restated for S = 256 (one row's squared
    norm has sd √(11.5/S)·k: 0.21·k here, 2.6e-2·k at the cell's 16384)."""
    limits = dict(CONFIG["limits"], norm_dev=8 * CONFIG["limits"]["norm_dev"])
    got = driver.check(state, [(0, out)])
    return {name: value for name, value in got.items() if value > limits[name]}


def _variant(state, name):
    """The features of a broken map, from the reference on altered parts."""
    cfg = state.config
    parts = reference.streams(state.context_seed, 0, cfg["n"], cfg["s"], cfg["q"])
    X = state.panels[0]
    if name == "sound":
        return reference.features(X, parts, cfg["gamma"], cfg["c"])
    if name == "dropped_sketch":                    # q = 2 in q = 3's place
        parts = {k: (v if k == "s" else v[:2]) for k, v in parts.items()}
    elif name == "shared_sketch":                   # h_1 = h_0, s_1 = s_0
        parts = dict(parts, h=parts["h"].at[1].set(parts["h"][0]),
                     v=parts["v"].at[1].set(parts["v"][0]))
    elif name == "no_homogeneity":                  # the map of ⟨x, y⟩^q
        parts = dict(parts, hv=jnp.zeros_like(parts["hv"]))
    elif name == "truncated_spectrum":              # the upper half of the bins
        Z = np.fft.rfft(np.asarray(reference.features(X, parts, cfg["gamma"],
                                                      cfg["c"])), axis=1)
        Z[:, cfg["s"] // 4:] = 0.0
        return jnp.asarray(np.fft.irfft(Z, n=cfg["s"], axis=1), jnp.float32)
    return reference.features(X, parts, cfg["gamma"], cfg["c"])


def test_check_passes_the_program():
    state = _state()
    assert _checked(state, driver.step(state, 0)) == {}
    assert _checked(state, _variant(state, "sound")) == {}
    assert driver.describe(state)["route"] == "program"


@pytest.mark.parametrize("broken", ["dropped_sketch", "shared_sketch",
                                    "truncated_spectrum", "no_homogeneity"])
def test_check_fails_a_broken_map(broken):
    state = _state()
    failed = _checked(state, _variant(state, broken))
    assert "rel_max" in failed, failed


@pytest.mark.parametrize("stream,wrong", [("h", "half_range"), ("h", "all_even"),
                                          ("h", "past_the_end"), ("v", "all_plus")])
def test_check_fails_broken_streams(stream, wrong):
    parts = reference.streams(11, 0, 784, 16384, 3)
    sound = reference.law_z_scores(parts, 64)
    assert max(sound.values()) < 6.0
    if wrong == "half_range":
        parts = dict(parts, h=parts["h"].at[2].set(parts["h"][2] // 2))
    elif wrong == "all_even":
        parts = dict(parts, h=parts["h"].at[0].set(parts["h"][0] // 2 * 2))
    elif wrong == "past_the_end":
        parts = dict(parts, h=parts["h"].at[1, :8].set(16384))
    else:
        parts = dict(parts, v=jnp.ones_like(parts["v"]))
    got = reference.law_z_scores(parts, 64)
    assert got["bucket_chi2_z" if stream == "h" else "sign_mean_z"] > 6.0


# -- what the program declines ------------------------------------------------


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


def _sharded(X):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("rows",))
    return jax.device_put(X, NamedSharding(mesh, PartitionSpec("rows")))


@pytest.mark.parametrize("s,operand,reason", [
    (256, lambda: _examples(16, 33).astype(jnp.bfloat16), "dtype=bfloat16"),
    (514, lambda: _examples(16, 33), "s=514"),              # 2 · 257
    (255, lambda: _examples(16, 33), "s=255"),              # odd
    (256, lambda: _sharded(_examples(16, 33)), "devices=2"),
])
def test_declined_operands_keep_the_chain_and_say_why(s, operand, reason):
    T, X = _map(33, s, 3), operand()
    program = ppt._features_program()
    ran = program.stats.executions
    out, spans = _dispatch_of(lambda: T.apply(X, sk.ROWWISE))
    assert program.stats.executions == ran
    dispatch = next(sp for sp in spans if sp.name == "sketch.dispatch")
    assert dispatch.attrs["route"] == "chain" and dispatch.attrs["reason"] == reason
    assert dispatch.attrs["sketch"] == "segment_sum"
    assert dispatch.attrs["fft"] == "jnp.fft"
    assert np.array_equal(np.asarray(out), np.asarray(T._sketch_columns(X.T).T))


@pytest.mark.parametrize("s,want", [
    (16384, (128, 128)), (1024, (32, 32)), (256, (16, 16)), (64, (8, 8)),
    (512, (16, 32)), (24, (4, 6)), (2, (2, 1)), (65536, (256, 256)),
    (514, None), (255, None), (131072, None)])
def test_split(s, want):
    assert ppt.split(s) == want


def test_span_attributes_and_the_counter():
    from libskylark_tpu.telemetry.names import METRICS

    assert METRICS["sketch.tensorsketch_rows"] == "counter"
    rows, d, s, q = 24, 33, 256, 3
    T, X = _map(d, s, q), _examples(rows, d)
    counted = ppt._ROWS.value(family="PPT", route="program", radix="1")
    _, spans = _dispatch_of(lambda: T.apply(X, sk.ROWWISE))
    by_name = {sp.name: sp for sp in spans}
    dispatch, apply = by_name["sketch.dispatch"], by_name["sketch.apply"]
    assert dispatch.parent_id == apply.span_id
    assert by_name["sketch.plan"].parent_id == apply.span_id
    assert by_name["stream.key"].attrs["cached"] in (True, False)
    assert dispatch.attrs == {
        "path": "features", "family": "PPT", "q": q, "s": s, "rows": rows,
        "row_block": rows, "sketch": "spectral_operator", "fft": "mxu_two_stage",
        "route": "program", "grade": "float32", "product": "packed_k",
        "radix": 1, "class_cols": d, "k_tiles": 2, "features": rows * s,
        "elements": rows * s * (q + 1)}
    assert ppt._ROWS.value(family="PPT", route="program",
                           radix="1") == counted + rows


def test_block_rows():
    assert ppt.block_rows(60000, 16384) == 4096
    assert ppt.block_rows(100, 16384) == 100
    assert ppt.block_rows(10 ** 6, 64) == 10 ** 6


# -- the streams' bits --------------------------------------------------------


@pytest.mark.parametrize("seed,counter", [(3, 0), (2 ** 31 - 1, 0), (41, 2)])
def test_stream_bits_are_the_references(seed, counter):
    """Child k's sub-streams 0 and 1 and the parent's 100 and 101, to the
    bit, in the transform and inside the program's rebuilt one."""
    ctx = Context(seed)
    for _ in range(counter):
        ctx.allocate()
    T = sk.PPT(33, 256, ctx, q=3)
    parts = reference.streams(seed, counter, 33, 256, 3)
    inside = ppt.PPT._from_parts(33, 256, ppt._ProgramAllocation(
        T._alloc.key_data), {"q": 3})
    for which in (T, inside):
        mine = driver.transform_streams(which)
        for name in ("h", "v", "hh", "hv"):
            assert np.array_equal(np.asarray(mine[name]), np.asarray(parts[name]))


def test_one_program_a_shape():
    T1, T2 = _map(33, 256, 3, seed=1), _map(33, 256, 3, seed=2)
    X = _examples(12, 33)
    program = ppt._features_program()
    T1.apply(X, sk.ROWWISE)
    compiles = program.stats.compiles
    T2.apply(X, sk.ROWWISE)
    T1.apply(X + 1.0, sk.ROWWISE)
    assert program.stats.compiles == compiles
