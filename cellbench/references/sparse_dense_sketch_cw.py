"""Plain reference of the dense sketch (JLT) of a sparse operand, columnwise:
Y = S·X with the operator S (s × m) materialised from (context seed,
allocation counter) alone, a panel of its columns at a time, and the rows of
X densified.

It follows the published definitions, not the program's code, and imports
nothing of the program (the cipher and the inverse-CDF map below are this
file's own copies, as ``sparse_dense_sketch.py`` has its own):

* an allocation's key is ``fold_in(key(seed), counter)`` of JAX's own
  Threefry generator (``libSkylark base/context.hpp``: a context hands out
  counter ranges of one Threefry stream);
* the virtual (s × m) operator is laid out in column blocks of 256; block
  ``b`` has key ``fold_in(fold_in(key, 0), b)``, and with counters
  c[r, j] = r·128 + j the cipher Threefry-2x32-20 (Salmon et al., SC'11)
  of (c, c + s·128) gives two lanes of 32-bit words: lane 0 fills columns
  0..127 of the block and lane 1 columns 128..255 (README "Stream format",
  format 3). Column i of S is the column of example i;
* a word becomes a standard normal by the inverse CDF, z = √2·erfinv(2u − 1)
  with u its top 24 bits / 2²⁴, clamped one ulp inside (−1, 1);
* JLT scales by √(1/s) (``libSkylark sketch/JLT_data.hpp``);
* columnwise is S·A, the sketch of the row axis (``libSkylark
  sketch/transforms.hpp:12-18``), and the sparse operand enters as what it
  is, a matrix: ``Y = Σ_panels S[:, panel] · X[panel, :].toarray()``
  (``libSkylark base/Gemm.hpp`` computes the same product over the stored
  nonzeros).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_COLS = 256
PANEL_ROWS = 2048       # rows of X (columns of S) densified at a time
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
_PARITY = 0x1BD11BDA


def _rotl(x, r):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def threefry2x32(k0, k1, c0, c1):
    """Threefry-2x32 with 20 rounds: counter words (c0, c1) under key (k0, k1)."""
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(_PARITY))
    x0, x1 = c0 + ks[0], c1 + ks[1]
    for group in range(5):
        for r in _ROTATIONS[group % 2]:
            x0 = x0 + x1
            x1 = _rotl(x1, r) ^ x0
        x0 = x0 + ks[(group + 1) % 3]
        x1 = x1 + ks[(group + 2) % 3] + jnp.uint32(group + 1)
    return x0, x1


def bits_to_normal(bits):
    u = (bits >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2.0 ** -24)
    v = jnp.clip(2.0 * u - 1.0, -1.0 + 2.0 ** -23, 1.0 - 2.0 ** -23)
    return jnp.float32(2.0 ** 0.5) * jax.scipy.special.erfinv(v)


def allocation_key_data(context_seed: int, counter: int) -> jax.Array:
    """The key words of allocation ``counter`` of a context seeded
    ``context_seed``."""
    return jax.random.key_data(
        jax.random.fold_in(jax.random.key(context_seed), counter))


def _panel(key_data, first_block, *, s: int, blocks: int):
    """S[:, first_block·256 : (first_block + blocks)·256], float32, scaled."""
    half = BLOCK_COLS // 2
    base = jax.random.fold_in(jax.random.wrap_key_data(key_data), 0)

    def block(b):
        kd = jax.random.key_data(jax.random.fold_in(base, b))
        c = (jnp.arange(s, dtype=jnp.uint32)[:, None] * jnp.uint32(half)
             + jnp.arange(half, dtype=jnp.uint32)[None, :])
        lane0, lane1 = threefry2x32(kd[0], kd[1], c, c + jnp.uint32(s * half))
        return jnp.concatenate(
            [bits_to_normal(lane0), bits_to_normal(lane1)], axis=1)

    ids = first_block + jnp.arange(blocks, dtype=jnp.uint32)
    S = jnp.transpose(jax.vmap(block)(ids), (1, 0, 2)).reshape(s, -1)
    return S * jnp.float32((1.0 / s) ** 0.5)


operator_panel = jax.jit(_panel, static_argnames=("s", "blocks"))


def operator(context_seed: int, counter: int, s: int, m: int) -> jax.Array:
    """The whole (s × m) float32 JLT operator (small m: the tests')."""
    blocks = -(-m // BLOCK_COLS)
    return operator_panel(allocation_key_data(context_seed, counter),
                          jnp.uint32(0), s=s, blocks=blocks)[:, :m]


def _dot(Sp, A, precision: str):
    """S_panel · A. ``"highest"`` is the reference; ``"bf16"`` (both
    operands rounded to bfloat16, one pass, float32 accumulation) is the
    control: the reference one precision below."""
    if precision == "bf16":
        return jnp.dot(Sp.astype(jnp.bfloat16), A.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    if precision != "highest":
        raise ValueError(f"unknown precision {precision!r}")
    with jax.default_matmul_precision("highest"):
        return jnp.dot(Sp, A, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("s", "precision"))
def _add_panel(acc, key_data, first_block, dense, *, s: int, precision: str):
    Sp = _panel(key_data, first_block, s=s, blocks=dense.shape[0] // BLOCK_COLS)
    return acc + _dot(Sp, dense, precision)


def _panels(m: int):
    """(lo, hi) row panels of ``PANEL_ROWS`` rows; the last may be short."""
    return [(lo, min(lo + PANEL_ROWS, m)) for lo in range(0, m, PANEL_ROWS)]


def _padded(dense: np.ndarray) -> np.ndarray:
    """Rows zero-padded to a whole number of the stream's blocks (the
    operator's columns past m multiply zeros)."""
    return np.pad(dense, ((0, -dense.shape[0] % BLOCK_COLS), (0, 0)))


def apply_cols(X_cols, key_data, s: int, precision: str = "highest") -> jax.Array:
    """``S · X_cols.toarray()`` (s × columns) for a scipy sparse block cut
    to a few of its columns (all its m rows), accumulated over row panels
    in float32: Σ_panels S[:, panel] · X_cols[panel].toarray()."""
    X_cols = X_cols.tocsr()
    acc = jnp.zeros((s, X_cols.shape[1]), jnp.float32)
    for lo, hi in _panels(X_cols.shape[0]):
        dense = _padded(X_cols[lo:hi].toarray().astype(np.float32))
        acc = _add_panel(acc, key_data, jnp.uint32(lo // BLOCK_COLS),
                         jnp.asarray(dense), s=s, precision=precision)
    return acc


@functools.partial(jax.jit, static_argnames=("s", "n", "span", "precision"))
def _apply_block(starts, cols, vals, row_of, key_data, *, s: int, n: int,
                 span: int, precision: str):
    def one(p, acc):
        lo = starts[p]
        c, v, r = (jax.lax.dynamic_slice_in_dim(x, lo, span)
                   for x in (cols, vals, row_of))
        local = r - p * PANEL_ROWS
        # a span reaches into the next panel's lanes: those are left out
        mine = (local >= 0) & (local < PANEL_ROWS)
        dense = jnp.zeros((PANEL_ROWS, n), jnp.float32).at[
            jnp.where(mine, local, 0), c].add(jnp.where(mine, v, 0.0))
        Sp = _panel(key_data, (p * (PANEL_ROWS // BLOCK_COLS)).astype(jnp.uint32),
                    s=s, blocks=PANEL_ROWS // BLOCK_COLS)
        return acc + _dot(Sp, dense, precision)

    return jax.lax.fori_loop(0, starts.shape[0], one,
                             jnp.zeros((s, n), jnp.float32))


def apply_block(X, key_data, s: int, precision: str = "highest") -> jax.Array:
    """The same product for a WHOLE scipy CSR row block, on the device:
    ``PANEL_ROWS`` rows at a time scattered into a dense (PANEL_ROWS × n)
    array and multiplied by their columns of S, summed — what the controls
    put in the program's place. The lanes travel as (column, value, row)
    triplets in row order, padded so that every panel reads one span of the
    same length; rows past m are rows of zeros."""
    m, n = X.shape
    edges = np.arange(0, m + PANEL_ROWS, PANEL_ROWS).clip(max=m)
    starts = X.indptr[edges[:-1]].astype(np.int32)
    widest = int(np.max(X.indptr[edges[1:]] - starts, initial=1))
    span = 1 << max(widest - 1, 1).bit_length()
    pad = (0, span)
    row_of = np.repeat(np.arange(m, dtype=np.int32), np.diff(X.indptr))
    return _apply_block(
        jnp.asarray(starts), jnp.asarray(np.pad(X.indices.astype(np.int32), pad)),
        jnp.asarray(np.pad(X.data.astype(np.float32), pad)),
        jnp.asarray(np.pad(row_of, pad, constant_values=-1)), key_data,
        s=s, n=n, span=span, precision=precision)


@functools.partial(jax.jit, static_argnames=("s", "blocks"))
def _panel_sums(key_data, first_block, weights, *, s: int, blocks: int):
    Sp = _panel(key_data, first_block, s=s, blocks=blocks)
    with jax.default_matmul_precision("highest"):
        return (jnp.sum(Sp, axis=0),
                jnp.dot(Sp, weights, precision=jax.lax.Precision.HIGHEST))


def operator_sums(key_data, s: int, m: int, row_sums: np.ndarray) -> tuple:
    """One pass over S: ``(1ᵀS, S·W)`` — the sum of S's rows, an (m,) vector,
    float64 on the host, and S times the (m × w) matrix ``row_sums`` (a
    column a block: X·1), float64 (s × w) summed over the panels."""
    ones_S = np.empty(m, np.float64)
    SW = np.zeros((s, row_sums.shape[1]), np.float64)
    for lo, hi in _panels(m):
        weights = _padded(np.asarray(row_sums[lo:hi], np.float32))
        blocks = weights.shape[0] // BLOCK_COLS
        sums, part = _panel_sums(key_data, jnp.uint32(lo // BLOCK_COLS),
                                 jnp.asarray(weights), s=s, blocks=blocks)
        ones_S[lo:hi] = np.asarray(sums, np.float64)[:hi - lo]
        SW += np.asarray(part, np.float64)
    return ones_S, SW


def law_z_scores(X_cols, Y_cols, s: int) -> tuple:
    """(z of the mean against 0, z of the variance against 1/s) of the
    served operator as ``Y_cols = S·X_cols`` shows it: with K = X_colsᵀ
    X_cols = V·Λ·Vᵀ, the entries of Y_cols·V·Λ^(−1/2) are i.i.d. N(0, 1/s)
    whenever S's entries are i.i.d. N(0, 1/s) — whatever examples the
    columns share. Directions of K under 1e-9 of its largest are left out
    (two features whose only lane lies in one example are one direction).
    Float64 on the host; infinite where no direction is left."""
    X64 = X_cols.astype(np.float64)
    lam, V = np.linalg.eigh(np.asarray((X64.T @ X64).todense()))
    kept = lam > 1e-9 * max(float(lam[-1]), 0.0)
    if not kept.any():
        return float("inf"), float("inf")
    W = np.asarray(Y_cols, np.float64) @ (V[:, kept] / np.sqrt(lam[kept]))
    count = W.size
    return (abs(float(W.mean())) * (count * s) ** 0.5,
            abs(float(W.var()) * s - 1.0) * (count / 2.0) ** 0.5)
