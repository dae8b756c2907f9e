"""Plain reference of the dense sketch (JLT) of a sparse operand, rowwise:
Y = X·Sᵀ with the operator S materialised from (context seed, allocation
counter) alone, and the rows of X densified.

It follows the published definitions, not the program's code, and imports
nothing of the program (the cipher and the inverse-CDF map below are this
file's own copies):

* an allocation's key is ``fold_in(key(seed), counter)`` of JAX's own
  Threefry generator (``libSkylark base/context.hpp``: a context hands out
  counter ranges of one Threefry stream);
* the virtual (s × n) operator is laid out in column blocks of 256; block
  ``b`` has key ``fold_in(fold_in(key, 0), b)``, and with counters
  c[r, j] = r·128 + j the cipher Threefry-2x32-20 (Salmon et al., SC'11)
  of (c, c + s·128) gives two lanes of 32-bit words: lane 0 fills columns
  0..127 of the block and lane 1 columns 128..255 (README "Stream format",
  format 3). ``n`` need be no multiple of 256: the last block is cut;
* a word becomes a standard normal by the inverse CDF, z = √2·erfinv(2u − 1)
  with u its top 24 bits / 2²⁴, clamped one ulp inside (−1, 1);
* JLT scales by √(1/s) (``libSkylark sketch/JLT_data.hpp``);
* the sparse operand enters as what it is, a matrix: ``X_rows.toarray()``
  (``libSkylark base/Gemm.hpp:335-519`` computes the same product over the
  stored nonzeros).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_COLS = 256
ROW_BLOCK = 2048        # rows densified at a time by :func:`apply_block`
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


@functools.partial(jax.jit, static_argnames=("s", "n"))
def _operator(key_data, *, s: int, n: int):
    half = BLOCK_COLS // 2
    base = jax.random.fold_in(jax.random.wrap_key_data(key_data), 0)

    def block(b):
        kd = jax.random.key_data(jax.random.fold_in(base, b))
        c = (jnp.arange(s, dtype=jnp.uint32)[:, None] * jnp.uint32(half)
             + jnp.arange(half, dtype=jnp.uint32)[None, :])
        lane0, lane1 = threefry2x32(kd[0], kd[1], c, c + jnp.uint32(s * half))
        return jnp.concatenate(
            [bits_to_normal(lane0), bits_to_normal(lane1)], axis=1)

    blocks = jax.vmap(block)(jnp.arange(-(-n // BLOCK_COLS), dtype=jnp.uint32))
    S = jnp.transpose(blocks, (1, 0, 2)).reshape(s, -1)[:, :n]
    return S * jnp.float32((1.0 / s) ** 0.5)


def operator(context_seed: int, counter: int, s: int, n: int) -> jax.Array:
    """The (s × n) float32 JLT operator of allocation ``counter`` of a
    context seeded ``context_seed``; any ``n``."""
    alloc = jax.random.fold_in(jax.random.key(context_seed), counter)
    return _operator(jax.random.key_data(alloc), s=s, n=n)


def _dot(A, St, precision: str):
    """A · Sᵀ. ``"highest"`` is the reference; ``"bf16"`` (both operands
    rounded to bfloat16, one pass, float32 accumulation) is the control:
    the reference one precision below."""
    if precision == "bf16":
        return jnp.dot(A.astype(jnp.bfloat16), St.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    if precision != "highest":
        raise ValueError(f"unknown precision {precision!r}")
    with jax.default_matmul_precision("highest"):
        return jnp.dot(A, St, precision=jax.lax.Precision.HIGHEST)


def apply_rows(X_rows, S, precision: str = "highest",
               block: int = ROW_BLOCK) -> jax.Array:
    """``X_rows.toarray() · Sᵀ`` for a scipy sparse (or dense) row block,
    ``block`` rows densified at a time, float32."""
    out = []
    for lo in range(0, X_rows.shape[0], block):
        rows = X_rows[lo:lo + block]
        dense = rows.toarray() if hasattr(rows, "toarray") else rows
        out.append(_dot(jnp.asarray(dense, jnp.float32), S.T, precision))
    return jnp.concatenate(out)


@functools.partial(jax.jit, static_argnames=("rows", "span", "precision"))
def _apply_block(starts, cols, vals, row_of, S, *, rows: int, span: int,
                 precision: str):
    n = S.shape[1]
    St = S.T

    def one(lo_and_block):
        lo, b = lo_and_block
        c, v, r = (jax.lax.dynamic_slice_in_dim(x, lo, span)
                   for x in (cols, vals, row_of))
        local = r - b * ROW_BLOCK
        # a span reaches into the next block's lanes: those are left out
        mine = (local >= 0) & (local < ROW_BLOCK)
        dense = jnp.zeros((ROW_BLOCK, n), jnp.float32).at[
            jnp.where(mine, local, 0), c].add(jnp.where(mine, v, 0.0))
        return _dot(dense, St, precision)

    blocks = jnp.arange(starts.shape[0], dtype=jnp.int32)
    return jax.lax.map(one, (starts, blocks)).reshape(-1, S.shape[0])[:rows]


def apply_block(X, S, precision: str = "highest") -> jax.Array:
    """The same product for a WHOLE scipy CSR row block, on the device:
    ``ROW_BLOCK`` rows at a time scattered into a dense (ROW_BLOCK × n)
    array and multiplied — what the controls put in the program's place.
    The lanes travel as (column, value, row) triplets in row order, padded
    so that every block reads one span of the same length."""
    rows = X.shape[0]
    edges = np.arange(0, rows + ROW_BLOCK, ROW_BLOCK).clip(max=rows)
    starts = X.indptr[edges[:-1]].astype(np.int32)
    widest = int(np.max(X.indptr[edges[1:]] - starts, initial=1))
    span = 1 << max(widest - 1, 1).bit_length()
    pad = (0, span)
    row_of = np.repeat(np.arange(rows, dtype=np.int32), np.diff(X.indptr))
    return _apply_block(
        jnp.asarray(starts), jnp.asarray(np.pad(X.indices.astype(np.int32), pad)),
        jnp.asarray(np.pad(X.data.astype(np.float32), pad)),
        jnp.asarray(np.pad(row_of, pad, constant_values=-1)), S,
        rows=rows, span=span, precision=precision)


def column_sums_sketch(column_sums, S) -> jax.Array:
    """Σ_r Y[r, :] as the definition gives it from the operand's column
    sums: (Σ_r X[r, :]) · Sᵀ."""
    return _dot(jnp.asarray(column_sums, jnp.float32)[None, :], S.T,
                "highest")[0]


def operator_column_sums(S) -> np.ndarray:
    """Σ_j S[j, :], float64 on the host."""
    return np.asarray(S, np.float64).sum(axis=0)


def row_sums_sketch(X, column_sums_of_S) -> np.ndarray:
    """Σ_j Y[r, j] for every row r of a scipy sparse block, as the
    definition gives it: X·(Σ_j S[j, :]), float64 on the host. One stored
    nonzero left out moves its row's sum by value·Σ_j S[j, c], a standard
    normal's worth of its value."""
    return X.astype(np.float64) @ column_sums_of_S


def expected_sq_norm(energy, gram_hot, hot, S) -> float:
    """‖X·Sᵀ‖²_F with the rows' cross terms kept for the ``hot`` columns
    only: Σ_c ‖X[:, c]‖²·‖S[:, c]‖² + Σ_{c ≠ c' hot} G[c, c']·⟨S[:, c],
    S[:, c']⟩, G the Gram matrix XᵀX of those columns. Over the other
    pairs the inner products of S's columns stay random with mean 0, and
    E‖Y‖²_F = ‖X‖²_F is the transform's guarantee; a few hot columns that
    many rows share add up coherently, so their share is taken as known."""
    col_sq = jnp.sum(S.astype(jnp.float32) ** 2, axis=0)
    own = float(jnp.sum(jnp.asarray(energy, jnp.float32) * col_sq))
    S_hot = S[:, jnp.asarray(hot)]
    inner = _dot(S_hot.T, S_hot, "highest")
    off = 1.0 - jnp.eye(inner.shape[0], dtype=jnp.float32)
    return own + float(jnp.sum(off * inner * gram_hot))


def law_z_scores(X_rows, Y_rows, s: int) -> tuple:
    """(z of the mean against 0, z of the variance against 1/s) of the
    served operator as ``Y_rows = X_rows·Sᵀ`` shows it: with K = X_rows
    X_rowsᵀ = L·Lᵀ, the rows of L⁻¹·Y_rows are i.i.d. N(0, I/s) whenever
    S's entries are i.i.d. N(0, 1/s) — whatever features the rows share.
    Float64 on the host; infinite where K is not positive definite."""
    X64 = np.asarray(X_rows.toarray() if hasattr(X_rows, "toarray")
                     else X_rows, np.float64)
    try:
        L = np.linalg.cholesky(X64 @ X64.T)
    except np.linalg.LinAlgError:
        return float("inf"), float("inf")
    W = np.linalg.solve(L, np.asarray(Y_rows, np.float64))
    count = W.size
    return (abs(float(W.mean())) * (count * s) ** 0.5,
            abs(float(W.var()) * s - 1.0) * (count / 2.0) ** 0.5)
