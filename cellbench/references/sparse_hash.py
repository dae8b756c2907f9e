"""Plain reference of the hash sketch (CountSketch, CWT) of a sparse operand,
rowwise: Z[r, h(c)] += v(c)·X[r, c] over the stored nonzeros, h and v
rebuilt from (context seed, allocation counter) alone.

It follows the published definitions, not the program's code:

* an allocation's key is ``fold_in(key(seed), counter)`` of JAX's own
  Threefry generator (``libSkylark base/context.hpp``), and sub-stream ``t``
  of it is ``fold_in(key, t)``: 0 holds the buckets, 1 the signs
  (``libSkylark sketch/hash_transform_data.hpp``: ``row_idx``, ``row_value``);
* a stream is laid out in chunks of 4096; chunk ``c`` has key
  ``fold_in(fold_in(stream key, c >> 31), c & (2³¹ − 1))``, and with counters
  j < 2048 the cipher Threefry-2x32-20 (Salmon et al., SC'11) of (j, j + 2048)
  gives two lanes of 32-bit words: lane 0 fills positions 0..2047 of the
  chunk and lane 1 positions 2048..4095 (README "Stream format", format 3);
* a uniform integer on [0, s) takes two such draws, under the keys
  ``fold_in(chunk key, 0)`` (high word) and ``fold_in(chunk key, 1)`` (low
  word): the 64-bit word high·2³² + low reduced mod s in wrapping 32-bit
  arithmetic, ((high mod s)·(2³² mod s) + low mod s) mod s;
* a Rademacher sign is +1 where the word's top bit is 0, else −1
  (``libSkylark sketch/CWT_data.hpp``: values ±1).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cellbench.references.dense_sketch import threefry2x32

CHUNK = 4096
_MASK31 = (1 << 31) - 1


def _chunk_words(key):
    """The 4096 uint32 draws of one chunk under the (typed) key ``key``."""
    kd = jax.random.key_data(key)
    half = CHUNK // 2
    c = jnp.arange(half, dtype=jnp.uint32)
    lane0, lane1 = threefry2x32(kd[0], kd[1], c, c + jnp.uint32(half))
    return jnp.concatenate([lane0, lane1])


def _chunk_key(stream_key, chunk_id: int):
    return jax.random.fold_in(
        jax.random.fold_in(stream_key, chunk_id >> 31), chunk_id & _MASK31)


def streams(context_seed: int, counter: int, n: int, s: int):
    """(h, v): the bucket in [0, s) (int32) and the sign ±1 (float32) of each
    of the ``n`` input coordinates, for allocation ``counter`` of a context
    seeded ``context_seed``."""
    if not 0 < s < (1 << 16):
        raise ValueError(f"s must lie in (0, 65536), got {s}")
    alloc = jax.random.fold_in(jax.random.key(context_seed), counter)
    buckets, signs = jax.random.fold_in(alloc, 0), jax.random.fold_in(alloc, 1)
    span, mult = jnp.uint32(s), jnp.uint32((1 << 32) % s)
    h, v = [], []
    for chunk_id in range(-(-n // CHUNK)):
        key = _chunk_key(buckets, chunk_id)
        high = _chunk_words(jax.random.fold_in(key, 0))
        low = _chunk_words(jax.random.fold_in(key, 1))
        h.append(((high % span) * mult + low % span) % span)
        words = _chunk_words(_chunk_key(signs, chunk_id))
        v.append(jnp.where((words >> jnp.uint32(31)) == 0, 1.0, -1.0))
    return (jnp.concatenate(h)[:n].astype(jnp.int32),
            jnp.concatenate(v)[:n].astype(jnp.float32))


def _values(x, precision: str):
    """The operand's values as the reference reads them. ``"highest"`` is
    the reference; ``"bf16"`` (each value rounded to bfloat16, float32
    sums) is the control: the reference one precision below."""
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision != "highest":
        raise ValueError(f"unknown precision {precision!r}")
    return x.astype(jnp.float32)


def apply_rows(X_rows, h, v, s: int, precision: str = "highest",
               block: int = 64) -> jax.Array:
    """Z for dense rows ``X_rows`` (rows × n): a ``segment_sum`` over each
    row's own coordinates, ``block`` rows at a time."""
    out = []
    for lo in range(0, X_rows.shape[0], block):
        x = _values(jnp.asarray(X_rows[lo:lo + block]), precision)
        out.append(jax.ops.segment_sum(v[:, None] * x.T, h, num_segments=s).T)
    return jnp.concatenate(out)


def apply_coo(rows, cols, vals, h, v, shape: tuple,
              precision: str = "highest") -> jax.Array:
    """The same sum written over the stored nonzeros (row, column, value)
    of a whole operand with ``shape`` = (rows, s) of the result: what the
    controls put in the program's place."""
    return jnp.zeros(shape, jnp.float32).at[rows, h[cols]].add(
        v[cols] * _values(vals, precision))


def bucket_sums(column_sums, h, v, s: int) -> jax.Array:
    """Σ_r Z[r, b] as the definition gives it from the operand's column sums:
    Σ_{c: h(c) = b} v(c)·Σ_r X[r, c]."""
    return jax.ops.segment_sum(v * column_sums, h, num_segments=s)


def expected_sq_norm(x_sq_norm: float, gram_hot, h_hot, v_hot) -> float:
    """E‖Z‖²_F given the buckets and signs of the ``hot`` columns:
    ‖X‖²_F + Σ_{c ≠ c' hot, h(c) = h(c')} v(c)·v(c')·G[c, c'], with G the
    Gram matrix XᵀX of those columns. Over the other columns h and v stay
    random, and E‖Z‖²_F = ‖X‖²_F is the transform's guarantee; a few hot
    columns that every row shares collide coherently, so their share is
    taken as known."""
    same = (h_hot[:, None] == h_hot[None, :]) & ~jnp.eye(
        h_hot.shape[0], dtype=bool)
    cross = jnp.sum(jnp.where(same, jnp.outer(v_hot, v_hot) * gram_hot, 0.0))
    return float(x_sq_norm) + float(cross)


def law_z_scores(h, v, s: int) -> tuple:
    """(chi-square z of the bucket counts against uniform on [0, s),
    z of the signs' mean against 0)."""
    n = h.shape[0]
    counts = jnp.bincount(h, length=s).astype(jnp.float32)
    expected = n / s
    chi2 = float(jnp.sum((counts - expected) ** 2) / expected)
    return (abs(chi2 - (s - 1)) / (2.0 * (s - 1)) ** 0.5,
            abs(float(jnp.mean(v))) * n ** 0.5)
