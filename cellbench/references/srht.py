"""Plain reference of the subsampled randomized Hadamard transform (the
FJLT with the Walsh-Hadamard mixer), columnwise:

    S·A = √(N/s) · R · (H_N / √N) · D · A

with D a random ±1 diagonal, H_N the Sylvester Hadamard matrix (natural
ordering, H[i, j] = (−1)^popcount(i & j)) and R the rows ``idx`` sampled
uniformly with replacement. D and idx are rebuilt from (context seed,
allocation counter) alone, the transform is the plain log₂N-stage butterfly
(adds and subtracts only, float32), on a block of columns so that it fits.

It follows the published definitions, not the program's code:

* Blendenpik (Avron, Maymounkov, Toledo, SISC 32(3), 2010): mix the rows
  with a sign diagonal and a fast unitary transform, sample γ·n of them
  uniformly; libSkylark ``sketch/FJLT_data.hpp:83-86`` draws the samples
  with replacement and ``sketch/FJLT_Elemental.hpp:144-174`` scales them
  by √(N/s) after the transform scaled 1/√N (``sketch/FUT.hpp:55-56``).
  **Departures from upstream:** its FFTW build mixes with the DCT
  (``fftw_r2r`` REDFT10), its SpiralWHT build with the WHT — this
  reference and the configuration take the WHT; sampling is upstream's;
* an allocation's key is ``fold_in(key(seed), counter)`` of JAX's own
  Threefry generator (``libSkylark base/context.hpp``), and sub-stream ``t``
  of it is ``fold_in(key, t)``: 0 holds the signs (the underlying RFUT's
  D), 1 the sampled coordinates;
* a stream is laid out in chunks of 4096; chunk ``c`` has key
  ``fold_in(fold_in(stream key, c >> 31), c & (2³¹ − 1))``, and with counters
  j < 2048 the cipher Threefry-2x32-20 (Salmon et al., SC'11; written out in
  ``references/dense_sketch.py``) of (j, j + 2048) gives two lanes of 32-bit
  words: lane 0 fills positions 0..2047 of the chunk and lane 1 positions
  2048..4095 (README "Stream format", format 3; the two helpers that spell
  this layout out are ``references/sparse_hash.py``'s, the same streams
  being the CWT's signs and buckets);
* a Rademacher sign is +1 where the word's top bit is 0, else −1;
* a uniform integer on [0, N) takes two such draws, under the keys
  ``fold_in(chunk key, 0)`` (high word) and ``fold_in(chunk key, 1)`` (low
  word): the 64-bit word high·2³² + low reduced mod N in wrapping 32-bit
  arithmetic, ((high mod N)·(2³² mod N) + low mod N) mod N — for a power
  of two N that is low mod N.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from cellbench.references.sparse_hash import _chunk_key, _chunk_words

CHUNK = 4096


def streams(context_seed: int, counter: int, n: int, s: int):
    """(D, idx): the sign ±1 (float32) of each of the ``n`` coordinates and
    the ``s`` sampled coordinates in [0, n) (int32), for allocation
    ``counter`` of a context seeded ``context_seed``."""
    if not 0 < n < 1 << 31:
        raise ValueError(f"n must lie in (0, 2**31), got {n}")
    alloc = jax.random.fold_in(jax.random.key(context_seed), counter)
    signs, samples = jax.random.fold_in(alloc, 0), jax.random.fold_in(alloc, 1)
    span, mult = jnp.uint32(n), jnp.uint32((1 << 32) % n)
    D, idx = [], []
    for chunk_id in range(-(-n // CHUNK)):
        words = _chunk_words(_chunk_key(signs, chunk_id))
        D.append(jnp.where((words >> jnp.uint32(31)) == 0, 1.0, -1.0))
    for chunk_id in range(-(-s // CHUNK)):
        key = _chunk_key(samples, chunk_id)
        high = _chunk_words(jax.random.fold_in(key, 0))
        low = _chunk_words(jax.random.fold_in(key, 1))
        idx.append(((high % span) * mult + low % span) % span)
    return (jnp.concatenate(D)[:n].astype(jnp.float32),
            jnp.concatenate(idx)[:s].astype(jnp.int32))


@jax.jit
def hadamard_columns(x):
    """H_N · x for x (N, columns), N a power of two: the plain butterfly,
    log₂N stages of one add and one subtract an entry."""
    n = x.shape[0]
    if n & (n - 1):
        raise ValueError(f"the Hadamard transform needs a power of two, got {n}")
    h = 1
    while h < n:
        x = x.reshape(n // (2 * h), 2, h, -1)
        a, b = x[:, 0], x[:, 1]
        x = jnp.stack([a + b, a - b], axis=1).reshape(n, -1)
        h *= 2
    return x


def _bf16_part(x):
    # reduce_precision, not a cast and back: inside a compiled function XLA
    # may keep the excess precision of a convert pair
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _values(x, precision: str):
    """The operand's values as the reference reads them. ``"highest"`` is
    the reference. The controls, each with float32 sums: ``"bf16x2"``, the
    value cut to the first two bfloat16 parts of its three-part split (16
    significant bits of float32's 24: the nearest precision below the one
    the configuration states), and ``"bf16"``, to the first alone."""
    x = x.astype(jnp.float32)
    if precision == "highest":
        return x
    hi = _bf16_part(x)
    if precision == "bf16":
        return hi
    if precision == "bf16x2":
        return hi + _bf16_part(x - hi)
    raise ValueError(f"unknown precision {precision!r}")


@functools.partial(jax.jit, static_argnames=("precision",))
def apply_cols(A_cols, D, idx, precision: str = "highest") -> jax.Array:
    """S·A_cols (s × columns) for a block of columns A_cols (N × columns)."""
    n, s = A_cols.shape[0], idx.shape[0]
    with jax.default_matmul_precision("highest"):
        mixed = hadamard_columns(D[:, None] * _values(A_cols, precision))
        return (mixed * jnp.float32(n ** -0.5))[idx] * jnp.float32((n / s) ** 0.5)


def law_z_scores(D, idx, n: int, bins: int = 64) -> tuple:
    """(z of the signs' mean against 0, chi-square z of the sampled
    coordinates against uniform on [0, n) in ``bins`` equal bins)."""
    sign_z = abs(float(jnp.mean(D))) * D.shape[0] ** 0.5
    s = idx.shape[0]
    counts = np.bincount(np.asarray(idx, np.int64) * bins // n, minlength=bins)
    expected = s / bins
    chi2 = float(((counts - expected) ** 2).sum() / expected)
    return sign_z, abs(chi2 - (bins - 1)) / (2.0 * (bins - 1)) ** 0.5
