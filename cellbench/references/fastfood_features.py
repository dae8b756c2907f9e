"""Plain reference of Fastfood features for the Gaussian kernel (Le, Sarlós,
Smola, "Fastfood — Approximating Kernel Expansions in Loglinear Time", ICML
2013), rowwise: Z = √(2/s)·cos(X̃·Vᵀ + b) with X̃ the input zero-padded from
n to NB = 2^⌈log₂n⌉ columns and V (numblks·NB × NB, cut to its first s rows)
the stacked blocks

    V_k = Sm · H · diag(g_k) · Π_k · H · diag(b_k),

H the NB × NB Sylvester Hadamard matrix (natural ordering, entries ±1,
unnormalized), b_k Rademacher signs, Π_k a uniform permutation ((Π_k v)[i] =
v[π_k(i)]), g_k i.i.d. N(0, 1), Sm = 1/(σ√NB), and b (s) i.i.d. U[0, 2π) —
and the kernel the map approximates, k(x, y) = exp(−‖x − y‖²/2σ²).

It follows the published definitions, not the program's code: H is a dense
float32 matrix applied to the sampled rows with ``highest`` products, a block
at a time so that it fits; everything random is rebuilt from (context seed,
allocation counter) alone by JAX's own generator:

* an allocation's key is ``fold_in(key(seed), counter)`` of JAX's Threefry
  generator, and sub-stream ``t`` of it is ``fold_in(key, t)``: 0 holds the
  shifts b, 1 the signs b_k, 2 the Gaussians g_k, 3 the permutations (4:
  Matern's Sm, not used here) — libSkylark ``sketch/FRFT_data.hpp:26-139``
  builds them in this order;
* a stream is laid out in chunks of 4096; chunk ``c`` has key
  ``fold_in(fold_in(stream key, c >> 31), c & (2³¹ − 1))``, and with counters
  j < 2048 the cipher Threefry-2x32-20 of (j, j + 2048) gives two lanes of
  32-bit words: lane 0 fills positions 0..2047 of the chunk and lane 1
  positions 2048..4095 (README "Stream format", format 3;
  ``references/sparse_hash.py`` spells the layout out). Block k of a stream
  holds positions [k·NB, (k + 1)·NB);
* a Rademacher sign is +1 where the word's top bit is 0, else −1; a standard
  normal is the inverse CDF of the word's top 24 bits
  (``references/dense_sketch.py``); a shift is 2π·u, u the top 24 bits / 2²⁴;
* permutation k is ``jax.random.permutation(fold_in(stream 3's key, k), NB)``.

Departures, each stated where it is made:

* from the paper: the paper scales row i of a block by s_i/‖g‖ with s_i
  chi-distributed lengths, so that the rows' norms follow the Gaussian's own;
  upstream (``FRFT_data.hpp:196-203``) uses the constant Sm = 1/(σ√N) — that
  is upstream's, and the reference's, not ours;
* from upstream: upstream's FFTW build runs the DCT on N itself; its
  SpiralWHT build, this reference and the configuration pad to a power of
  two and normalise Sm by NB, not N (with NB in place of N the pre-Sm
  variance is NB·‖x‖²); upstream records the permutation as Fisher–Yates
  swaps drawn from Random123 — here a uniform permutation of the same law;
  upstream draws every stream from one counter range of the context through
  Boost's samplers, so the VALUES differ and the laws are upstream's.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from cellbench.references.dense_sketch import bits_to_normal
from cellbench.references.rft_features import TWO_PI, gaussian_kernel  # noqa: F401
from cellbench.references.sparse_hash import _chunk_key, _chunk_words

CHUNK = 4096


def geometry(n: int, s: int) -> tuple:
    """(NB, numblks): the padded block length and the number of blocks."""
    NB = 1 << max(0, (n - 1).bit_length())
    return NB, -(-s // NB)


def _words(stream_key, count: int):
    return jnp.concatenate([_chunk_words(_chunk_key(stream_key, c))
                            for c in range(-(-count // CHUNK))])[:count]


def streams(context_seed: int, counter: int, n: int, s: int) -> dict:
    """The map's random parts for allocation ``counter`` of a context seeded
    ``context_seed``: ``B`` (numblks, NB) ±1, ``G`` (numblks, NB) standard
    normals, ``perms`` (numblks, NB) int32, ``shifts`` (s,) in [0, 2π)."""
    NB, nb = geometry(n, s)
    alloc = jax.random.fold_in(jax.random.key(context_seed), counter)
    sub = functools.partial(jax.random.fold_in, alloc)
    B = jnp.where((_words(sub(1), nb * NB) >> jnp.uint32(31)) == 0, 1.0, -1.0)
    G = bits_to_normal(_words(sub(2), nb * NB))
    perms = jnp.stack([jax.random.permutation(jax.random.fold_in(sub(3), k), NB)
                       for k in range(nb)])
    u = (_words(sub(0), s) >> jnp.uint32(8)).astype(jnp.float32) \
        * jnp.float32(2.0 ** -24)
    return {"B": B.astype(jnp.float32).reshape(nb, NB),
            "G": G.astype(jnp.float32).reshape(nb, NB),
            "perms": perms.astype(jnp.int32),
            "shifts": u * jnp.float32(TWO_PI)}


@functools.lru_cache(maxsize=2)
def hadamard(NB: int) -> np.ndarray:
    """The dense NB × NB Sylvester Hadamard matrix, H[i, j] =
    (−1)^popcount(i & j), float32."""
    if NB & (NB - 1):
        raise ValueError(f"the Hadamard matrix needs a power of two, got {NB}")
    i = np.arange(NB, dtype=np.uint32)
    bits = i[:, None] & i[None, :]
    parity = np.zeros_like(bits)
    while bits.any():
        parity ^= bits & 1
        bits >>= 1
    return (1.0 - 2.0 * parity).astype(np.float32)


def _times_h(W, H, precision: str):
    if precision == "highest":
        return jnp.dot(W, H, precision=jax.lax.Precision.HIGHEST)
    if precision == "bf16":
        # the operand in one bfloat16 part (H's ±1 are exact in it), float32
        # sums; reduce_precision, not a cast and back: inside a compiled
        # function XLA may keep the excess precision of a convert pair
        low = jax.lax.reduce_precision(W, exponent_bits=8, mantissa_bits=7)
        return jnp.dot(low, H, precision=jax.lax.Precision.HIGHEST)
    raise ValueError(f"unknown precision {precision!r}")


@functools.partial(jax.jit, static_argnames=("s", "precision"))
def _features(X_rows, H, B, G, perms, shifts, sm, *, s: int, precision: str):
    NB = H.shape[0]
    Xp = jnp.pad(X_rows, ((0, 0), (0, NB - X_rows.shape[1])))

    def block(_, parts):
        b, g, perm = parts
        W = _times_h(Xp * b[None, :], H, precision)         # H symmetric
        W = jnp.take(W, perm, axis=1) * g[None, :]
        return None, sm * _times_h(W, H, precision)

    _, phases = jax.lax.scan(block, None, (B, G, perms))    # one block live
    phase = jnp.moveaxis(phases, 0, 1).reshape(X_rows.shape[0], -1)[:, :s]
    return jnp.float32(math.sqrt(2.0 / s)) * jnp.cos(phase + shifts[None, :])


def features(X_rows, parts: dict, sigma: float,
             precision: str = "highest") -> jax.Array:
    """√(2/s)·cos(X̃·Vᵀ + b) of the rows ``X_rows`` (r × n). ``precision``
    ``"highest"`` is the reference; ``"bf16"`` (the operand of each Hadamard
    product rounded to bfloat16, float32 accumulation) is the control: the
    reference put in the program's place one precision below."""
    nb, NB = parts["B"].shape
    return _features(X_rows, jnp.asarray(hadamard(NB)), parts["B"], parts["G"],
                     parts["perms"], parts["shifts"],
                     jnp.float32(1.0 / (sigma * math.sqrt(NB))),
                     s=parts["shifts"].shape[0], precision=precision)


def explicit_v(parts: dict, sigma: float, n: int) -> np.ndarray:
    """The (s × n) float64 frequency matrix V itself, block by block from
    its factors (small widths only: the tests')."""
    nb, NB = parts["B"].shape
    H = hadamard(NB).astype(np.float64)
    blocks = []
    for b, g, perm in zip(np.asarray(parts["B"], np.float64),
                          np.asarray(parts["G"], np.float64),
                          np.asarray(parts["perms"])):
        P = np.zeros((NB, NB))
        P[np.arange(NB), perm] = 1.0                        # (P v)[i] = v[π(i)]
        blocks.append(H @ np.diag(g) @ P @ H @ np.diag(b))
    V = np.concatenate(blocks) / (sigma * math.sqrt(NB))
    return V[:parts["shifts"].shape[0], :n]


def law_z_scores(B, G, shifts, perms, bins: int) -> dict:
    """How far streams are from the laws the configuration states: ``B``
    fair ±1 (z of the mean), ``G`` N(0, 1) (z of the mean and of the
    variance), ``shifts`` U[0, 2π) (z of a chi-square over ``bins`` bins)
    and, exactly, how many entries of ``perms`` keep their row from being a
    permutation of 0..NB−1 (0 for a sound one)."""
    B, G = np.asarray(B, np.float64).ravel(), np.asarray(G, np.float64).ravel()
    hist = np.bincount(np.minimum(
        (np.asarray(shifts, np.float64) * (bins / TWO_PI)).astype(np.int64),
        bins - 1), minlength=bins)
    expected = hist.sum() / bins
    chi2 = float(((hist - expected) ** 2).sum() / expected)
    perms = np.asarray(perms)
    NB = perms.shape[1]
    defect = sum(int(NB - np.unique(row[(row >= 0) & (row < NB)]).size)
                 for row in perms)
    return {"sign_mean_z": abs(B.mean()) * B.size ** 0.5,
            "gauss_mean_z": abs(G.mean()) * G.size ** 0.5,
            "gauss_var_z": abs(G.var() - 1.0) * (G.size / 2.0) ** 0.5,
            "shift_chi2_z": abs(chi2 - (bins - 1)) / (2.0 * (bins - 1)) ** 0.5,
            "perm_defect": float(defect)}
