"""Threefry-2x32-20 counter PRNG, written in plain jnp integer ops.

This is the bit-level definition of the framework's stream formats — the
*dense block* panels and the *chunk* streams of base/randgen.py
(ref: base/randgen.hpp Random123 Threefry usage:98-115). It exists as
explicit ops — rather than calling ``jax.random`` — so the exact same
sequence of 32-bit adds/xors/rotations can run in three places with
identical bits, on any JAX release:

1. the XLA path (:func:`randgen.dense_block`, :func:`randgen.stream_slice`),
2. the Pallas TPU kernels that regenerate the streams in VMEM
   (sketch/pallas_dense.py, pallas_hash.py),
3. any host-side replay (integer ops are bitwise identical on every
   backend).

The algorithm is the public Threefry-2x32 with 20 rounds (5 groups of 4)
from Salmon et al., "Parallel random numbers: as easy as 1, 2, 3" (SC'11) —
the same cipher the reference's Random123 dependency implements.
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np

# rotation schedule for Threefry-2x32 (Salmon et al. Table 2)
_ROTATIONS = (13, 15, 26, 6, 17, 29, 16, 24)
_PARITY = 0x1BD11BDA

# NOTE: every numeric constant below is a weak-typed Python scalar on
# purpose — jnp.uint32(...)/jnp.float32(...) create array constants, which
# a Pallas kernel cannot capture. Weak scalars promote to the operand's
# dtype and trace cleanly both in XLA and inside kernels.


def _same(x):
    return x


def _rotl(x, r: int, wrap=_same):
    return wrap(x << r) | (x >> (32 - r))


def _cipher(k0, k1, x0, x1, wrap=_same):
    """The 20 rounds on counter words (x0, x1) under key (k0, k1): the one
    definition of the schedule, for uint32 arrays (traced or not — they
    wrap by themselves, ``wrap`` stays the identity and adds no op) and,
    with ``wrap`` = :func:`_wrap32`, for plain Python ints on the host."""
    ks2 = k0 ^ k1 ^ _PARITY
    x0 = wrap(x0 + k0)
    x1 = wrap(x1 + k1)
    keys = (k0, k1, ks2)
    for group in range(5):
        r0, r1, r2, r3 = _ROTATIONS[:4] if group % 2 == 0 else _ROTATIONS[4:]
        for r in (r0, r1, r2, r3):
            x0 = wrap(x0 + x1)
            x1 = _rotl(x1, r, wrap) ^ x0
        # key injection after each 4-round group
        x0 = wrap(x0 + keys[(group + 1) % 3])
        x1 = wrap(x1 + keys[(group + 2) % 3] + (group + 1))
    return x0, x1


def threefry2x32(k0, k1, c0: jnp.ndarray, c1: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Encrypt counter words (c0, c1) under key (k0, k1).

    ``c0``/``c1`` are uint32 arrays; ``k0``/``k1`` are uint32 scalars
    (python ints, numpy scalars, or traced values — e.g. SMEM reads inside
    a Pallas kernel). Returns two uint32 arrays of c0's shape — 64 random
    bits per counter."""
    return _cipher(k0, k1, c0.astype(jnp.uint32), c1.astype(jnp.uint32))


def fold_in(kd: jnp.ndarray, data) -> jnp.ndarray:
    """Key data derived from ``kd`` ((2,) uint32) and one 32-bit word:
    the cipher of counter (0, data) under ``kd``. Bit-equal to
    ``jax.random.fold_in`` on threefry keys (tests/test_stream_golden.py), but
    spelled out so no stream key can move with a JAX release."""
    d = jnp.asarray(data).astype(jnp.uint32)
    x0, x1 = threefry2x32(kd[0], kd[1], jnp.zeros_like(d), d)
    return jnp.stack([x0, x1], axis=-1)


# -- the host replay: the same cipher on Python ints, no device involved --

_MASK32 = 0xFFFFFFFF


def _wrap32(x: int) -> int:
    return x & _MASK32


def seed_words(seed: int) -> Tuple[int, int]:
    """Key words of the root key of ``seed``: (0, low 32 bits of the seed's
    two's complement), for every seed a 64-bit signed integer holds —
    ``jax.random.key(seed)`` under JAX's default 32-bit mode, which drops
    the high word (2**32 + 5 seeds like 5, -1 like 2**32 - 1). A seed
    outside 64 signed bits is an OverflowError, as it is there. Serialized
    transforms store the seed, so the rule is part of the stream format."""
    return 0, int(np.int64(int(seed))) & _MASK32


def fold_in_words(kd: Tuple[int, int], data: int) -> Tuple[int, int]:
    """:func:`fold_in` on the host: ``kd`` and the result are pairs of
    Python ints. ``data`` outside [0, 2**32) is an OverflowError, as it is
    for ``jax.random.fold_in``."""
    data = int(data)
    if not 0 <= data <= _MASK32:
        raise OverflowError(
            f"Python integer {data} out of bounds for uint32")
    return _cipher(kd[0], kd[1], 0, data, _wrap32)


def chunk_bits(kd: jnp.ndarray, n: int) -> jnp.ndarray:
    """The ``n`` (even) uint32 draws of one stream chunk under chunk key
    ``kd``: counter pairs (j, j + n/2), position j on the cipher's first
    output lane and position j + n/2 on the second — two draws per
    cipher call. The kernels replay exactly this layout
    (``pallas_hash._chunk_bits``)."""
    half = n // 2
    c = jnp.arange(half, dtype=jnp.uint32)
    x0, x1 = threefry2x32(kd[0], kd[1], c, c + half)
    return jnp.concatenate([x0, x1])


def bits_to_unit(bits: jnp.ndarray) -> jnp.ndarray:
    """uint32 bits → f32 uniform in [0, 1) with 24-bit resolution.

    The top 24 bits are bitcast to int32 before the float cast — the value
    fits, and Mosaic (Pallas TPU) has no uint32→f32 cast."""
    import jax

    top = jax.lax.bitcast_convert_type(bits >> 8, jnp.int32)
    return top.astype(jnp.float32) * (2.0**-24)


def bits_to_normal(bits: jnp.ndarray) -> jnp.ndarray:
    """uint32 bits → f32 standard normal via inverse-CDF.

    z = √2·erfinv(2u−1) with u clamped away from {0,1}. The integer→(−1,1)
    mapping is bit-exact everywhere; erfinv itself is backend-dependent at
    the ~1e-5 level (the framework's accepted cross-backend drift — the
    reference's oracle tolerance is 1e-4)."""
    import jax

    u = bits_to_unit(bits)
    v = jnp.clip(2.0 * u - 1.0, -1.0 + 2.0**-23, 1.0 - 2.0**-23)
    return 1.4142135623730951 * jax.lax.erf_inv(v)


def bits_to_cauchy(bits: jnp.ndarray) -> jnp.ndarray:
    """uint32 bits → f32 standard Cauchy: tan(π(u−1/2)), u clamped."""
    u = bits_to_unit(bits)
    v = jnp.clip(u, 2.0**-24, 1.0 - 2.0**-24)
    return jnp.tan(3.141592653589793 * (v - 0.5))


def bits_to_rademacher(bits: jnp.ndarray) -> jnp.ndarray:
    """uint32 bits → ±1 from the top bit."""
    return jnp.where((bits >> 31) == 0, 1.0, -1.0).astype(jnp.float32)


def bits_to_uniform(bits: jnp.ndarray, low: float, high: float) -> jnp.ndarray:
    return low + bits_to_unit(bits) * (high - low)


def bits_to_exponential(bits: jnp.ndarray) -> jnp.ndarray:
    """uint32 bits → f32 standard exponential: −log(1−u), u ∈ [0, 1)."""
    return -jnp.log1p(-bits_to_unit(bits))


def randint_multiplier(span: int) -> int:
    """2³² mod ``span`` as (2¹⁶ mod span)² mod span — static Python math.
    Zero for every power-of-two span up to 2³² (2³² mod 2²⁰ = 0: the
    square of 2¹⁶ is reduced again), where the high draw of
    :func:`bits_to_randint` cancels and a kernel can skip its cipher."""
    m = (1 << 16) % span
    return (m * m) % span


def bits_to_randint(hi: jnp.ndarray, lo: jnp.ndarray, span: int) -> jnp.ndarray:
    """Two uint32 draws → uint32 in [0, span): the 64-bit word hi·2³² + lo
    reduced mod ``span`` in wrapping uint32 arithmetic, so the modulo
    bias is ~span/2⁶⁴ instead of span/2³²."""
    sp = np.uint32(span)
    mult = np.uint32(randint_multiplier(span))
    return ((hi % sp) * mult + lo % sp) % sp
