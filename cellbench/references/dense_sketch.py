"""Plain reference of the dense sketch transform (JLT): the operator S
materialised from (context seed, allocation counter) alone, and A_rows · Sᵀ.

It follows the published definitions, not the program's code:

* an allocation's key is ``fold_in(key(seed), counter)`` of JAX's own
  Threefry generator (``libSkylark base/context.hpp``: a context hands out
  counter ranges of one Threefry stream);
* the virtual (s × n) operator is laid out in column blocks of 256; block
  ``b`` has key ``fold_in(fold_in(key, 0), b)``, and with counters
  c[r, j] = r·128 + j the cipher Threefry-2x32-20 (Salmon et al., SC'11)
  of (c, c + s·128) gives two lanes of 32-bit words: lane 0 fills columns
  0..127 of the block and lane 1 columns 128..255 (README "Stream format",
  format 3);
* a word becomes a standard normal by the inverse CDF, z = √2·erfinv(2u − 1)
  with u its top 24 bits / 2²⁴, clamped one ulp inside (−1, 1);
* JLT scales by √(1/s) (``libSkylark sketch/JLT_data.hpp``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BLOCK_COLS = 256
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


def operator(context_seed: int, counter: int, s: int, n: int) -> jax.Array:
    """The (s × n) float32 JLT operator of allocation ``counter`` of a
    context seeded ``context_seed``."""
    if n % BLOCK_COLS:
        raise ValueError(f"n must be a multiple of {BLOCK_COLS}, got {n}")
    half = BLOCK_COLS // 2
    alloc = jax.random.fold_in(jax.random.key(context_seed), counter)
    base = jax.random.fold_in(alloc, 0)  # high word of the block id

    def block(b):
        kd = jax.random.key_data(jax.random.fold_in(base, b))
        c = (jnp.arange(s, dtype=jnp.uint32)[:, None] * jnp.uint32(half)
             + jnp.arange(half, dtype=jnp.uint32)[None, :])
        lane0, lane1 = threefry2x32(kd[0], kd[1], c, c + jnp.uint32(s * half))
        return jnp.concatenate(
            [bits_to_normal(lane0), bits_to_normal(lane1)], axis=1)

    blocks = jax.vmap(block)(jnp.arange(n // BLOCK_COLS, dtype=jnp.uint32))
    S = jnp.transpose(blocks, (1, 0, 2)).reshape(s, n)
    return S * jnp.float32((1.0 / s) ** 0.5)


def apply_rows(A_rows, S, precision: str = "highest") -> jax.Array:
    """A_rows · Sᵀ. ``precision`` ``"highest"`` is the reference; ``"bf16"``
    (operands rounded to bfloat16, float32 accumulation) is the control: the
    reference put in the program's place one precision below."""
    if precision == "bf16":
        return jnp.dot(A_rows.astype(jnp.bfloat16), S.T.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    if precision != "highest":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.dot(A_rows, S.T, precision=jax.lax.Precision.HIGHEST)
