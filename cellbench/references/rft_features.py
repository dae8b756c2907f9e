"""Plain reference of Gaussian random Fourier features (Rahimi & Recht),
rowwise: Z = √(2/s)·cos(X·Wᵀ/σ + b), with W (s × n) i.i.d. N(0, 1) and
b (s) i.i.d. U[0, 2π) rebuilt from (context seed, allocation counter) alone,
and the kernel they approximate, k(x, y) = exp(−‖x − y‖²/2σ²).

It follows the published definitions, not the program's code:

* an allocation's key is ``fold_in(key(seed), counter)`` of JAX's own
  Threefry generator (``libSkylark base/context.hpp``), and sub-stream ``t``
  of it is ``fold_in(key, t)``: 0 holds the frequencies W, 1 the shifts b
  (``libSkylark sketch/RFT_data.hpp:25-115``: the underlying dense transform,
  then ``_shifts``);
* W is laid out in column blocks of 256; block ``c`` has key
  ``fold_in(fold_in(W's key, 0), c)``, and with counters c[r, j] = r·128 + j
  the cipher Threefry-2x32-20 (Salmon et al., SC'11) of (c, c + s·128) gives
  two lanes of 32-bit words: lane 0 fills columns 0..127 of the block and
  lane 1 columns 128..255; a word becomes a standard normal by the inverse
  CDF (README "Stream format", format 3; ``references/dense_sketch.py``). A
  width that is no multiple of 256 keeps the first n columns: the last block
  is generated whole and cut;
* b is a chunk stream: chunks of 4096, chunk ``c`` under the key
  ``fold_in(fold_in(b's key, c >> 31), c & (2³¹ − 1))``, counters j < 2048,
  (j, j + 2048) → positions j and j + 2048; a word becomes 2π·u with u its
  top 24 bits / 2²⁴;
* the Gaussian kernel's map has inscale 1/σ and outscale √(2/s)
  (``RFT_data.hpp:117-145``).

Departures from upstream: upstream draws W and b from Random123 through
Boost's samplers in one counter range of the context, so the VALUES differ;
the laws (N(0, 1), U[0, 2π)) and the map are upstream's. Upstream applies
columnwise to a d × n matrix of examples as columns or rowwise to n × d;
only the rowwise form is reproduced here.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from cellbench.references.dense_sketch import (BLOCK_COLS, bits_to_normal,
                                               threefry2x32)

CHUNK = 4096
_MASK31 = (1 << 31) - 1
TWO_PI = 2.0 * math.pi


def _allocation(context_seed: int, counter: int):
    return jax.random.fold_in(jax.random.key(context_seed), counter)


@functools.partial(jax.jit, static_argnames=("s", "n"))
def frequencies(context_seed: int, counter: int, s: int, n: int) -> jax.Array:
    """The (s × n) float32 standard-normal W of allocation ``counter`` of a
    context seeded ``context_seed``; n need not be a multiple of 256."""
    half = BLOCK_COLS // 2
    base = jax.random.fold_in(
        jax.random.fold_in(_allocation(context_seed, counter), 0), 0)

    def block(b):
        kd = jax.random.key_data(jax.random.fold_in(base, b))
        c = (jnp.arange(s, dtype=jnp.uint32)[:, None] * jnp.uint32(half)
             + jnp.arange(half, dtype=jnp.uint32)[None, :])
        lane0, lane1 = threefry2x32(kd[0], kd[1], c, c + jnp.uint32(s * half))
        return jnp.concatenate(
            [bits_to_normal(lane0), bits_to_normal(lane1)], axis=1)

    n_blocks = -(-n // BLOCK_COLS)
    blocks = jax.vmap(block)(jnp.arange(n_blocks, dtype=jnp.uint32))
    return jnp.transpose(blocks, (1, 0, 2)).reshape(
        s, n_blocks * BLOCK_COLS)[:, :n]


@functools.partial(jax.jit, static_argnames="s")
def shifts(context_seed: int, counter: int, s: int) -> jax.Array:
    """The (s,) float32 shifts b ~ U[0, 2π)."""
    stream = jax.random.fold_in(_allocation(context_seed, counter), 1)
    half = CHUNK // 2
    j = jnp.arange(half, dtype=jnp.uint32)
    words = []
    for chunk_id in range(-(-s // CHUNK)):
        kd = jax.random.key_data(jax.random.fold_in(
            jax.random.fold_in(stream, chunk_id >> 31), chunk_id & _MASK31))
        lane0, lane1 = threefry2x32(kd[0], kd[1], j, j + jnp.uint32(half))
        words += [lane0, lane1]
    u = (jnp.concatenate(words)[:s] >> jnp.uint32(8)).astype(jnp.float32) \
        * jnp.float32(2.0 ** -24)
    return u * jnp.float32(TWO_PI)


def features(X_rows, W, b, sigma: float, precision: str = "highest") -> jax.Array:
    """√(2/s)·cos(X_rows·Wᵀ/σ + b). ``precision`` ``"highest"`` is the
    reference; ``"bf16"`` (the projection's operands rounded to bfloat16,
    float32 accumulation) is the control: the reference put in the program's
    place one precision below."""
    s = W.shape[0]
    Ws = W * jnp.float32(1.0 / sigma)
    if precision == "bf16":
        phase = jnp.dot(X_rows.astype(jnp.bfloat16), Ws.T.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    elif precision == "highest":
        phase = jnp.dot(X_rows, Ws.T, precision=jax.lax.Precision.HIGHEST)
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.float32(math.sqrt(2.0 / s)) * jnp.cos(phase + b[None, :])


def gaussian_kernel(X_rows, sigma: float) -> jax.Array:
    """k(x_i, x_j) = exp(−‖x_i − x_j‖²/2σ²) over the rows, from the
    differences themselves (no ‖x‖² + ‖y‖² − 2x·y cancellation)."""
    with jax.default_matmul_precision("highest"):
        diff = X_rows[:, None, :] - X_rows[None, :, :]
        return jnp.exp(-jnp.sum(diff * diff, axis=-1) / (2.0 * sigma * sigma))
