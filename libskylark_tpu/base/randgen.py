"""Counter-based lazy random streams in explicit Threefry ops.

TPU-native analog of the reference's ``random_samples_array_t``
(ref: base/randgen.hpp:17-193): a *virtual* array of i.i.d. samples in which
element ``i`` is a pure function of (key, i) — order-independent and
replicable on any device/shard, which is the property that makes sketch
application layout-independent and exactly testable ("sharded apply ==
single-device apply with the same seed", ref: tests/unit/DenseSketchApplyElementalTest.cpp:44-101).

Implementation: the stream is generated in fixed-size chunks. Chunk ``c`` of a
stream with allocation key ``k`` has chunk key ``fold_in(fold_in(k, c>>31), c&M)``;
its ``CHUNK`` uint32 draws are :func:`threefry.chunk_bits` of that key, and a
distribution is a pure map from draws to samples (``from_bits``) — so any
contiguous slice can be materialized by generating only its covering chunks,
on whichever device needs it (:func:`stream_slice`), and the elements at
arbitrary indices can be computed where they are needed with no table at all
(:func:`stream_at`). Every step is written in the explicit integer
ops of base/threefry.py, never through ``jax.random``'s samplers: the bits are
a function of this file, not of the installed JAX, and the Pallas kernels
replay the same ops in VMEM. The one exception is :class:`Gamma` (rejection
sampling has no fixed draw count); tests/test_stream_golden.py pins it with
the rest so a JAX upgrade that moves it fails loudly. The chunk size is an
internal constant: changing it — or anything above — changes the stream, so
it is part of the format (``SketchTransform.STREAM_FORMAT``).

Distributions mirror the reference's set (ref: utility/distributions.hpp):
normal, uniform real/int, Cauchy, Rademacher, standard Levy (= 1/Gamma(1/2, 2),
ref: utility/distributions.hpp:17-34), exponential.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import jax.random as jr
import numpy as np

from libskylark_tpu.base import threefry as tf

# Elements per generation block. Part of the stream format: changing it
# changes every stream's values.
CHUNK = 4096

_MASK31 = (1 << 31) - 1


@jax.jit
def _chunk_key_data(kd: jax.Array, hi, lo) -> jax.Array:
    return tf.fold_in(tf.fold_in(kd, hi), lo)


def chunk_key_data(kd: jax.Array, cid) -> jax.Array:
    """Raw key words of chunk ``cid`` (host int of any size, or traced
    int32 < 2^31) under the raw key words ``kd`` ((2,) uint32)."""
    if isinstance(cid, (int, np.integer)):
        hi, lo = int(cid) >> 31, int(cid) & _MASK31
    else:
        # Traced chunk ids are restricted to < 2^31 (hi word = 0).
        hi, lo = 0, cid
    return _chunk_key_data(kd, hi, lo)


def chunk_key(key: jax.Array, cid) -> jax.Array:
    """Key for chunk ``cid`` (:func:`chunk_key_data` around typed keys)."""
    return jr.wrap_key_data(chunk_key_data(jr.key_data(key), cid))


# ---------------------------------------------------------------------------
# Distributions
# ---------------------------------------------------------------------------


class Distribution:
    """A named, serializable sampler: a pure map from uint32 draws to
    samples. ``draws`` independent 32-bit words feed each sample."""

    name: str = "distribution"
    draws: int = 1

    def from_bits(self, *bits: jax.Array) -> jax.Array:
        """Map ``draws`` uint32 arrays -> samples (f32, or int32 for
        integer distributions). Shared by the chunk streams, the dense
        blocks and the kernels' in-VMEM replay."""
        raise NotImplementedError(f"{self.name} has no bit transform")

    def live_draws(self) -> tuple:
        """The draws ``from_bits`` depends on. A draw not named here
        cancels for this instance's parameters, so a caller that pays a
        cipher per element (:func:`stream_at`) may pass zeros in its
        place."""
        return tuple(range(self.draws))

    def to_dict(self) -> dict[str, Any]:
        d = dataclasses.asdict(self)  # type: ignore[call-overload]
        d["distribution"] = self.name
        return d

    @staticmethod
    def from_dict(d: dict[str, Any]) -> "Distribution":
        d = dict(d)
        cls = _DIST_REGISTRY[d.pop("distribution")]
        return cls(**d)


@dataclasses.dataclass(frozen=True)
class Normal(Distribution):
    mean: float = 0.0
    std: float = 1.0
    name = "normal"

    def from_bits(self, bits):
        return self.mean + self.std * tf.bits_to_normal(bits)


@dataclasses.dataclass(frozen=True)
class Uniform(Distribution):
    low: float = 0.0
    high: float = 1.0
    name = "uniform"

    def from_bits(self, bits):
        return tf.bits_to_uniform(bits, self.low, self.high)


@dataclasses.dataclass(frozen=True)
class UniformInt(Distribution):
    """Uniform integers in [low, high] inclusive (boost convention,
    ref: utility/distributions.hpp:84-100). Two draws per sample, so
    the modulo bias stays negligible at any span
    (:func:`threefry.bits_to_randint`)."""

    low: int = 0
    high: int = 1
    name = "uniform_int"
    draws = 2

    def from_bits(self, hi, lo):
        off = tf.bits_to_randint(hi, lo, self.high - self.low + 1)
        return self.low + off.astype(jnp.int32)

    def live_draws(self):
        # 2³² ≡ 0 mod every power-of-two span (≤ 2³²): the high draw cancels
        dead = tf.randint_multiplier(self.high - self.low + 1) == 0
        return (1,) if dead else (0, 1)


@dataclasses.dataclass(frozen=True)
class Cauchy(Distribution):
    loc: float = 0.0
    scale: float = 1.0
    name = "cauchy"

    def from_bits(self, bits):
        return self.loc + self.scale * tf.bits_to_cauchy(bits)


@dataclasses.dataclass(frozen=True)
class Rademacher(Distribution):
    name = "rademacher"

    def from_bits(self, bits):
        return tf.bits_to_rademacher(bits)


@dataclasses.dataclass(frozen=True)
class StandardLevy(Distribution):
    """Standard Levy: 1/Gamma(1/2, scale=2) == 1/Z^2, Z~N(0,1)
    (ref: utility/distributions.hpp:17-34)."""

    name = "standard_levy"

    def from_bits(self, bits):
        z = tf.bits_to_normal(bits)
        return 1.0 / jnp.maximum(z * z, jnp.finfo(jnp.float32).tiny)


@dataclasses.dataclass(frozen=True)
class Exponential(Distribution):
    rate: float = 1.0
    name = "exponential"

    def from_bits(self, bits):
        return tf.bits_to_exponential(bits) / self.rate


@dataclasses.dataclass(frozen=True)
class Gamma(Distribution):
    """Rejection-sampled, so not a fixed map from draws: the one
    distribution that goes through ``jax.random`` (module docstring)."""

    shape_param: float = 1.0
    scale: float = 1.0
    name = "gamma"
    draws = 0

    def sample(self, key, shape, dtype=jnp.float32):
        return self.scale * jr.gamma(key, self.shape_param, shape, dtype)


_DIST_REGISTRY = {
    cls.name: cls
    for cls in [
        Normal,
        Uniform,
        UniformInt,
        Cauchy,
        Rademacher,
        StandardLevy,
        Exponential,
        Gamma,
    ]
}


# ---------------------------------------------------------------------------
# Virtual streams
# ---------------------------------------------------------------------------


def _chunk_samples(kd: jax.Array, dist: Distribution, chunk: int,
                   dtype) -> jax.Array:
    """The ``chunk`` samples of the chunk whose key data is ``kd``.
    One-draw distributions read the chunk key's own draws; a
    distribution needing ``d`` draws reads those of ``fold_in(kd, i)``,
    i < d."""
    if not dist.draws:
        return dist.sample(jr.wrap_key_data(kd), (chunk,), dtype)
    if dist.draws == 1:
        words = (tf.chunk_bits(kd, chunk),)
    else:
        words = tuple(tf.chunk_bits(tf.fold_in(kd, i), chunk)
                      for i in range(dist.draws))
    return dist.from_bits(*words).astype(dtype)


@functools.partial(jax.jit, static_argnames=("dist", "chunk", "dtype"))
def _chunk_range(kd, hi, lo, *, dist: Distribution, chunk: int, dtype):
    """The chunks with id words ``(hi[i], lo[i])``, flattened. Jitted
    (distributions are frozen dataclasses, hence static): the cipher is
    ~100 integer ops, far too many to dispatch one by one per slice."""
    return jax.vmap(
        lambda h, l: _chunk_samples(_chunk_key_data(kd, h, l), dist, chunk,
                                    dtype))(hi, lo).reshape(-1)


def stream_slice(
    key: jax.Array,
    dist: Distribution,
    start: int,
    stop: int,
    dtype=jnp.float32,
    chunk: int = CHUNK,
) -> jax.Array:
    """Materialize elements [start, stop) of the virtual stream.

    ``start``/``stop`` are host-side ints (shard-local slice bounds are static
    under jit). Equivalent of indexing ``random_samples_array_t``
    (ref: base/randgen.hpp:98-115): the result does not depend on what other
    slices anyone else materializes.
    """
    dtype = jnp.dtype(dtype)
    if stop <= start:
        return jnp.zeros((0,), dtype)
    c0 = start // chunk
    c1 = -(-stop // chunk)
    cids = np.arange(c0, c1, dtype=np.int64)
    flat = _chunk_range(
        jr.key_data(key), (cids >> 31).astype(np.uint32),
        (cids & _MASK31).astype(np.uint32), dist=dist, chunk=chunk,
        dtype=dtype)
    return flat[start - c0 * chunk : stop - c0 * chunk]


def stream_at(key: jax.Array, dist: Distribution, idx,
              dtype=jnp.float32) -> jax.Array:
    """Elements ``idx`` of the virtual stream: bit-equal to
    ``stream_slice(key, dist, 0, n, dtype)[idx]`` for any ``n`` past the
    largest index, computed at each index and never read from a table.

    ``idx`` is an integer array of any shape (traced or not, non-negative;
    64-bit indices reach chunk ids past 2³¹). Each element re-derives what
    :func:`stream_slice` derives once a chunk — the chunk key, for a
    distribution of several draws the key of each draw, then the one
    cipher call that holds its position (:func:`threefry.chunk_bits`'
    layout) — so an element costs 2 cipher calls (one draw) or ``1 + 2 ×``
    live draws, whatever the stream's extent. On a TPU that is a fraction
    of a nanosecond where an element gather costs several. Fixed-draw
    distributions only.
    """
    if not dist.draws:
        raise ValueError(f"stream_at needs a fixed-draw distribution, got "
                         f"{dist.name}")
    idx = jnp.asarray(idx)
    if not jnp.issubdtype(idx.dtype, jnp.integer):
        raise TypeError(f"stream_at takes integer indices, got {idx.dtype}")
    half = CHUNK // 2
    cid = idx >> (CHUNK.bit_length() - 1)
    pos = (idx & (CHUNK - 1)).astype(jnp.uint32)
    j = pos & (half - 1)

    def fold(k0, k1, word):         # threefry.fold_in, for array keys
        return tf.threefry2x32(k0, k1, jnp.zeros_like(word), word)

    def draw(k0, k1):
        x0, x1 = tf.threefry2x32(k0, k1, j, j + half)
        return jnp.where(pos < half, x0, x1)

    # chunk_key's two folds: the high word is zero for 32-bit indices, and
    # its fold a scalar
    kd = jr.key_data(key)
    hi = (cid >> 31) if idx.dtype.itemsize > 4 else jnp.zeros((), jnp.uint32)
    ck = fold(*fold(kd[0], kd[1], hi), (cid & _MASK31).astype(jnp.uint32))
    if dist.draws == 1:
        words = (draw(*ck),)
    else:
        live = dist.live_draws()
        words = tuple(
            draw(*fold(*ck, jnp.full_like(pos, d))) if d in live
            else jnp.zeros_like(pos)
            for d in range(dist.draws))
    return dist.from_bits(*words).astype(dtype)


def stream_chunks(
    key: jax.Array,
    dist: Distribution,
    first_cid,
    n_chunks: int,
    dtype=jnp.float32,
    chunk: int = CHUNK,
) -> jax.Array:
    """Materialize ``n_chunks`` whole chunks starting at chunk id ``first_cid``.

    ``first_cid`` may be a traced int32 (for use inside lax loops over
    panels); ``n_chunks`` must be static. Returns shape (n_chunks * chunk,).
    """
    lo = (first_cid + jnp.arange(n_chunks, dtype=jnp.int32)).astype(
        jnp.uint32)
    return _chunk_range(jr.key_data(key), jnp.zeros_like(lo), lo, dist=dist,
                        chunk=chunk, dtype=jnp.dtype(dtype))


def dense_block(
    key: jax.Array,
    dist: Distribution,
    rows: int,
    block_id,
    block_cols: int,
    dtype=jnp.float32,
) -> jax.Array:
    """Column block ``block_id`` of a virtual i.i.d. (rows x n) matrix.

    Any shard can materialize any column panel without generating the rest —
    the TPU-native form of the reference's ``realize_matrix_view`` lazy-panel
    trick (ref: sketch/dense_transform_data.hpp:79-152). ``block_id`` may be
    traced.

    Block format: with (k0, k1) = key_data(chunk_key(key, b)),
    ``half = block_cols // 2`` and counter c[r, j] = r·half + j,
    Threefry-2x32-20 of (c, c + rows·half) yields two uint32 lanes; the
    block is ``[from_bits(lane0) | from_bits(lane1)]`` columns. Written in
    explicit integer ops (base/threefry.py) so the Pallas fused-apply kernel
    (sketch/pallas_dense.py) can reproduce the exact bits in-kernel.
    One-draw distributions and even ``block_cols`` only.
    """
    if dist.draws != 1 or block_cols % 2:
        raise ValueError(
            f"dense_block needs a one-draw distribution and an even block "
            f"width, got {dist.name} × {block_cols}")
    kd = jr.key_data(chunk_key(key, block_id))
    half = block_cols // 2
    c = (
        jnp.arange(rows, dtype=jnp.uint32)[:, None] * jnp.uint32(half)
        + jnp.arange(half, dtype=jnp.uint32)[None, :]
    )
    b0, b1 = tf.threefry2x32(kd[0], kd[1], c, c + jnp.uint32(rows * half))
    block = jnp.concatenate([dist.from_bits(b0), dist.from_bits(b1)], axis=1)
    return block.astype(dtype)


def dense_panel(
    key: jax.Array,
    dist: Distribution,
    rows: int,
    col_start: int,
    col_stop: int,
    block_cols: int,
    dtype=jnp.float32,
) -> jax.Array:
    """Materialize columns [col_start, col_stop) of the virtual (rows x n)
    matrix defined by :func:`dense_block`. Host-side static bounds."""
    b0 = col_start // block_cols
    b1 = -(-col_stop // block_cols)
    blocks = [
        dense_block(key, dist, rows, b, block_cols, dtype) for b in range(b0, b1)
    ]
    panel = blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=1)
    return panel[:, col_start - b0 * block_cols : col_stop - b0 * block_cols]


def dense_block_rows(
    key: jax.Array,
    dist: Distribution,
    rows: int,
    block_id,
    block_cols: int,
    dtype=jnp.float32,
    lanes: int | None = None,
) -> jax.Array:
    """:func:`dense_block` transposed, (block_cols × rows): row j is column j
    of the block, the same words under the same counters (c[j, r] = r·half +
    j), generated in this layout — the cipher and ``from_bits`` are
    elementwise, so nothing is transposed. For an operator whose columns
    are wanted as rows (the right factor of a transposed sparse product).
    ``lanes`` (a divisor of ``rows``) gives each row as (rows / lanes,
    lanes): with 128 a row of 1024 entries is one vector register, the view
    the sparse × dense kernel reads, generated with no relayout."""
    if dist.draws != 1 or block_cols % 2:
        raise ValueError(
            f"dense_block_rows needs a one-draw distribution and an even "
            f"block width, got {dist.name} × {block_cols}")
    kd = jr.key_data(chunk_key(key, block_id))
    half = block_cols // 2
    r = jnp.arange(rows, dtype=jnp.uint32)
    if lanes is not None:
        r = r.reshape(rows // lanes, lanes)
    j = jnp.arange(half, dtype=jnp.uint32).reshape((half,) + (1,) * r.ndim)
    c = r[None] * jnp.uint32(half) + j
    b0, b1 = tf.threefry2x32(kd[0], kd[1], c, c + jnp.uint32(rows * half))
    block = jnp.concatenate([dist.from_bits(b0), dist.from_bits(b1)], axis=0)
    return block.astype(dtype)
