"""Pallas TPU kernel: panel-free fused SRHT (sign → FWHT → sample).

The FJLT/``wht`` family's serve path contracts operands through the
XLA twin :func:`libskylark_tpu.sketch.fut.fwht_sketch` — a diag
multiply, a Walsh-Hadamard transform, and a row gather, three separate
HLOs with the full (m, n) mixed intermediate written back between
them. This kernel fuses the whole program into one pallas_call so the
intermediate never leaves VMEM:

1. **In-kernel stream generation.** The Rademacher sign diagonal
   (sub-stream 0) and the sampled coordinates (sub-stream 1) are
   regenerated inside the kernel from the transform's raw Threefry
   key, replicating ``randgen.stream_slice``'s chunk format exactly —
   the same discipline as ``pallas_hash`` (per-chunk derived keys in a
   tiny SMEM table, the wide ciphers in VMEM per grid step), so the
   kernel's streams are **bit-identical** to the XLA path's.
   ``UniformInt``'s double-draw multiplier is zero for every
   power-of-two span (:func:`threefry.randint_multiplier`), and
   the FWHT length is a power of two by construction, so the
   coordinate stream needs only the low cipher.

2. **In-kernel butterfly.** The n-point transform factors as
   H_n = (H_{n/128} ⊗ I_128) · (I_{n/128} ⊗ H_128): the inner factor
   is one MXU contraction of each 128-lane block against H_128 (built
   in-register from an iota-parity identity — no large constants baked
   into the program), the outer factor is log2(n/128) lane-aligned
   butterfly stages whose minor dimension stays 128. The sign diagonal
   is folded into the first stage's operand load; the ``1/sqrt(n)``
   scale multiplies the diagonal first (the twin's op order).

3. **Fused sample gather.** The s sampled rows come out of the last
   stage as a fori_loop of 128-wide signed-one-hot MXU dots — each
   output coordinate meets exactly one nonzero across the loop, and
   ``x + 0.0`` / ``0.0 · x`` are exact for finite x, so the dot
   sequence is bit-equal to a true gather.

Both stream generation and the butterfly are exact-arithmetic
programs, so on dyadic data (integer-valued f32 operands, n and s
even powers of two) the kernel is **bit-equal** to the XLA twin and
to the ``FJLT.operator_panel`` matmul oracle; on general floats the
summation order differs from the kron-matmul lowering and agreement
is allclose (tests/test_fwht.py pins both regimes in interpret mode).

Like every kernel in this tree, dispatch DECLINES (``qualify``
explains why) rather than failing: off-TPU callers keep the XLA twin.
On a TPU v5e (jax 0.9.0) the Pallas TPU lowering rejects this kernel —
``dynamic_slice`` is unimplemented there (PERF.md) — so it is reachable
only through an explicit override (``SKYLARK_FWHT_KERNEL``) or a
plan-cache entry; the serve layer then counts the rejection
(``mosaic-reject``) and serves the XLA program.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from libskylark_tpu.base import threefry as tf
from libskylark_tpu.sketch.pallas_dense import (_VMEM_BUDGET_BYTES,
                                                available,
                                                compiler_params)
from libskylark_tpu.sketch.pallas_hash import (CHUNK, _GEN_COLS, _HALF,
                                               _hot_dot, _mod_span)

# Default rows-per-grid-step of the free (m) axis; shrunk (never
# failed) against the VMEM budget like pallas_hash's m-tile.
_DEFAULT_M_TILE = 256

# The coordinate stream must fit one cipher sweep (positions 0.._HALF-1
# of chunk 0 ride the low Threefry lane alone) — comfortably above any
# serve-realistic SRHT sketch dimension.
_MAX_S_DIM = _HALF


# ---------------------------------------------------------------------------
# stream replication: host/XLA side (tiny per-chunk key table)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames="n_chunks")
def fwht_key_table(key, n_chunks: int) -> jnp.ndarray:
    """(n_chunks, 6) uint32 table of the derived keys the kernel needs:
    cols 0:2 the sign stream's chunk key (sub-stream 0, ``Rademacher``
    — used directly, like ``pallas_hash``'s value stream), cols 2:4 /
    4:6 the coordinate stream's low / high draw keys (sub-stream 1,
    ``UniformInt``, chunk 0 — one chunk covers the whole sample vector;
    the high key in 4:6 rides along unused because the span is a power
    of two). Exactly the keys ``randgen.stream_slice`` derives (see
    ``pallas_hash.chunk_key_table``). Traced and vmappable — the serve
    executable computes the whole cohort's tables inline."""
    import jax.random as jr

    kd = jr.key_data(key)
    dkey = tf.fold_in(tf.fold_in(kd, 0), 0)
    ick = tf.fold_in(tf.fold_in(tf.fold_in(kd, 1), 0), 0)
    tail = jnp.concatenate([tf.fold_in(ick, 1), tf.fold_in(ick, 0)])

    def one(c):
        return jnp.concatenate([tf.fold_in(dkey, c), tail])

    return jax.vmap(one)(jnp.arange(n_chunks, dtype=jnp.int32))


# ---------------------------------------------------------------------------
# in-kernel generation
# ---------------------------------------------------------------------------


def _row_bits(k0, k1, length: int):
    """uint32 draws for the leading ``length`` positions of one chunk,
    laid out (1, length): the same counter pairs (j, j + _HALF) as
    ``pallas_hash._chunk_bits`` — the cipher is elementwise in the
    counters, so the flat row layout carries identical values — kept
    as a single lane row because the consumer broadcasts against
    minor-axis-n operand tiles."""
    cw = min(length, _HALF)
    c = jax.lax.broadcasted_iota(jnp.uint32, (1, cw), 1)
    x0, x1 = tf.threefry2x32(k0, k1, c, c + _HALF)
    if length > _HALF:
        return jnp.concatenate([x0, x1], axis=1)
    return x0


def _gen_diag(keys_ref, base, n: int, n_chunks: int):
    """(1, n) ±1 f32 sign diagonal: sub-stream 0's leading n draws,
    bit-identical to ``FJLT.diagonal()``'s ``stream_slice``."""
    parts = []
    for c in range(n_chunks):
        parts.append(_row_bits(keys_ref[base + c, 0],
                               keys_ref[base + c, 1], min(n, CHUNK)))
    bits = parts[0] if n_chunks == 1 else jnp.concatenate(parts, axis=1)
    return tf.bits_to_rademacher(bits)


def _gen_idx(keys_ref, base, n: int, s_pad: int):
    """(1, s_pad) int32 sampled coordinates: sub-stream 1's leading
    draws through ``bits_to_randint``'s modular map. The power-of-two span
    kills the double-draw multiplier, so only the low cipher runs;
    positions past the true s_dim carry real stream values that gather
    real rows — the wrapper slices them off."""
    lo = _row_bits(keys_ref[base, 2], keys_ref[base, 3], s_pad)
    return _mod_span(lo, n).astype(jnp.int32)


def _h128():
    """H_128 (Sylvester natural ordering) in-register: the entry at
    (i, j) is (−1)^popcount(i & j), a five-shift xor parity fold —
    cheaper than baking a 64 KiB constant into every program."""
    i = jax.lax.broadcasted_iota(jnp.int32, (_GEN_COLS, _GEN_COLS), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (_GEN_COLS, _GEN_COLS), 1)
    x = i & j
    for shift in (16, 8, 4, 2, 1):
        x = x ^ (x >> shift)
    return (1 - 2 * (x & 1)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------


def _kernel(s_pad, n, n_chunks, m_tile, fut_scale, samp_scale,
            keys_ref, a_ref, out_ref):
    """One (batch lane, m-tile) grid step: out[b] (m_tile, s_pad) =
    samp_scale · gather(FWHT_n((fut_scale · D) ⊙ a[b]), idx) with the
    transform along the minor axis. Grid (B, m_tiles), both parallel —
    every step owns its whole output block."""
    b = pl.program_id(0)
    base = b * n_chunks
    D = _gen_diag(keys_ref, base, n, n_chunks)
    idx = _gen_idx(keys_ref, base, n, s_pad)

    # sign + 1/sqrt(n) fused into the load, the twin's op order:
    # (fut_scale * diag) * A
    W = (fut_scale * D) * a_ref[0]

    # H_n = (H_K ⊗ I_128)(I_K ⊗ H_128): inner factor as one MXU
    # contraction per 128-lane block...
    K = n // _GEN_COLS
    W = W.reshape(m_tile, K, _GEN_COLS)
    W = _hot_dot(W, _h128(), (((2,), (0,)), ((), ())))
    # ...outer factor as log2(K) butterfly stages over the block
    # index; the minor dimension stays 128 throughout.
    g = 1
    while g < K:
        Wr = W.reshape(m_tile, K // (2 * g), 2, g, _GEN_COLS)
        hi, lo = Wr[:, :, 0], Wr[:, :, 1]
        W = jnp.concatenate([hi + lo, hi - lo], axis=2).reshape(
            m_tile, K, _GEN_COLS)
        g *= 2

    # fused sample gather: 128 source rows per one-hot MXU dot; each
    # output coordinate meets exactly one nonzero across the loop, so
    # the accumulation is bit-equal to a true gather on finite data.
    iota_l = jax.lax.broadcasted_iota(jnp.int32, (_GEN_COLS, s_pad), 0)

    def body(c, acc):
        wc = jax.lax.dynamic_slice(
            W, (0, c, 0), (m_tile, 1, _GEN_COLS)
        ).reshape(m_tile, _GEN_COLS)
        onehot = ((iota_l + c * _GEN_COLS) == idx).astype(jnp.float32)
        return acc + _hot_dot(wc, onehot, (((1,), (0,)), ((), ())))

    acc = jax.lax.fori_loop(
        0, K, body, jnp.zeros((m_tile, s_pad), jnp.float32))
    out_ref[:] = (samp_scale * acc)[None]


# ---------------------------------------------------------------------------
# planning + launch
# ---------------------------------------------------------------------------


def _pad_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _vmem_estimate(m_tile: int, n: int, s_pad: int) -> int:
    """Per-grid-step VMEM plan: double-buffered input tile, the
    working transform array plus one stage temporary, double-buffered
    output block plus the gather accumulator, the one-hot and H_128
    tiles, and the generated sign/coordinate rows with their cipher
    temporaries."""
    return 4 * (
        2 * m_tile * n
        + 2 * m_tile * n
        + 3 * m_tile * s_pad
        + _GEN_COLS * s_pad
        + _GEN_COLS * _GEN_COLS
        + 6 * n
        + 4 * s_pad
    )


def plan_tiles(n: int, m: int, s_dim: int,
               m_tile: Optional[int] = None) -> Optional[tuple]:
    """(m_pad, m_tile, s_pad) under the VMEM budget, or None when even
    the minimum tile doesn't fit — shrink-don't-fail, the same
    discipline as ``pallas_hash.plan_tiles``. The transform axis is
    NEVER padded: the FWHT length defines the operator."""
    s_pad = _pad_to(s_dim, _GEN_COLS)
    mt = m_tile or _DEFAULT_M_TILE
    mt = max(8, 1 << (max(int(mt), 8).bit_length() - 1))
    while mt > 8 and _vmem_estimate(mt, n, s_pad) > _VMEM_BUDGET_BYTES:
        mt //= 2
    if _vmem_estimate(mt, n, s_pad) > _VMEM_BUDGET_BYTES:
        return None
    m_pad = _pad_to(max(m, 8), mt)
    mt = min(mt, m_pad)
    while m_pad % mt:
        mt //= 2
    return m_pad, mt, s_pad


def qualify(s_dim: int, n: int, m: int, dtype,
            interpret: bool = False) -> tuple[bool, str]:
    """Host-side qualification: (ok, reason). The serve layer counts
    declined reasons (``serve.kernel_declined``) so operators can see
    WHY a replica is not on the fast path."""
    if not interpret and not available():
        return False, "backend is not a TPU (interpret-mode only here)"
    if jnp.dtype(dtype) != jnp.float32:
        return False, f"dtype {jnp.dtype(dtype).name} != float32"
    if s_dim < 1 or n < 1 or m < 1:
        return False, "degenerate shape"
    if n & (n - 1):
        return False, f"transform length {n} is not a power of two"
    if n < _GEN_COLS:
        return False, f"transform length {n} below one lane block"
    if s_dim > _MAX_S_DIM:
        return False, (f"s_dim {s_dim} exceeds one cipher sweep "
                       f"({_MAX_S_DIM})")
    if plan_tiles(n, m, s_dim) is None:
        return False, "no tile fits the VMEM budget"
    return True, "ok"


@functools.partial(
    jax.jit, static_argnames=("s_dim", "s_pad", "m_tile", "interpret"))
def _fwht_call(A, keys, *, s_dim, s_pad, m_tile, interpret):
    """One pallas_call over the stacked, rowwise-natural (B, m, n)
    operand (already padded along m). ``keys`` is the flattened
    (B * n_chunks, 6) key table."""
    B, m, n = A.shape
    n_chunks = max(1, n // CHUNK)
    fut_scale = 1.0 / math.sqrt(n)
    samp_scale = math.sqrt(n / s_dim)
    kern = functools.partial(_kernel, s_pad, n, n_chunks, m_tile,
                             fut_scale, samp_scale)
    params = compiler_params("parallel", "parallel")
    return pl.pallas_call(
        kern,
        grid=(B, m // m_tile),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # whole key table
            pl.BlockSpec((1, m_tile, n), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, m_tile, s_pad),
                               lambda b, i: (b, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, m, s_pad), jnp.float32),
        compiler_params=params,
        interpret=interpret,
    )(keys, A)


def srht_apply_batched(key_data, A, *, s_dim: int, rowwise: bool,
                       m_tile: Optional[int] = None,
                       interpret: bool = False) -> jnp.ndarray:
    """Batched panel-free SRHT: one kernel over a stacked cohort.
    ``key_data`` (B, 2) uint32 raw keys (one transform per lane),
    ``A`` (B, n, m) columnwise / (B, m, n) rowwise — the same contract
    as :func:`fjlt.srht_serve_apply` per lane. The kernel is
    rowwise-natural (transform along the minor axis); columnwise
    cohorts transpose around it, which is exact. Fully traceable — the
    serve layer calls this inside its engine-compiled batched
    executable. Raises on unqualified input (callers gate on
    :func:`qualify` first); per-lane bits are capacity-invariant
    because every lane runs the same fixed-tile program."""
    import jax.random as jr

    A = jnp.asarray(A)
    kd = jnp.asarray(key_data, jnp.uint32)
    B = A.shape[0]
    n_axis = 2 if rowwise else 1
    n, m = A.shape[n_axis], A.shape[3 - n_axis]
    if n & (n - 1):
        raise ValueError(f"SRHT kernel requires power-of-2 n, got {n}")
    plan = plan_tiles(n, m, s_dim, m_tile)
    if plan is None:
        raise ValueError(f"no VMEM plan for s_dim={s_dim} n={n} m={m}")
    m_pad, mt, s_pad = plan
    if not rowwise:
        A = jnp.transpose(A, (0, 2, 1))
    if m_pad != m:
        A = jnp.pad(A, ((0, 0), (0, m_pad - m), (0, 0)))
    n_chunks = max(1, n // CHUNK)
    keys = jax.vmap(
        lambda k: fwht_key_table(jr.wrap_key_data(k), n_chunks))(kd)
    out = _fwht_call(A, keys.reshape(B * n_chunks, 6), s_dim=s_dim,
                     s_pad=s_pad, m_tile=mt, interpret=interpret)
    out = out[:, :m, :s_dim]
    return jnp.transpose(out, (0, 2, 1)) if not rowwise else out


def srht_apply(key_data, A, *, s_dim: int, rowwise: bool,
               m_tile: Optional[int] = None,
               interpret: bool = False) -> jnp.ndarray:
    """Single-request form: the batched kernel at B == 1 (bit-identical
    lanes either way)."""
    A = jnp.asarray(A)
    kd = jnp.asarray(key_data, jnp.uint32).reshape(1, 2)
    out = srht_apply_batched(kd, A[None], s_dim=s_dim, rowwise=rowwise,
                             m_tile=m_tile, interpret=interpret)
    return out[0]
