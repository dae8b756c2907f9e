"""Pallas TPU kernel: scatter-free CWT/CountSketch apply.

The hash sketch is the framework's cheapest transform — O(nnz) work, one
±1 multiply and one add per input coordinate — yet it was the LEAST
kernel-optimized: ``HashTransform.apply`` / ``hash.cwt_serve_apply`` are
``jax.ops.segment_sum`` scatters, which XLA lowers to a serialized
scatter-add on every backend (the TPU scatter unit retires one update
row at a time, so the MXU idles through the whole apply). Per the
FlashSketch sketch-kernel co-design line (PAPERS.md), this kernel
replaces the scatter with MXU work it can pipeline:

1. **On-the-fly stream generation.** The (h, v) bucket/value streams are
   regenerated in-kernel from the transform's raw Threefry key — the
   same discipline as ``pallas_dense._gen_block``, but replicating
   ``randgen.stream_slice``'s *chunk* format (the shared integer-op
   cipher and draw→sample maps of ``base/threefry.py``) so the kernel's
   streams are **bit-identical** to the XLA path's. The per-chunk
   derived keys (a handful of tiny fold_in ciphers) are precomputed by the
   traced wrapper into an SMEM table (:func:`chunk_key_table`); the
   per-entry work (one or two 2048-wide Threefry sweeps + the
   ``bits_to_randint`` modular math + a sign map) runs in VMEM per grid
   step.

2. **Bucket-tiled one-hot contraction** (``accum="mxu"``, the TPU fast
   path): each 128-entry row of the generated chunk becomes a signed
   one-hot matrix ``Hv`` (s_dim × 128) contracted against the matching
   input rows on the MXU — the sketch *is* a matmul against a matrix the
   kernel never stores globally. f32 operands at ``Precision.HIGHEST``;
   the one-hot entries and ±1 values are exact, so only the contraction
   ORDER differs from the scatter — last-ulp differences on float data,
   bit-equal on any data whose bucket sums are exact (the lattice-valued
   battery in tests/test_pallas_hash.py pins the whole dataflow bitwise
   this way).

3. **Exact sequential accumulation** (``accum="exact"``): a fori_loop
   masked-broadcast add that reproduces the scatter's
   increasing-coordinate accumulation order term by term — **bit-equal
   to ``HashTransform.apply`` and ``cwt_serve_apply``** including
   zero-padded serve lanes (padded coordinates contribute exact ±0.0,
   which can never flip an accumulator bit). This is the interpret-mode
   correctness surface CPU tier-1 pins and the CI serve gate's
   bit-equality leg; it is VPU-serial over coordinates, so nothing
   selects it for throughput (on TPU the mxu mode serves; on CPU the
   default keeps XLA).

The batched entry point (:func:`cwt_apply_batched`) adds a leading
cohort dimension as a grid axis — one ``pallas_call`` flushes a whole
microbatch cohort (``engine/serve.py``) instead of vmap-of-XLA — with
the same shrink-don't-fail VMEM planning as ``pallas_dense._qualify``.
Lanes are computed independently at fixed tile sizes, so per-lane bits
are invariant to the capacity class, which is the serve layer's lane-
invariance contract.

Non-finite caveat: the scatter touches only bucket ``h[j]`` with row
``j``, while both kernel modes multiply every bucket by a 0/±1 mask —
``0 · inf = nan``, so a non-finite input coordinate poisons all buckets
of its output column, not just its own. Finite inputs are unaffected.

Like every kernel in this tree, dispatch DECLINES (returns None /
``qualify`` explains why) rather than failing: callers keep the XLA
scatter. On a TPU v5e the batched kernel compiles and matches its XLA
twin (rel-max 1.6e-7 at 8 × (8192, 512) → 1024) but does not beat it
(PERF.md), so only an explicit override routes a direct apply here (the
serve tier takes it under a ``kernel=`` / env pin alone). A selected
kernel that Mosaic rejects raises on the direct-apply path; the serve
layer counts it (``mosaic-reject``) and serves the XLA program.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from libskylark_tpu.base import randgen
from libskylark_tpu.base import threefry as tf
from libskylark_tpu.sketch.pallas_dense import (_VMEM_BUDGET_BYTES,
                                                available,
                                                compiler_params)

# Stream chunk width — randgen's CHUNK is part of the stream format; the
# kernel's n-axis tile is one chunk (or a pow2 prefix of one).
CHUNK = randgen.CHUNK

# threefry.chunk_bits draws a chunk as threefry2x32 over counter pairs
# (j, j + CHUNK//2): position j < half rides the cipher's first output
# lane, position j + half the second. Fixed by the format.
_HALF = CHUNK // 2

# Lane width of the in-kernel generation grid: chunk positions are laid
# out row-major over (rows, _GEN_COLS) so every Threefry/randint op is a
# native 2-D vector op (Mosaic has no 1-D iota).
_GEN_COLS = 128

# Default rows-per-grid-step of the non-contracted axis; shrunk (never
# failed) against the VMEM budget like pallas_dense's m-tile.
_DEFAULT_M_TILE = 256

_MODES = ("mxu", "exact")


# ---------------------------------------------------------------------------
# stream replication: host/XLA side (tiny per-chunk key table)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames="n_chunks")
def chunk_key_table(key, n_chunks: int) -> jnp.ndarray:
    """(n_chunks, 6) uint32 table of the derived keys the kernel needs
    per stream chunk: the two-draw key pair of the bucket stream
    (sub-stream 0, ``UniformInt``) and the chunk key of the value stream
    (sub-stream 1). Exactly the keys ``randgen.stream_slice`` derives —
    ``fold_in(fold_in(subkey, hi), lo)`` (hi == 0 below 2³¹ chunks), then
    ``fold_in(chunk key, i)`` per draw — a few 2-wide ciphers per chunk,
    traced and vmappable (the serve executable computes the whole
    cohort's tables inline)."""
    import jax.random as jr

    kd = jr.key_data(key)
    hkey = tf.fold_in(tf.fold_in(kd, 0), 0)
    vkey = tf.fold_in(tf.fold_in(kd, 1), 0)

    def one(c):
        hck = tf.fold_in(hkey, c)
        return jnp.concatenate([
            tf.fold_in(hck, 0), tf.fold_in(hck, 1), tf.fold_in(vkey, c),
        ])

    return jax.vmap(one)(jnp.arange(n_chunks, dtype=jnp.int32))


# ---------------------------------------------------------------------------
# in-kernel generation
# ---------------------------------------------------------------------------


def _chunk_bits(k0, k1, rows: int, cols: int, both: bool):
    """uint32 draws for the leading ``rows*cols`` (× 2 when ``both``)
    positions of one chunk, row-major (rows, cols) — the
    ``threefry.chunk_bits`` layout: counter pairs
    (j, j + _HALF) with position j on the first cipher lane and
    position j + _HALF on the second."""
    c = (
        jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 0) * cols
        + jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 1)
    )
    x0, x1 = tf.threefry2x32(k0, k1, c, c + _HALF)
    if both:
        return jnp.concatenate([x0, x1], axis=0)
    return x0


def _gen_hv(keys_ref, kidx, s_dim: int, length: int, cols: int):
    """(h, v) for the leading ``length`` positions of chunk ``kidx`` of
    the key table, as row-major (length // cols, cols) grids — h the
    int32 bucket stream (``UniformInt(0, s_dim-1)``), v the ±1 f32
    value stream (``Rademacher``), both bit-identical to
    ``randgen.stream_slice`` (tests pin this through an identity-input
    apply)."""
    cipher_rows = min(length, _HALF) // cols
    both = length > _HALF
    mult = tf.randint_multiplier(s_dim)
    lo = _chunk_bits(keys_ref[kidx, 2], keys_ref[kidx, 3],
                     cipher_rows, cols, both)
    if mult == 0:
        mixed = _mod_span(lo, s_dim)
    else:
        hi = _chunk_bits(keys_ref[kidx, 0], keys_ref[kidx, 1],
                         cipher_rows, cols, both)
        mixed = _mod_span(
            _mod_span(hi, s_dim) * mult + _mod_span(lo, s_dim), s_dim)
    h = mixed.astype(jnp.int32)
    vbits = _chunk_bits(keys_ref[kidx, 4], keys_ref[kidx, 5],
                        cipher_rows, cols, both)
    v = tf.bits_to_rademacher(vbits)
    return h, v


def _mod_span(x, s_dim: int):
    """x % s_dim on uint32 — a lane mask for pow2 spans (the common
    serve case; Mosaic-native), the general remainder otherwise."""
    if s_dim & (s_dim - 1) == 0:
        return x & (s_dim - 1)
    return x % s_dim


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _mxu_rows(h, v, s_dim: int, cols: int, rows: int, contract):
    """Σ over generation rows of the signed-one-hot contraction:
    ``contract(Hv, r)`` supplies each row's dot against the matching
    input slice. The one-hot build is pure VPU compare/select; the
    contraction is the MXU's."""
    acc = None
    for r in range(rows):
        hr = h[r:r + 1, :]
        vr = v[r:r + 1, :]
        onehot = (jax.lax.broadcasted_iota(jnp.int32, (s_dim, cols), 0)
                  == hr).astype(jnp.float32)
        part = contract(onehot * vr, r)
        acc = part if acc is None else acc + part
    return acc


def _hot_dot(lhs, rhs, dims):
    return jax.lax.dot_general(
        lhs, rhs, dims, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _kernel_cw(s_dim, n_tile, n_chunks, cols, accum, keys_ref, a_ref,
               out_ref):
    """Columnwise: out[b] (s_dim, m_tile) += CWT over one chunk of
    a[b] (n_tile, m_tile). Grid (batch, m_tiles, n_chunks); the chunk
    axis is sequential (accumulation), batch/m parallel."""
    b = pl.program_id(0)
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    h, v = _gen_hv(keys_ref, b * n_chunks + c, s_dim, n_tile, cols)
    if accum == "mxu":
        A = a_ref[0]

        def contract(hv, r):
            return _hot_dot(hv, A[r * cols:(r + 1) * cols, :],
                            (((1,), (0,)), ((), ())))

        out_ref[:] += _mxu_rows(h, v, s_dim, cols, n_tile // cols,
                                contract)[None]
    else:
        # exact scatter order: one coordinate at a time, increasing j —
        # the mask lanes contribute ±0.0, which never perturbs a sum
        iota_s = jax.lax.broadcasted_iota(jnp.int32, (s_dim, 1), 0)

        def body(j, _):
            r = j // cols
            col = j % cols
            hj = jax.lax.dynamic_slice(h, (r, col), (1, 1))
            vj = jax.lax.dynamic_slice(v, (r, col), (1, 1))
            arow = a_ref[0, pl.ds(j, 1), :]
            mask = (iota_s == hj).astype(jnp.float32)
            out_ref[:] += (mask * (vj * arow))[None]
            return 0

        jax.lax.fori_loop(0, n_tile, body, 0)


def _kernel_rw(s_dim, n_tile, n_chunks, cols, accum, keys_ref, a_ref,
               out_ref):
    """Rowwise orientation: out[b] (m_tile, s_dim) += a[b] (m_tile,
    n_tile) · signed-one-hot."""
    b = pl.program_id(0)
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    h, v = _gen_hv(keys_ref, b * n_chunks + c, s_dim, n_tile, cols)
    if accum == "mxu":
        A = a_ref[0]

        def contract(hv, r):
            return _hot_dot(A[:, r * cols:(r + 1) * cols], hv,
                            (((1,), (1,)), ((), ())))

        out_ref[:] += _mxu_rows(h, v, s_dim, cols, n_tile // cols,
                                contract)[None]
    else:
        iota_s = jax.lax.broadcasted_iota(jnp.int32, (1, s_dim), 1)

        def body(j, _):
            r = j // cols
            col = j % cols
            hj = jax.lax.dynamic_slice(h, (r, col), (1, 1))
            vj = jax.lax.dynamic_slice(v, (r, col), (1, 1))
            acol = a_ref[0, :, pl.ds(j, 1)]
            mask = (iota_s == hj).astype(jnp.float32)
            out_ref[:] += (acol * (vj * mask))[None]
            return 0

        jax.lax.fori_loop(0, n_tile, body, 0)


# ---------------------------------------------------------------------------
# planning + launch
# ---------------------------------------------------------------------------


def _pad_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _padded_n(n: int) -> int:
    """Stream-axis extent the kernel runs at: next pow2 (min 8) below
    one chunk, else the next whole-chunk multiple. Zero-padding is
    exact — padded coordinates carry real stream values but multiply
    zero data."""
    if n <= 8:
        return 8
    if n < CHUNK:
        return 1 << (n - 1).bit_length()
    return _pad_to(n, CHUNK)


def _vmem_estimate(m_tile: int, s_dim: int, n_tile: int) -> int:
    """Per-grid-step VMEM plan: double-buffered input tile and output
    accumulator, the generated h/v grids and cipher temporaries
    (~6 chunk-sized u32/f32 arrays), and the (s_dim × _GEN_COLS)
    one-hot."""
    return 4 * (
        2 * n_tile * m_tile
        + 2 * s_dim * m_tile
        + 6 * n_tile
        + 2 * s_dim * _GEN_COLS
    )


def plan_tiles(n: int, m: int, s_dim: int,
               m_tile: Optional[int] = None) -> Optional[tuple]:
    """(n_pad, n_tile, m_pad, m_tile) under the VMEM budget, or None
    when even the minimum tile doesn't fit — shrink-don't-fail, the
    same discipline as ``pallas_dense._qualify``."""
    n_pad = _padded_n(n)
    n_tile = min(n_pad, CHUNK)
    mt = m_tile or _DEFAULT_M_TILE
    mt = max(8, 1 << (max(int(mt), 8).bit_length() - 1))
    while mt > 8 and _vmem_estimate(mt, s_dim, n_tile) > _VMEM_BUDGET_BYTES:
        mt //= 2
    if _vmem_estimate(mt, s_dim, n_tile) > _VMEM_BUDGET_BYTES:
        return None
    m_pad = _pad_to(max(m, 8), mt)
    mt = min(mt, m_pad)
    while m_pad % mt:
        mt //= 2
    return n_pad, n_tile, m_pad, mt


def qualify(s_dim: int, n: int, m: int, dtype,
            interpret: bool = False,
            accum: str = "mxu") -> tuple[bool, str]:
    """Host-side qualification: (ok, reason). The serve layer counts
    declined reasons (``serve.kernel_declined``) so operators can see
    WHY a replica is not on the fast path."""
    if accum not in _MODES:
        return False, f"unknown accum mode {accum!r}"
    if not interpret and not available():
        return False, "backend is not a TPU (interpret-mode only here)"
    if jnp.dtype(dtype) != jnp.float32:
        return False, f"dtype {jnp.dtype(dtype).name} != float32"
    if s_dim < 1 or n < 1 or m < 1:
        return False, "degenerate shape"
    if plan_tiles(n, m, s_dim) is None:
        return False, "no tile fits the VMEM budget"
    return True, "ok"


@functools.partial(
    jax.jit,
    static_argnames=("s_dim", "rowwise", "accum", "m_tile", "interpret"),
)
def _hash_call(A, keys, *, s_dim, rowwise, accum, m_tile, interpret):
    """One pallas_call over the stacked (B, ...) operand (already
    padded). ``keys`` is the flattened (B * n_chunks, 6) chunk-key
    table."""
    B = A.shape[0]
    n = A.shape[2] if rowwise else A.shape[1]
    m = A.shape[1] if rowwise else A.shape[2]
    n_tile = min(n, CHUNK)
    n_chunks = n // n_tile
    cols = min(n_tile, _GEN_COLS)
    grid = (B, m // m_tile, n_chunks)
    params = compiler_params("parallel", "parallel", "arbitrary")
    if rowwise:
        kern = functools.partial(_kernel_rw, s_dim, n_tile, n_chunks,
                                 cols, accum)
        a_spec = pl.BlockSpec((1, m_tile, n_tile),
                              lambda b, i, c: (b, i, c),
                              memory_space=pltpu.VMEM)
        out_spec = pl.BlockSpec((1, m_tile, s_dim),
                                lambda b, i, c: (b, i, 0),
                                memory_space=pltpu.VMEM)
        out_shape = jax.ShapeDtypeStruct((B, m, s_dim), jnp.float32)
    else:
        kern = functools.partial(_kernel_cw, s_dim, n_tile, n_chunks,
                                 cols, accum)
        a_spec = pl.BlockSpec((1, n_tile, m_tile),
                              lambda b, i, c: (b, c, i),
                              memory_space=pltpu.VMEM)
        out_spec = pl.BlockSpec((1, s_dim, m_tile),
                                lambda b, i, c: (b, 0, i),
                                memory_space=pltpu.VMEM)
        out_shape = jax.ShapeDtypeStruct((B, s_dim, m), jnp.float32)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # whole key table
            a_spec,
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        compiler_params=params,
        interpret=interpret,
    )(keys, A)


def cwt_apply_batched(key_data, A, *, s_dim: int, rowwise: bool,
                      accum: str = "mxu",
                      m_tile: Optional[int] = None,
                      interpret: bool = False) -> jnp.ndarray:
    """Batched scatter-free CountSketch: one kernel over a stacked
    cohort. ``key_data`` (B, 2) uint32 raw keys (one transform per
    lane), ``A`` (B, n, m) columnwise / (B, m, n) rowwise. Fully
    traceable — the serve layer calls this inside its engine-compiled
    batched executable. Raises on unqualified input (callers gate on
    :func:`qualify` first); per-lane bits are capacity-invariant
    because every lane runs the same fixed-tile program."""
    import jax.random as jr

    if accum not in _MODES:
        raise ValueError(f"accum must be one of {_MODES}, got {accum!r}")
    A = jnp.asarray(A)
    kd = jnp.asarray(key_data, jnp.uint32)
    B = A.shape[0]
    n_axis = 2 if rowwise else 1
    n, m = A.shape[n_axis], A.shape[3 - n_axis]
    plan = plan_tiles(n, m, s_dim, m_tile)
    if plan is None:
        raise ValueError(
            f"no VMEM plan for s_dim={s_dim} n={n} m={m}")
    n_pad, n_tile, m_pad, mt = plan
    pads = [(0, 0), (0, 0), (0, 0)]
    pads[n_axis] = (0, n_pad - n)
    pads[3 - n_axis] = (0, m_pad - m)
    Ap = jnp.pad(A, pads) if (n_pad != n or m_pad != m) else A
    n_chunks = n_pad // n_tile
    keys = jax.vmap(
        lambda k: chunk_key_table(jr.wrap_key_data(k), n_chunks))(kd)
    out = _hash_call(Ap, keys.reshape(B * n_chunks, 6), s_dim=s_dim,
                     rowwise=rowwise, accum=accum, m_tile=mt,
                     interpret=interpret)
    return out[:, :m, :] if rowwise else out[:, :, :m]


def cwt_apply(key_data, A, *, s_dim: int, rowwise: bool,
              accum: str = "mxu", m_tile: Optional[int] = None,
              interpret: bool = False) -> jnp.ndarray:
    """Single-request form: the batched kernel at B == 1 (bit-identical
    lanes either way). Same contract as ``hash.cwt_serve_apply`` —
    zero-padding the operand past the transform's true N leaves the
    result bit-equal (``accum="exact"``) / ulp-close (``"mxu"``)."""
    A = jnp.asarray(A)
    kd = jnp.asarray(key_data, jnp.uint32).reshape(1, 2)
    out = cwt_apply_batched(kd, A[None], s_dim=s_dim, rowwise=rowwise,
                            accum=accum, m_tile=m_tile,
                            interpret=interpret)
    return out[0]


def try_apply(transform, A, *, rowwise: bool) -> Optional[jnp.ndarray]:
    """Direct-apply dispatch hook for ``HashTransform``: run the kernel
    when (a) it's a CWT on a qualifying f32 single-device operand on a
    TPU backend, and (b) an explicit override (``SKYLARK_HASH_KERNEL``
    = pallas | pallas_exact) picks it. Returns None to decline — the
    caller keeps the XLA scatter; with no override it declines (module
    docstring). A selected kernel that fails to compile raises."""
    from libskylark_tpu.base import env as _env
    from libskylark_tpu.sketch import params as sketch_params

    if type(transform).__name__ != "CWT":
        return None
    if not sketch_params.get_use_pallas():
        return None
    from libskylark_tpu.sketch.dense import pallas_ambient_ok

    if not pallas_ambient_ok(A):
        return None
    env = (_env.HASH_KERNEL.raw() or "").strip().lower()
    if env in ("pallas", "mxu", "1"):
        accum = "mxu"
    elif env in ("pallas_exact", "exact"):
        accum = "exact"
    else:
        return None  # unset, or explicit xla/off
    n = A.shape[1] if rowwise else A.shape[0]
    m = A.shape[0] if rowwise else A.shape[1]
    ok, _why = qualify(transform.sketch_dim, n, m, A.dtype)
    if not ok:
        return None
    return cwt_apply(transform.allocation.key_words, A,
                     s_dim=transform.sketch_dim, rowwise=rowwise,
                     accum=accum)
