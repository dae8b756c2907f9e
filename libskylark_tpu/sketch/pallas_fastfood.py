"""Fused Fastfood feature map: the whole SHGΠHB chain in one Mosaic
kernel.

Motivation (BASELINE.md crossover analysis; ref: sketch/FRFT_Elemental.hpp,
sketch/FUT.hpp:225-347): the XLA Fastfood chain is bandwidth-bound — at
(16384, 4096 → 4096) it moves 4.83 GB for 34.8 GFLOP (hlo_cost_r05.json)
because every stage re-touches the whole (rows, NB) intermediate in HBM,
while dense RFT's single gemm moves 3.31 GB. This kernel keeps one m-tile
of the input resident in VMEM through the ENTIRE chain:

    read X tile → B⊙ → WHT → Π-gather → (scal·G)⊙ → WHT → (scal·Sm)⊙
      → scale·cos(· + shifts) → write F tile

so HBM traffic is one read of X plus one write of F (~0.54 GB at the
flagship config — ~9× less than the XLA chain, ~6× less than the dense
gemm) while the WHT matmuls ride the MXU. Each WHT runs as the same
kron-factored two-dot form as fut._wht_matmul (Ha·X·Hb over the
(a, b)-folded axis) with the contractions always on a minor axis — the
(a, b) fold is transposed between the dots with a rank-3 minor-axes swap.
Contractions use pallas_dense._dot's bf16x3 / f32 / bf16 regime set
(±1 Hadamard factors are bf16-exact, so bf16x3 is f32-grade here).

Like pallas_dense, the kernel is planned against the VMEM budget (the
m-tile shrinks rather than failing Mosaic) and callers take the XLA
chain when the kernel declines. On a TPU v5e (jax 0.9.0) Mosaic rejects
both variants — the fused one's permutation gather
(`jnp.take_along_axis` along the lane axis: "Shape mismatch in input,
indices and output") and the split one's WHT fold (`tpu.reshape`
64×2048 → 4096×32: "unsupported shape cast"; PERF.md) — so the kernel
is off the default dispatch: only an explicit call reaches it, and a
launch that fails to compile raises.
Exact semantics vs the XLA chain are pinned by interpret-mode oracles
in tests/test_pallas_fastfood.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from libskylark_tpu.base import env as _env
from libskylark_tpu.sketch.fut import _hadamard_np
from libskylark_tpu.sketch.pallas_dense import (_VMEM_BUDGET_BYTES, _dot,
                                                available,
                                                compiler_params)


def _wht_split(NB: int) -> tuple[int, int]:
    """The (a, b) kron fold — SAME split rule as fut._wht_matmul so the
    kernel and the XLA path accumulate in comparable order."""
    k = NB.bit_length() - 1
    a = 1 << (k - k // 2)
    return a, NB // a


def _wht2(W, Ha, Hb, mt: int, a: int, b: int, precision: str):
    """Ha·X·Hb over the (a, b)-folded minor axis of W (mt, a·b): two 2-D
    MXU dots with the fold transposed between them (math identical to
    fut._wht_matmul's einsum; exact-arithmetic wise both are ±1-weighted
    f32 sums).

    The Hadamard operand is ±1 — EXACT in bfloat16, so its lo term is
    identically zero and bf16x3's middle pass (X_hi·H_lo) contributes
    exact zeros: the 2-pass split with the H side as the "generated"
    operand is bit-identical to bf16x3 here at 2/3 the MXU passes.
    ``_dot("bf16gen2", gen_side=1)`` is exactly that split."""
    if precision == "bf16x3":
        precision = "bf16gen2"  # bit-identical for ±1 rhs, one less pass
    dims = (((1,), (0,)), ((), ()))
    Z = _dot(W.reshape(mt * a, b), Hb, dims, precision,
             gen_side=1).reshape(mt, a, b)
    Zt = jnp.swapaxes(Z, 1, 2)
    Y = _dot(Zt.reshape(mt * b, a), Ha, dims, precision,
             gen_side=1).reshape(mt, b, a)
    return jnp.swapaxes(Y, 1, 2).reshape(mt, a * b)


def _stage_pre(x, bdiag, Ha, Hb, mt, NB, precision):
    """Everything before the Π gather: B⊙x → WHT. Shared verbatim by
    the fused kernel and the split variant's stage-1 kernel — one
    definition so the two variants cannot drift apart."""
    a, b = _wht_split(NB)
    return _wht2(bdiag * x, Ha, Hb, mt, a, b, precision)


def _stage_post(W, gdiag, smdiag, shift, Ha, Hb, mt, NB, precision,
                scale):
    """Everything after the Π gather: (scal·G)⊙ → WHT → (scal·Sm)⊙ →
    scale·cos(·+shifts). Shared by both variants like _stage_pre."""
    a, b = _wht_split(NB)
    W = _wht2(gdiag * W, Ha, Hb, mt, a, b, precision)
    return scale * jnp.cos(smdiag * W + shift)


def _kernel_pre(mt, NB, precision,
                x_ref, bdiag_ref, ha_ref, hb_ref, out_ref):
    """Split-variant stage-1 kernel. Exists because the fused kernel's
    in-kernel lane gather is the one op without certified Mosaic
    precedent: if Mosaic rejects it, the dispatch falls back to this
    two-kernel pipeline with the gather done by XLA between the calls —
    still ~3× less HBM traffic than the all-XLA chain (~1.6 GB modeled
    vs 4.83 GB at the flagship config)."""
    out_ref[:] = _stage_pre(x_ref[:], bdiag_ref[:], ha_ref[:], hb_ref[:],
                            mt, NB, precision).astype(out_ref.dtype)[None]


def _kernel_post(mt, NB, precision, scale,
                 w_ref, gdiag_ref, smdiag_ref, shift_ref,
                 ha_ref, hb_ref, out_ref):
    """Split-variant stage-2 kernel."""
    out_ref[:] = _stage_post(
        w_ref[0], gdiag_ref[:], smdiag_ref[:], shift_ref[:],
        ha_ref[:], hb_ref[:], mt, NB, precision, scale,
    ).astype(out_ref.dtype)[None]


def _kernel(mt, NB, precision, scale,
            x_ref, bdiag_ref, perm_ref, gdiag_ref, smdiag_ref, shift_ref,
            ha_ref, hb_ref, out_ref):
    """One (block, m-tile) grid step: the full chain in VMEM, composed
    from the SAME stage helpers the split variant runs.

    Refs: x (mt, NB) padded input rows; bdiag/gdiag/smdiag/shift
    (1, NB) this block's diagonals (g/sm pre-scaled by √NB·fut.scale);
    perm (1, NB) int32 gather indices; ha/hb the ±1 Hadamard kron
    factors (pallas requires trace constants as inputs); out (mt, NB)
    features before block-order interleave/truncation (done by the
    caller in XLA)."""
    Ha, Hb = ha_ref[:], hb_ref[:]
    W = _stage_pre(x_ref[:], bdiag_ref[:], Ha, Hb, mt, NB, precision)
    W = jnp.take_along_axis(W, perm_ref[:], axis=1)
    out_ref[:] = _stage_post(
        W, gdiag_ref[:], smdiag_ref[:], shift_ref[:], Ha, Hb,
        mt, NB, precision, scale,
    ).astype(out_ref.dtype)[None]


def plan_m_tile(NB: int, m: int) -> int | None:
    """Largest m-tile whose working set fits the VMEM budget: double-
    buffered in/out tiles plus ~4 chain temporaries, all (mt, NB) f32.
    None when even the minimum tile doesn't fit (NB too large)."""
    per_row = NB * 4 * (2 + 2 + 4)
    mt = _VMEM_BUDGET_BYTES // per_row
    mt = min(int(mt), m, 512)
    mt -= mt % 8
    return mt if mt >= 8 else None


@functools.partial(jax.jit, static_argnames=("mt", "NB", "nb",
                                             "precision", "scale",
                                             "interpret"))
def _launch(X, bdiag, perms, gdiag, smdiag, shifts, mt, NB, nb,
            precision, scale, interpret):
    n_tiles = X.shape[0] // mt
    a, b = _wht_split(NB)
    Ha = jnp.asarray(_hadamard_np(a), jnp.float32)
    Hb = jnp.asarray(_hadamard_np(b), jnp.float32)
    return pl.pallas_call(
        functools.partial(_kernel, mt, NB, precision, scale),
        grid=(nb, n_tiles),
        in_specs=[
            pl.BlockSpec((mt, NB), lambda blk, t: (t, 0)),
            pl.BlockSpec((1, NB), lambda blk, t: (blk, 0)),
            pl.BlockSpec((1, NB), lambda blk, t: (blk, 0)),
            pl.BlockSpec((1, NB), lambda blk, t: (blk, 0)),
            pl.BlockSpec((1, NB), lambda blk, t: (blk, 0)),
            pl.BlockSpec((1, NB), lambda blk, t: (blk, 0)),
            pl.BlockSpec((a, a), lambda blk, t: (0, 0)),
            pl.BlockSpec((b, b), lambda blk, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, mt, NB), lambda blk, t: (blk, t, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, X.shape[0], NB), X.dtype),
        compiler_params=compiler_params("parallel", "parallel"),
        interpret=interpret,
    )(X, bdiag, perms, gdiag, smdiag, shifts, Ha, Hb)


@functools.partial(jax.jit, static_argnames=("mt", "NB", "nb",
                                             "precision", "scale",
                                             "interpret"))
def _launch_split(X, bdiag, perms, gdiag, smdiag, shifts, mt, NB, nb,
                  precision, scale, interpret):
    """Two-kernel pipeline: K1 (B⊙ + WHT) → XLA Π gather → K2
    (G⊙ + WHT + Sm⊙ + cos). The gather runs exactly as in the XLA
    chain; everything else stays in VMEM-resident kernels."""
    n_tiles = X.shape[0] // mt
    a, b = _wht_split(NB)
    Ha = jnp.asarray(_hadamard_np(a), jnp.float32)
    Hb = jnp.asarray(_hadamard_np(b), jnp.float32)
    diag_spec = pl.BlockSpec((1, NB), lambda blk, t: (blk, 0))
    ha_spec = pl.BlockSpec((a, a), lambda blk, t: (0, 0))
    hb_spec = pl.BlockSpec((b, b), lambda blk, t: (0, 0))
    out3 = pl.BlockSpec((1, mt, NB), lambda blk, t: (blk, t, 0))
    W1 = pl.pallas_call(
        functools.partial(_kernel_pre, mt, NB, precision),
        grid=(nb, n_tiles),
        in_specs=[pl.BlockSpec((mt, NB), lambda blk, t: (t, 0)),
                  diag_spec, ha_spec, hb_spec],
        out_specs=out3,
        out_shape=jax.ShapeDtypeStruct((nb, X.shape[0], NB), X.dtype),
        compiler_params=compiler_params("parallel", "parallel"),
        interpret=interpret,
    )(X, bdiag, Ha, Hb)
    Wg = jnp.take_along_axis(W1, perms[:, None, :], axis=-1)
    return pl.pallas_call(
        functools.partial(_kernel_post, mt, NB, precision, scale),
        grid=(nb, n_tiles),
        in_specs=[out3, diag_spec, diag_spec, diag_spec,
                  ha_spec, hb_spec],
        out_specs=out3,
        out_shape=jax.ShapeDtypeStruct((nb, X.shape[0], NB), X.dtype),
        compiler_params=compiler_params("parallel", "parallel"),
        interpret=interpret,
    )(Wg, gdiag, smdiag, shifts, Ha, Hb)


def _kernel_batched(mt, NB, precision, scale,
                    x_ref, bdiag_ref, perm_ref, gdiag_ref, smdiag_ref,
                    shift_ref, ha_ref, hb_ref, out_ref):
    """Batched-cohort grid step: the SAME fused chain (shared stage
    helpers) with the microbatch lane as the leading grid axis — refs
    carry one lane's block, indexed off their unit batch dim."""
    Ha, Hb = ha_ref[:], hb_ref[:]
    W = _stage_pre(x_ref[0], bdiag_ref[0], Ha, Hb, mt, NB, precision)
    W = jnp.take_along_axis(W, perm_ref[0], axis=1)
    out_ref[:] = _stage_post(
        W, gdiag_ref[0], smdiag_ref[0], shift_ref[0], Ha, Hb,
        mt, NB, precision, scale,
    ).astype(out_ref.dtype)[None, None]


@functools.partial(jax.jit, static_argnames=("mt", "NB", "nb",
                                             "precision", "scale",
                                             "interpret"))
def _launch_batched(X, bdiag, perms, gdiag, smdiag, shifts, mt, NB, nb,
                    precision, scale, interpret):
    """One pallas_call over a stacked cohort: X (B, m_p, NB), per-lane
    diagonal/permutation/shift streams (B, nb, NB). Grid (B, nb,
    m-tiles) — batch lanes tile innermost against the same VMEM plan
    as the single-request launcher (one lane's chain working set per
    step; ``plan_m_tile`` unchanged)."""
    from libskylark_tpu.sketch.fut import _hadamard_np

    B = X.shape[0]
    n_tiles = X.shape[1] // mt
    a, b = _wht_split(NB)
    Ha = jnp.asarray(_hadamard_np(a), jnp.float32)
    Hb = jnp.asarray(_hadamard_np(b), jnp.float32)
    diag_spec = pl.BlockSpec((1, 1, NB), lambda i, blk, t: (i, blk, 0))
    return pl.pallas_call(
        functools.partial(_kernel_batched, mt, NB, precision, scale),
        grid=(B, nb, n_tiles),
        in_specs=[
            pl.BlockSpec((1, mt, NB), lambda i, blk, t: (i, t, 0)),
            diag_spec, diag_spec, diag_spec, diag_spec, diag_spec,
            pl.BlockSpec((a, a), lambda i, blk, t: (0, 0)),
            pl.BlockSpec((b, b), lambda i, blk, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, mt, NB),
                               lambda i, blk, t: (i, blk, t, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nb, X.shape[1], NB), X.dtype),
        compiler_params=compiler_params("parallel", "parallel", "parallel"),
        interpret=interpret,
    )(X, bdiag, perms, gdiag, smdiag, shifts, Ha, Hb)


def serve_qualify(n_dim: int, s_dim: int, m: int, dtype, fut: str,
                  interpret: bool = False) -> tuple[bool, str]:
    """Host-side qualification for the batched serve launcher:
    (ok, reason) — mirrors :func:`supported` for the stacked-cohort
    case (the serve layer's decline counter wants the why)."""
    from libskylark_tpu.sketch.frft import block_geometry

    if not interpret and not available():
        return False, "backend is not a TPU (interpret-mode only here)"
    if fut != "wht":
        return False, f"fut {fut!r} has no kernel (WHT core only)"
    NB, _nb = block_geometry(n_dim, s_dim, fut)
    if NB < 512 or NB & (NB - 1):
        return False, f"NB={NB} outside the MXU-matmul regime (>=512 pow2)"
    if jnp.dtype(dtype) != jnp.float32:
        return False, f"dtype {jnp.dtype(dtype).name} != float32"
    if plan_m_tile(NB, max(int(m), 8)) is None:
        return False, "no m-tile fits the VMEM budget"
    return True, "ok"


def serve_features_batched(key_data, A, *, n_dim: int, s_dim: int,
                           fut: str = "wht", sm_kind: str = "ones",
                           sm_param=None,
                           precision: str | None = None,
                           interpret: bool = False) -> jnp.ndarray:
    """Batched fused Fastfood chain for a microbatch flush: the
    stacked-cohort analog of :func:`features_rows`, fully traceable
    (compiled into the bucket's batched executable by engine/serve).
    ``key_data`` (B, 2) uint32, ``A`` (B, m, n_dim). Per-lane streams
    are rebuilt inline from the raw keys (``frft.serve_streams`` — the
    bit-pinned pure form), so one kernel serves transforms differing
    only by seed. Raises on unqualified input: callers gate on
    :func:`serve_qualify` first."""
    import math

    import jax.random as jr

    from libskylark_tpu.sketch.frft import block_geometry, serve_streams
    from libskylark_tpu.sketch.fut import make_fut

    A = jnp.asarray(A)
    B, m, d = A.shape
    if d != n_dim:
        raise ValueError(f"operand cols {d} != n_dim {n_dim}")
    NB, nb = block_geometry(n_dim, s_dim, fut)
    mt = plan_m_tile(NB, max(m, 8))
    if mt is None:
        raise ValueError(f"no VMEM plan for NB={NB}")
    if precision is None:
        precision = "bf16x3"
    dt = A.dtype
    fut_obj = make_fut(fut, NB)
    scal = math.sqrt(NB) * fut_obj.scale()

    def lane_streams(kd):
        bd, gd, sm, pm, sh = serve_streams(
            jr.wrap_key_data(kd), dt, NB=NB, nb=nb, s_dim=s_dim,
            sm_kind=sm_kind, sm_param=sm_param)
        # shifts indexed by final feature position; features past S are
        # computed then sliced — pad their shifts with zeros (same
        # epilogue as features_rows)
        sh = jnp.pad(sh, (0, nb * NB - s_dim)).reshape(nb, NB)
        return (bd, pm.astype(jnp.int32), scal * gd,
                scal * sm.reshape(nb, NB), sh)

    bdiag, perms, gdiag, smdiag, shifts = jax.vmap(lane_streams)(
        jnp.asarray(key_data, jnp.uint32))

    pad_rows = (-m) % mt
    pad_cols = NB - d
    Ap = (jnp.pad(A, ((0, 0), (0, pad_rows), (0, pad_cols)))
          if pad_rows or pad_cols else A)
    F = _launch_batched(Ap, bdiag, perms, gdiag, smdiag, shifts,
                        mt=mt, NB=NB, nb=nb, precision=precision,
                        scale=float(math.sqrt(2.0 / s_dim)),
                        interpret=interpret)
    # (B, nb, m_p, NB) → block-major feature order, un-pad, truncate
    return jnp.moveaxis(F, 1, 2).reshape(B, Ap.shape[1], nb * NB)[
        :, :m, :s_dim]


def supported(transform, A) -> bool:
    """Whether the fused kernel may serve this FastRFT apply: WHT core
    in its MXU-matmul regime, f32 single-device eager input (sharded
    applies keep the XLA path, whose partitioning XLA handles)."""
    if not available():
        return False
    if getattr(transform, "_fut_name", None) != "wht":
        return False
    if transform._NB < 512 or transform._NB & (transform._NB - 1):
        return False
    if isinstance(A, jax.core.Tracer):
        return False
    if not isinstance(A, jax.Array) or A.dtype != jnp.float32:
        return False
    try:
        if len(A.sharding.device_set) != 1:
            return False
    except Exception:
        return False
    return plan_m_tile(transform._NB, int(A.shape[0])) is not None


# which launcher served the last features_rows call ("fused" | "split")
# — diagnostics for the on-chip certification and chip_smoke.py; never
# consulted for dispatch decisions
last_served_variant: str | None = None


def features_rows(transform, At, *, interpret: bool = False,
                  precision: str | None = None,
                  variant: str = "fused"):
    """The (m, S) Fastfood feature map for row-major input At (m, N)
    through the fused kernel, or None when the kernel declines (caller
    takes the XLA chain — mirror of pallas_dense.rowwise_apply's
    contract). A launch that Mosaic rejects raises: it never turns into
    another variant or the XLA chain silently. ``interpret`` runs the
    pallas interpreter (CPU-testable exact semantics).

    ``variant``: "fused" (single kernel, in-kernel Π gather) or "split"
    (two kernels around an XLA gather), given by the caller. Mosaic
    rejects both on the TPU tried so far (PERF.md), so the transform's
    own dispatch (``FastRFT._apply_rowwise``) takes the XLA chain and
    this kernel is reached only by an explicit call."""
    import math

    if variant not in ("fused", "split"):
        raise ValueError(
            f"variant must be 'fused' or 'split', got {variant!r}")
    if not interpret and not supported(transform, At):
        return None
    T = transform
    NB, nb = T._NB, T._numblks
    m, d = At.shape
    mt = plan_m_tile(NB, m)
    if mt is None:
        return None
    if precision is None:
        precision = _env.FASTFOOD_PRECISION.raw()
    if precision is None:
        # honor an explicit user matmul-precision policy exactly like
        # the XLA chain does (frft._fut_apply / r4 advisor): pins with
        # a kernel-equivalent regime map to it — "highest"/"float32" →
        # full-f32 passes, "high"/"bfloat16_3x" → the 3-pass bf16
        # split (the same arithmetic _dot("bf16x3") implements),
        # "bfloat16" → single-pass bf16 — anything else (e.g.
        # "tensorfloat32", "default") has no kernel equivalent, so
        # decline and let the XLA chain run under the ambient setting
        from libskylark_tpu.base import precision as bprec

        pinned = (_env.MATMUL_PRECISION.raw()
                  or (bprec.ambient_matmul_precision()
                      if bprec.ambient_precision_pinned_by_user()
                      else None))
        _PIN_REGIME = {"highest": "f32", "float32": "f32",
                       "high": "bf16x3", "bfloat16_3x": "bf16x3",
                       "bfloat16": "bf16"}
        if pinned is None:
            precision = "bf16x3"
        elif pinned in _PIN_REGIME:
            precision = _PIN_REGIME[pinned]
        else:
            return None
    dt = At.dtype
    scal = math.sqrt(NB) * T._fut.scale()

    pad_rows = (-m) % mt
    pad_cols = NB - d
    Ap = (jnp.pad(At, ((0, pad_rows), (0, pad_cols)))
          if pad_rows or pad_cols else At)

    bdiag = T._B(dt)
    gdiag = scal * T._G(dt)
    smdiag = scal * T._Sm(dt).reshape(nb, NB)
    perms = T._perms().astype(jnp.int32)
    sh = T.shifts(dt)
    # shifts indexed by FINAL feature position f = blk·NB + j; features
    # past S are computed then sliced off — pad their shifts with zeros
    sh = jnp.pad(sh, (0, nb * NB - T._S)).reshape(nb, NB)

    global last_served_variant
    launch = _launch if variant == "fused" else _launch_split
    F = launch(Ap, bdiag, perms, gdiag, smdiag, sh,
               mt=mt, NB=NB, nb=nb, precision=precision,
               scale=float(T.scale), interpret=interpret)
    last_served_variant = variant
    # (nb, m_p, NB) → block-major feature order, un-pad, truncate —
    # identical to FastRFT._features_rows' epilogue
    return jnp.moveaxis(F, 0, 1).reshape(Ap.shape[0], nb * NB)[
        :m, : T._S]
