"""Fastfood random features: FastGaussianRFT, FastMaternRFT.

TPU-native analog of ref: sketch/FRFT_data.hpp:26-291, sketch/FRFT_Elemental.hpp.
Le-Sarlos-Smola Fastfood: each block of NB features is
Sm ⊙ F(G ⊙ Π(F(B ⊙ x))) — two fast unitary transforms around a random
permutation and three random diagonals, giving an implicit Gaussian-like
frequency matrix in O(NB log NB) per block instead of O(NB²). Output is
scale·cos(w + shifts) like RFT.

Differences from the reference, by design:
- The block permutation is a uniform permutation from a sub-stream key
  (jax.random.permutation) rather than the reference's hand-rolled
  Fisher-Yates swap records (ref: FRFT_data.hpp:105-113) — same distribution,
  TPU-friendly gather.
- All columns and all blocks are processed batched (vmapped FUT over a
  (numblks, NB, m) tensor) instead of the reference's per-column OpenMP loop
  (ref: FRFT_Elemental.hpp:77-160).

Sub-streams: 0=shifts, 1=B, 2=G, 3=permutations, 4=Sm (Matern).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import jax.random as jr

from libskylark_tpu.base import randgen
from libskylark_tpu.sketch.fut import make_fut
from libskylark_tpu.sketch.transform import SketchTransform, register


def fut_apply_policy(fut_obj, fut_name: str, W):
    """The FUT along the contiguous feature axis. The WHT core opts
    into Precision.HIGH (TPU: 3-pass bf16 — near-lossless for ±1
    Hadamard factors, ~2× the full-f32 MXU rate; analysis at
    fut._wht_matmul) UNLESS the user pinned an explicit policy —
    via SKYLARK_MATMUL_PRECISION, jax.config.update, or an active
    jax.default_matmul_precision(...) context (r4 advisor) — which
    then governs here too. Runtime tuning only — never serialized,
    like the pallas regime knobs. Shared by the transform method and
    the serve-layer pure apply so the two paths cannot drift."""
    if fut_name != "wht":
        return fut_obj.apply(W, axis=-1)
    from libskylark_tpu.base import env as _env
    from libskylark_tpu.base import precision as bprec

    prec = (None if _env.MATMUL_PRECISION.raw()
            or bprec.ambient_precision_pinned_by_user()
            else jax.lax.Precision.HIGH)
    return fut_obj.apply(W, axis=-1, precision=prec)


def _chain_rows(Ap, bdiag, gdiag, smdiag, perms, shifts, out_scale,
                scal, NB: int, nb: int, fut_apply):
    """The SHGΠHB chain on padded row-major input (m, NB) — ONE
    definition shared by ``FastRFT._features_rows`` and the pure serve
    apply (:func:`fastfood_serve_apply`), so the served features are
    the transform's features by construction. Laid out for HBM economy
    (see the ``_features_rows`` docstring): (blocks, rows, NB) with the
    transform length contiguous; block-major feature order, truncation
    to S = ``shifts.shape[0]``."""
    W = bdiag[:, None, :] * Ap[None, :, :]                # (nb, m, NB)
    W = fut_apply(W)
    W = jnp.take_along_axis(W, perms[:, None, :], axis=-1)
    W = (scal * gdiag)[:, None, :] * W
    W = fut_apply(W)
    W = (scal * smdiag.reshape(nb, 1, NB)) * W
    # block-major feature order (matches the serialized definition);
    # for nb == 1 the moveaxis is a free squeeze
    W = jnp.moveaxis(W, 0, 1).reshape(Ap.shape[0], nb * NB)
    W = W[:, : shifts.shape[0]]
    return out_scale * jnp.cos(W + shifts[None, :])


def block_geometry(n_dim: int, s_dim: int, fut: str = "wht"
                   ) -> tuple[int, int]:
    """(NB, numblks) for a Fastfood transform of these dimensions —
    the ``FastRFT._build`` rule as a pure function (the serve layer
    recomputes geometry from bucket statics)."""
    NB = (1 << max(0, (n_dim - 1).bit_length())) if fut == "wht" \
        else n_dim
    return NB, 1 + (s_dim - 1) // NB


def serve_streams(key, dtype, *, NB: int, nb: int, s_dim: int,
                  sm_kind: str, sm_param):
    """Every Fastfood stream as a pure function of the transform's
    allocation key: (bdiag, gdiag, smdiag, perms, shifts) — identical
    bits to ``_B``/``_G``/``_Sm``/``_perms``/``shifts`` (sub-streams
    1/2/4-spec/3/0 of the key; pinned by tests). vmap-safe, so the
    microbatch serve executable rebuilds a whole cohort's streams from
    the stacked raw keys."""
    def sub(tag):
        return jr.fold_in(key, tag)

    bdiag = randgen.stream_slice(
        sub(1), randgen.Rademacher(), 0, nb * NB, dtype=dtype,
    ).reshape(nb, NB)
    gdiag = randgen.stream_slice(
        sub(2), randgen.Normal(), 0, nb * NB, dtype=dtype,
    ).reshape(nb, NB)
    pkey = sub(3)
    perms = jnp.stack(
        [jr.permutation(jr.fold_in(pkey, i), NB) for i in range(nb)])
    shifts = randgen.stream_slice(
        sub(0), randgen.Uniform(0.0, 2.0 * math.pi), 0, s_dim,
        dtype=dtype)
    if sm_kind == "ones":
        smdiag = jnp.ones((nb * NB,), dtype)
    elif sm_kind == "gauss":
        smdiag = jnp.full(
            (nb * NB,), 1.0 / (float(sm_param) * math.sqrt(NB)), dtype)
    elif sm_kind == "matern":
        nu, el = sm_param
        chi2 = randgen.stream_slice(
            sub(4), randgen.Gamma(shape_param=float(nu), scale=2.0),
            0, nb * NB, dtype=dtype)
        smdiag = jnp.sqrt(
            2.0 * float(nu) / jnp.maximum(chi2, jnp.finfo(dtype).tiny)
        ) / (float(el) * math.sqrt(NB))
    else:
        raise ValueError(f"unknown Sm spec kind {sm_kind!r}")
    return bdiag, gdiag, smdiag, perms, shifts


def fastfood_serve_apply(key_data, A, *, n_dim: int, s_dim: int,
                         fut: str = "wht", sm_kind: str = "ones",
                         sm_param=None) -> jnp.ndarray:
    """Pure, vmap-batchable Fastfood feature map for the microbatch
    serving layer: one request's (m, S) features as a function of the
    transform's raw key data ((2,) uint32) and static geometry. Rows
    are independent lanes, so zero-padding the row extent past the true
    request is exact for the real rows (padded rows are sliced away by
    the executor); the column extent must equal ``n_dim`` (the chain's
    own NB-padding is part of the feature definition)."""
    key = jr.wrap_key_data(jnp.asarray(key_data))
    NB, nb = block_geometry(n_dim, s_dim, fut)
    dt = A.dtype
    pad = NB - n_dim
    Ap = jnp.pad(A, ((0, 0), (0, pad))) if pad else A
    fut_obj = make_fut(fut, NB)
    scal = math.sqrt(NB) * fut_obj.scale()
    bdiag, gdiag, smdiag, perms, shifts = serve_streams(
        key, dt, NB=NB, nb=nb, s_dim=s_dim, sm_kind=sm_kind,
        sm_param=sm_param)
    return _chain_rows(
        Ap, bdiag, gdiag, smdiag, perms, shifts,
        math.sqrt(2.0 / s_dim), scal, NB, nb,
        lambda W: fut_apply_policy(fut_obj, fut, W))


class FastRFT(SketchTransform):
    """Base Fastfood transform (ref: sketch/FRFT_data.hpp:26-139).

    Default FUT is the Walsh-Hadamard transform — the reference's
    preferred Fastfood core when SpiralWHT is available
    (ref: FRFT_data.hpp:125, sketch/FUT.hpp:225-347); here it runs as the
    kron-factored MXU matmul (fut.py _wht_matmul), which is what makes
    Fastfood *fast* on TPU. ``fut="dct"`` keeps the FFT-based FFTW-analog
    path (any N without padding)."""

    sketch_type = "FastRFT"

    def __init__(self, N, S, context, fut: str = "wht"):
        self._fut_name = fut
        super().__init__(N, S, context)

    def _build(self):
        # DCT works for any N (FFTW analog, NB=N); WHT needs power-of-2
        # blocks (SpiralWHT analog) — ref: FRFT_data.hpp block_size().
        # One rule, shared with the serve layer's bucket-statics
        # recomputation (:func:`block_geometry`), so the two can never
        # drift apart.
        self._NB, self._numblks = block_geometry(
            self._N, self._S, self._fut_name)
        self._fut = make_fut(self._fut_name, self._NB)

    def _fut_apply(self, W):
        """The FUT along the contiguous feature axis — one shared
        definition with the serve-layer pure apply
        (:func:`fut_apply_policy`)."""
        return fut_apply_policy(self._fut, self._fut_name, W)

    def _sm_spec(self) -> tuple:
        """(kind, param) descriptor of the per-feature Sm scaling — the
        static the serve layer buckets on and rebuilds ``_Sm`` from in
        :func:`serve_streams` (base: all-ones)."""
        return ("ones", None)

    @property
    def scale(self) -> float:
        return math.sqrt(2.0 / self._S)

    def shifts(self, dtype=jnp.float32) -> jnp.ndarray:
        return randgen.stream_slice(
            self.subkey(0), randgen.Uniform(0.0, 2.0 * math.pi), 0, self._S,
            dtype=dtype,
        )

    def _B(self, dtype) -> jnp.ndarray:
        return randgen.stream_slice(
            self.subkey(1), randgen.Rademacher(), 0, self._numblks * self._NB,
            dtype=dtype,
        ).reshape(self._numblks, self._NB)

    def _G(self, dtype) -> jnp.ndarray:
        return randgen.stream_slice(
            self.subkey(2), randgen.Normal(), 0, self._numblks * self._NB,
            dtype=dtype,
        ).reshape(self._numblks, self._NB)

    def _perms(self) -> jnp.ndarray:
        key = self.subkey(3)
        return jnp.stack(
            [jr.permutation(jr.fold_in(key, i), self._NB) for i in range(self._numblks)]
        )

    def _Sm(self, dtype) -> jnp.ndarray:
        """Kernel-specific per-feature scaling (numblks·NB,); subclasses override
        (ref: FRFT_data.hpp:118 — base fills 1)."""
        return jnp.ones((self._numblks * self._NB,), dtype)

    def _features_rows(self, At: jnp.ndarray) -> jnp.ndarray:
        """The (m, S) feature map for ROW-major input At (m, N).

        Laid out for HBM economy (the r3 on-CPU finding was Fastfood
        losing to the dense gemm on data movement, not FLOPs): the whole
        SHGΠHB chain runs in (blocks, rows, NB) layout with the
        transform length CONTIGUOUS, so the kron-factored WHT's two
        batched matmuls (fut._wht_matmul) touch no transposes, the
        permutation gathers along the minor axis, and the diagonals
        (B, G, Sm) fuse into the adjacent contractions. The rowwise
        apply — the ML feature-map case — moves no axis at all for a
        single block (numblks == 1 whenever S <= NB): input is consumed
        and features are produced in their natural layouts."""
        dt = At.dtype
        NB, nb = self._NB, self._numblks
        pad = NB - self._N
        Ap = jnp.pad(At, ((0, 0), (0, pad))) if pad else At
        scal = math.sqrt(NB) * self._fut.scale()
        return _chain_rows(
            Ap, self._B(dt), self._G(dt), self._Sm(dt), self._perms(),
            self.shifts(dt), self.scale, scal, NB, nb, self._fut_apply)

    def _apply_columnwise(self, A: jnp.ndarray) -> jnp.ndarray:
        # the chain is written for row-major input; the transpose feeds
        # it either way
        return self._apply_rowwise(A.T).T

    def _apply_rowwise(self, A: jnp.ndarray) -> jnp.ndarray:
        # The XLA chain. The fused single-kernel chain
        # (sketch/pallas_fastfood.py: one HBM read of A, one write of the
        # features — this chain re-touches the intermediate ~9×;
        # BASELINE.md crossover analysis) is not on this path: Mosaic
        # rejects both its variants on the TPU tried so far (PERF.md).
        return self._features_rows(A)

    def _extra_params(self) -> dict[str, Any]:
        return {"fut": self._fut_name}


@register
class FastGaussianRFT(FastRFT):
    """Fastfood for the Gaussian kernel: Sm = 1/(σ√N)
    (ref: FRFT_data.hpp:196-203)."""

    sketch_type = "FastGaussianRFT"

    def __init__(self, N, S, context, sigma: float = 1.0, fut: str = "wht"):
        self._sigma = float(sigma)
        super().__init__(N, S, context, fut=fut)

    def _Sm(self, dtype) -> jnp.ndarray:
        # Normalize by the padded block length NB, not N: pre-Sm feature
        # variance is NB·‖x‖² (the reference always has NB == N via FFTW,
        # ref: FRFT_data.hpp:196-203; with WHT padding NB > N and using N
        # would bias the kernel bandwidth by NB/N).
        v = 1.0 / (self._sigma * math.sqrt(self._NB))
        return jnp.full((self._numblks * self._NB,), v, dtype)

    def _sm_spec(self) -> tuple:
        return ("gauss", self._sigma)

    def _extra_params(self) -> dict[str, Any]:
        return {"sigma": self._sigma, "fut": self._fut_name}

    @classmethod
    def _from_parts(cls, N, S, alloc, d):
        return cls(N, S, alloc, sigma=float(d.get("sigma", 1.0)),
                   fut=d.get("fut", "wht"))


@register
class FastMaternRFT(FastRFT):
    """Fastfood for the Matern kernel: Sm = sqrt(2ν/χ²(2ν))/(l√N)
    (ref: FRFT_data.hpp:268-277)."""

    sketch_type = "FastMaternRFT"

    def __init__(self, N, S, context, nu: float = 1.0, l: float = 1.0,
                 fut: str = "wht"):
        self._nu = float(nu)
        self._l = float(l)
        super().__init__(N, S, context, fut=fut)

    def _Sm(self, dtype) -> jnp.ndarray:
        chi2 = randgen.stream_slice(
            self.subkey(4),
            randgen.Gamma(shape_param=self._nu, scale=2.0),
            0,
            self._numblks * self._NB,
            dtype=dtype,
        )
        return jnp.sqrt(
            2.0 * self._nu / jnp.maximum(chi2, jnp.finfo(dtype).tiny)
        ) / (self._l * math.sqrt(self._NB))

    def _sm_spec(self) -> tuple:
        return ("matern", (self._nu, self._l))

    def _extra_params(self) -> dict[str, Any]:
        return {"nu": self._nu, "l": self._l, "fut": self._fut_name}

    @classmethod
    def _from_parts(cls, N, S, alloc, d):
        return cls(N, S, alloc, nu=float(d.get("nu", 1.0)),
                   l=float(d.get("l", 1.0)), fut=d.get("fut", "wht"))
