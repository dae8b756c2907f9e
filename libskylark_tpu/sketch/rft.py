"""Random Fourier feature transforms: GaussianRFT, LaplacianRFT, MaternRFT.

TPU-native analog of ref: sketch/RFT_data.hpp:25-354, sketch/RFT_Elemental.hpp:62-332.
Rahimi-Recht random features: z(x) = outscale · cos(scales ⊙ (W x) + b), with
W an i.i.d. dense matrix scaled by ``inscale`` (kernel-specific distribution),
b ~ U[0, 2π), and per-row ``scales`` that default to 1 (Matern overrides them
with sqrt(2ν / χ²(2ν)) samples to realize multivariate-t frequencies,
ref: RFT_data.hpp:335-346).

A dense apply is ONE compiled program (``sketch.rft_features``,
:func:`rft_features`): frequencies, scales and shifts generated from the
allocation's key, the projection and the featurization — the hand-written
OpenMP elementwise loops of the reference (ref: RFT_Elemental.hpp:83-156)
disappear. On a TPU a rowwise apply of normal frequencies runs that program
as the fused kernels of sketch/pallas_dense.py (the operator generated once
an apply, the contraction, and the cos finishing each result tile in VMEM —
sketch/cos_turns.py: the phase formed in turns, reduced exactly, one short
polynomial; the result tiled along s where s_dim is wide); everywhere else
it is W, one XLA matmul and the elementwise tail with the stock ``jnp.cos``,
fused by XLA.

Sub-streams of the allocation: 0 = W entries, 1 = shifts, 2 = scales (Matern).
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from libskylark_tpu.base import randgen
from libskylark_tpu.base import threefry as tf
from libskylark_tpu.sketch.dense import BLOCK_COLS
from libskylark_tpu.sketch.transform import (_REGISTRY, OperatorCache,
                                             SketchTransform, note_apply,
                                             register)
from libskylark_tpu.telemetry import metrics as _metrics
from libskylark_tpu.telemetry import trace as _trace

_FEATURES = _metrics.counter(
    "sketch.features",
    "feature values produced by dense RFT applies, by family and kernel")


class _ProgramAllocation:
    """Stands in for the transform's Allocation inside the compiled
    program: the key words are the program's argument, so one executable
    serves every transform of a family and shape."""

    def __init__(self, key_data):
        self.key_data = key_data

    @property
    def key(self):
        return jax.random.wrap_key_data(self.key_data)

    def child(self, tag: int) -> "_ProgramAllocation":
        return _ProgramAllocation(tf.fold_in(self.key_data, tag))


def rft_features(key_data, A, *pinned, spec, rowwise: bool, plan=None):
    """One dense feature-map apply as a pure function of the transform's
    raw key data ((2,) uint32): frequencies, scales and shifts from the
    key, projection, featurization. ``spec`` = (sketch_type, N, S, sorted
    hyper-parameters) rebuilds the transform around the traced key;
    ``pinned`` is the materialized W where the caller holds one. ``plan``
    (a :class:`pallas_dense.Plan`) runs the rowwise projection and the cos
    on the fused kernels (:func:`pallas_dense.features_rows`); None is the
    XLA route."""
    sketch_type, n, s, extra = spec
    T = _REGISTRY[sketch_type]._from_parts(
        n, s, _ProgramAllocation(key_data), dict(extra))
    if plan is not None:
        from libskylark_tpu.sketch import pallas_dense

        return pallas_dense.features_rows(
            T._alloc.child(0).key_data, T.dist, A, s, T.inscale, T.outscale,
            T.row_scales(jnp.float32), T.shifts(jnp.float32),
            plan).astype(A.dtype)
    W = pinned[0] if pinned else T.w_panel(0, n, A.dtype)
    if rowwise:
        return T._featurize(A @ W.T, feature_axis=1)
    return T._featurize(W @ A, feature_axis=0)


@functools.lru_cache(maxsize=None)
def _features_program():
    """The compiled apply, built at the first dense operand so that
    importing the sketch layer never pulls the engine."""
    from libskylark_tpu.engine.compiled import compiled

    return compiled(rft_features, name="sketch.rft_features",
                    static_argnames=("spec", "rowwise", "plan"))


class RFT(OperatorCache, SketchTransform):
    """Base random-Fourier-feature transform. ``materialize()`` pins the
    frequency matrix W (OperatorCache) — the serving-predict /
    repeated-featurization reuse regime."""

    def _full_operator(self, dtype) -> jnp.ndarray:
        return self.w_panel(0, self._N, dtype)

    def _materialize_changes_numerics(self, A, seq_axis=None) -> bool:
        from libskylark_tpu.sketch.dense import pallas_serves_eager

        return (seq_axis != 0 and self._kernel_family()
                and pallas_serves_eager(A, self.dist, self._S, 1))

    sketch_type = "RFT"
    dist: randgen.Distribution = randgen.Normal()
    epilogue = "cos"    # the elementwise map of _featurize, by name

    @property
    def inscale(self) -> float:
        raise NotImplementedError

    @property
    def outscale(self) -> float:
        return math.sqrt(2.0 / self._S)

    def w_panel(self, col_start: int, col_stop: int, dtype=jnp.float32) -> jnp.ndarray:
        """W[:, col_start:col_stop] — lazy (S × N) frequency matrix
        (the 'underlying dense transform', ref: RFT_data.hpp:76-80)."""
        return self.inscale * randgen.dense_panel(
            self.subkey(0), self.dist, self._S, col_start, col_stop, BLOCK_COLS, dtype
        )

    def s_block(self, block_id, dtype=jnp.float32) -> jnp.ndarray:
        """Column block of W (traced id ok) — the DenseTransform block
        protocol, so the distributed-sparse panel machinery
        (sketch/dist_sparse_apply.py) applies to frequency matrices too."""
        return self.inscale * randgen.dense_block(
            self.subkey(0), self.dist, self._S, block_id, BLOCK_COLS, dtype
        )

    def shifts(self, dtype=jnp.float32) -> jnp.ndarray:
        return randgen.stream_slice(
            self.subkey(1), randgen.Uniform(0.0, 2.0 * math.pi), 0, self._S,
            dtype=dtype,
        )

    def row_scales(self, dtype=jnp.float32) -> jnp.ndarray:
        """Per-feature scaling; 1 unless a kernel subclass overrides
        (ref: RFT_data.hpp:84-86)."""
        return jnp.ones((self._S,), dtype)

    def _featurize(self, WA: jnp.ndarray, feature_axis: int) -> jnp.ndarray:
        dt = WA.dtype
        shape = [1, 1]
        shape[feature_axis] = self._S
        sc = self.row_scales(dt).reshape(shape)
        sh = self.shifts(dt).reshape(shape)
        return self.outscale * jnp.cos(WA * sc + sh)

    def _apply_columnwise(self, A: jnp.ndarray) -> jnp.ndarray:
        self._note_eager_apply(A, seq_axis=0)
        return self._features(A, rowwise=False)

    def _apply_rowwise(self, A: jnp.ndarray) -> jnp.ndarray:
        self._note_eager_apply(A, seq_axis=1)
        return self._features(A, rowwise=True)

    def _kernel_family(self) -> bool:
        """Normal-frequency cos maps only (Gaussian/Matern): Cauchy
        frequencies (Laplacian) produce heavy-tailed phases where f32
        ``cos`` is ill-conditioned, so the kernel's contraction order
        breaks the 1e-4 oracle — those keep XLA's float32 ``highest``
        projection."""
        return self.epilogue == "cos" and type(self.dist) is randgen.Normal

    def _kernel_plan(self, A, interpret: bool = False):
        """The static ``plan`` of :func:`rft_features` when the fused
        kernels serve this rowwise apply (pallas_dense._plan: a TPU,
        float32, a single device, a tile plan from the shapes), else None."""
        from libskylark_tpu.sketch.dense import pallas_ambient_ok

        if not self._kernel_family() or not pallas_ambient_ok(A):
            return None
        from libskylark_tpu.sketch import pallas_dense

        return pallas_dense._plan(self.dist, A, self._S, 1, None, None,
                                  interpret, epilogue=True)

    def _features(self, A: jnp.ndarray, rowwise: bool) -> jnp.ndarray:
        """The dense apply: one ``sketch.rft_features`` program — the
        kernel route (rowwise, nothing pinned, :meth:`_kernel_plan`) or
        the XLA route, W generated in the program or the pinned one."""
        W = self._cached_op(A.dtype)
        plan = self._kernel_plan(A) if rowwise and W is None else None
        rows = A.shape[0] if rowwise else A.shape[1]
        # finisher: what computes the elementwise map — the stock
        # jnp.cos / jnp.exp of _featurize, or the kernels' cos_turns
        attrs = {"path": "features", "family": self.sketch_type,
                 "epilogue": self.epilogue, "finisher": self.epilogue,
                 "kernel": "xla", "features": rows * self._S}
        if plan is not None:
            attrs.update(
                kernel=("pallas_planes" if plan.operator_residency == "hbm"
                        else "pallas_generate"),
                finisher="cos_turns",
                m_tile=plan.m_tile, s_tile=plan.s_tile,
                operator_residency=plan.operator_residency)
        else:
            note_apply(path="cached_op" if W is not None else "xla_full")
        key_data = self._alloc.key_data
        args = (key_data, A) if W is None else (key_data, A, W)
        spec = (self.sketch_type, self._N, self._S,
                tuple(sorted(self._extra_params().items())))
        if any(isinstance(a, jax.core.Tracer) for a in args):
            # inside a caller's trace: part of the caller's program
            return rft_features(*args, spec=spec, rowwise=rowwise, plan=plan)
        with _trace.span("sketch.dispatch", attrs):
            out = _features_program()(*args, spec=spec, rowwise=rowwise,
                                      plan=plan)
        _FEATURES.inc_always(attrs["features"], family=self.sketch_type,
                             kernel=attrs["kernel"])
        return out

    # -- sparse input: project with the segment-sum spmm kernels --

    def _apply_columnwise_sparse(self, A) -> jnp.ndarray:
        from libskylark_tpu.base.sparse import spmm_t

        W = self._op_or(A.device_dtype,
                        lambda dt: self.w_panel(0, self._N, dt))
        return self._featurize(spmm_t(A, W.T).T, feature_axis=0)

    def _apply_rowwise_sparse(self, A) -> jnp.ndarray:
        from libskylark_tpu.base.sparse import spmm

        W = self._op_or(A.device_dtype,
                        lambda dt: self.w_panel(0, self._N, dt))
        return self._featurize(spmm(A, W.T), feature_axis=1)

    # -- distributed sparse input: project with the per-cell virtual
    # panel machinery, then featurize (ref: the mixed sparse-input
    # RFT specializations, sketch/RFT.hpp dispatch) --

    def _apply_columnwise_dist_sparse(self, A) -> jnp.ndarray:
        from libskylark_tpu.sketch import dist_sparse_apply as dsa

        return self._featurize(dsa.dense_columnwise(self, A),
                               feature_axis=0)

    def _apply_rowwise_dist_sparse(self, A) -> jnp.ndarray:
        from libskylark_tpu.sketch import dist_sparse_apply as dsa

        return self._featurize(dsa.dense_rowwise(self, A), feature_axis=1)


@register
class GaussianRFT(RFT):
    """Gaussian-kernel random features: W ~ N(0,1), inscale 1/σ
    (ref: RFT_data.hpp:117-145)."""

    sketch_type = "GaussianRFT"
    dist = randgen.Normal()

    def __init__(self, N, S, context, sigma: float = 1.0):
        self._sigma = float(sigma)
        super().__init__(N, S, context)

    @property
    def inscale(self) -> float:
        return 1.0 / self._sigma

    def _extra_params(self) -> dict[str, Any]:
        return {"sigma": self._sigma}

    @classmethod
    def _from_parts(cls, N, S, alloc, d):
        return cls(N, S, alloc, sigma=float(d.get("sigma", 1.0)))


@register
class LaplacianRFT(RFT):
    """Laplacian-kernel random features: W ~ Cauchy, inscale 1/σ
    (ref: RFT_data.hpp:192-247)."""

    sketch_type = "LaplacianRFT"
    dist = randgen.Cauchy()

    def __init__(self, N, S, context, sigma: float = 1.0):
        self._sigma = float(sigma)
        super().__init__(N, S, context)

    @property
    def inscale(self) -> float:
        return 1.0 / self._sigma

    def _extra_params(self) -> dict[str, Any]:
        return {"sigma": self._sigma}

    @classmethod
    def _from_parts(cls, N, S, alloc, d):
        return cls(N, S, alloc, sigma=float(d.get("sigma", 1.0)))


@register
class MaternRFT(RFT):
    """Matern-kernel random features: multivariate-t frequencies — normal W
    with per-row scales sqrt(2ν / χ²(2ν)) (ref: RFT_data.hpp:320-346)."""

    sketch_type = "MaternRFT"
    dist = randgen.Normal()

    def __init__(self, N, S, context, nu: float = 1.0, l: float = 1.0):
        self._nu = float(nu)
        self._l = float(l)
        super().__init__(N, S, context)

    @property
    def inscale(self) -> float:
        return 1.0 / self._l

    def row_scales(self, dtype=jnp.float32) -> jnp.ndarray:
        # chi^2(2nu) == Gamma(shape=nu, scale=2)
        chi2 = randgen.stream_slice(
            self.subkey(2),
            randgen.Gamma(shape_param=self._nu, scale=2.0),
            0,
            self._S,
            dtype=dtype,
        )
        return jnp.sqrt(2.0 * self._nu / jnp.maximum(chi2, jnp.finfo(dtype).tiny))

    def _extra_params(self) -> dict[str, Any]:
        return {"nu": self._nu, "l": self._l}

    @classmethod
    def _from_parts(cls, N, S, alloc, d):
        return cls(N, S, alloc, nu=float(d.get("nu", 1.0)), l=float(d.get("l", 1.0)))


@register
class ExpSemigroupRLT(RFT):
    """Random Laplace features for the exponential semigroup kernel
    (Yang et al., ref: sketch/RLT_data.hpp:94-160, sketch/RLT_Elemental.hpp:77):
    z(x) = sqrt(1/S) · exp(−(W x)), W ~ (β²/2)·StandardLevy.

    Inputs must be nonnegative (the semigroup kernel's domain is R+); negative
    coordinates make −Wx arbitrarily large and overflow exp, exactly as the
    reference's ``exp(-val)`` would. Shares RFT's lazy-W machinery; only the
    elementwise feature map differs (exp(−·) instead of cos(·+shift))."""

    sketch_type = "ExpSemigroupRLT"
    dist = randgen.StandardLevy()

    def __init__(self, N, S, context, beta: float = 1.0):
        self._beta = float(beta)
        super().__init__(N, S, context)

    @property
    def inscale(self) -> float:
        return self._beta * self._beta / 2.0

    @property
    def outscale(self) -> float:
        return math.sqrt(1.0 / self._S)

    epilogue = "exp"

    def _featurize(self, WA: jnp.ndarray, feature_axis: int) -> jnp.ndarray:
        return self.outscale * jnp.exp(-WA)

    def _extra_params(self) -> dict[str, Any]:
        return {"beta": self._beta}

    @classmethod
    def _from_parts(cls, N, S, alloc, d):
        return cls(N, S, alloc, beta=float(d.get("beta", 1.0)))
