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

An apply of a float32 operand on one device under the ``wht`` core is ONE
compiled program (``sketch.fastfood_features``, :func:`fastfood_features`):
the streams from the allocation's key words, the operand laid feature-major
(NB, rows) — there Π is a gather of whole rows and both Hadamard stages
transform axis 0, ``pallas_wht.mix_blocks``' own orientation (``fut.wht_blocks``
off the TPU) — and the numblks blocks walked one at a time, so that the
temporaries are a few (NB, rows) arrays whatever S is; the cosine is
sketch/cos_turns.py. Every other core and dtype, and an operand that lies on
several devices, keeps the eager chain (:func:`_chain_rows`), which stays the
tests' oracle and the serve tier's lane.

Sub-streams: 0=shifts, 1=B, 2=G, 3=permutations, 4=Sm (Matern).
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import jax.random as jr

from libskylark_tpu.base import randgen
from libskylark_tpu.sketch import fut as _fut
from libskylark_tpu.sketch.cos_turns import TURN, cos_turns
from libskylark_tpu.sketch.fut import make_fut
from libskylark_tpu.sketch.rft import _ProgramAllocation
from libskylark_tpu.sketch.transform import (_REGISTRY, SketchTransform,
                                             register)
from libskylark_tpu.telemetry import metrics as _metrics
from libskylark_tpu.telemetry import trace as _trace

_FEATURES = _metrics.counter(
    "sketch.fastfood_features",
    "feature values produced by Fastfood applies, by family and route")

#: Entries (NB × free-axis tile) a grid step of the block kernel holds at
#: most: 2²² is ``pallas_wht``'s own 16384 × 256 (80 MiB of VMEM asked for).
_MIX_STEP_ENTRIES = 1 << 22
#: The widest free-axis tile of a kernel pass, and the free axis' padding a
#: wider tile may cost (a 64th of it: 50,000 rows → 50,176 = 98 × 512).
_MIX_TILE_MAX = 512
_PAD_SHARE = 64
#: Free-axis entries of a stage array the kernel route walks at a time: to
#: 32768 the v5e compiler gathers whole rows of it in one pass; a wider
#: array it cuts in halves first and joins them after (two passes more).
_GATHER_COLS_MAX = 32768


def _precision_pinned_by_user() -> bool:
    """True where the user pinned a matmul precision — the environment
    knob or an ambient ``jax.default_matmul_precision`` — which then
    governs the Fastfood WHT in place of the library's own choice."""
    from libskylark_tpu.base import env as _env
    from libskylark_tpu.base import precision as bprec

    return bool(_env.MATMUL_PRECISION.raw()
                or bprec.ambient_precision_pinned_by_user())


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
    prec = None if _precision_pinned_by_user() else jax.lax.Precision.HIGH
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


def mix_tile(NB: int, m: int) -> int:
    """Free-axis entries a pass of the block kernel takes of a (NB, m)
    stage array: the widest of 512, 256, 128 that the kernel's VMEM plan
    holds beside NB rows and that pads the free axis by at most a 64th."""
    widest = min(_MIX_TILE_MAX, max(128, _MIX_STEP_ENTRIES // NB))
    for tile in (512, 256):
        if tile <= widest and -m % tile <= m // _PAD_SHARE:
            return tile
    return 128


def walk_geometry(m: int, tile: int) -> tuple:
    """``(chunks, steps)`` of the kernel route's walk over m examples: the
    free axis in ``chunks`` chunks of ``steps`` tiles, a chunk at most
    :data:`_GATHER_COLS_MAX` entries and, where such a count divides the
    tiles, every tile starting inside the m examples (the result then has
    no padded row: 50,000 → 2 × 49 tiles of 512)."""
    tiles = -(-m // tile)
    least = -(-tiles * tile // _GATHER_COLS_MAX)
    for chunks in range(least, min(tiles, 4 * least + 4) + 1):
        if tiles % chunks == 0:
            return chunks, tiles // chunks
    return least, -(-tiles // least)


def fastfood_features(key_data, A, *, spec, rowwise: bool, kernel: str,
                      tile: int = 0, passes: int = 3):
    """One Fastfood feature-map apply as a pure function of the transform's
    raw key data ((2,) uint32) and a float32 operand: ``scale · cos(Sm ⊙
    H(G ⊙ Π(H(B ⊙ x))) + shifts)``, block by block, under the ``wht`` core.
    ``spec`` = (sketch_type, N, S, sorted hyper-parameters) rebuilds the
    transform around the traced key, so the streams are the transform's own
    methods on the same sub-streams: the same bits as the eager chain's.

    The operand is laid feature-major, (NB, m) — a rowwise operand
    transposed once, a columnwise one as it comes, N → NB zero rows. There Π is a gather of whole rows and a Hadamard stage transforms
    axis 0. The numblks blocks are walked in a loop, so a block's stage
    arrays are (NB, m), a few of them live, and its finished features — Sm
    and the shifts in turns, :func:`cos_turns` — go into their slab of the
    result in place. ``kernel``:

    * ``"pallas_wht"`` — rowwise on a TPU (:func:`_walk_rows_kernel`): the
      stages on sketch/pallas_wht.py, ``tile`` examples a grid step,
      ``passes`` bfloat16 parts of the operand a product (3: float32-grade;
      1: the ``"bf16"`` regime of sketch/params.py, a control's);
    * ``"xla_bf16x3"`` | ``"xla_f32"`` — ``fut.wht_blocks``: exact bfloat16
      factors against the three-way split operand on a TPU, float32 off it.
    """
    sketch_type, n, s, extra = spec
    T = _REGISTRY[sketch_type]._from_parts(
        n, s, _ProgramAllocation(key_data), dict(extra))
    NB, nb = T._NB, T._numblks
    dt = A.dtype
    B, G, perms = T._B(dt), T._G(dt), T._perms()
    # the phase in turns: (Sm/2π) ⊙ W + shifts/2π, feature by feature
    sm = (T._Sm(dt) * (1.0 / TURN)).reshape(nb, NB)
    sh = jnp.pad(T.shifts(dt) * (1.0 / TURN), (0, nb * NB - s)).reshape(nb, NB)
    if kernel == "pallas_wht":
        out = _walk_rows_kernel(A, B, G, perms, sm, sh, T.scale, tile, passes)
        return out if s == nb * NB else out[:, :s]

    m = A.shape[0] if rowwise else A.shape[1]
    X = jnp.pad(A.T if rowwise else A, ((0, NB - n), (0, 0)))   # (NB, m)
    split = kernel == "xla_bf16x3"

    def block(k):
        """Block k's finished features, (m, NB) rowwise | (NB, m)."""
        Y = _fut.wht_blocks(B[k][:, None] * X, NB, split)
        Y = _fut.wht_blocks(G[k][:, None] * Y[perms[k]], NB, split)
        Y = cos_turns(sm[k][:, None] * Y + sh[k][:, None], T.scale)
        return Y.T if rowwise else Y

    if nb == 1:
        out = block(0)
    else:
        def place(k, out):
            at = (0, k * NB) if rowwise else (k * NB, 0)
            return jax.lax.dynamic_update_slice(out, block(k), at)

        out = jax.lax.fori_loop(
            0, nb, place,
            jnp.zeros((m, nb * NB) if rowwise else (nb * NB, m), dt))
    if s == nb * NB:
        return out
    return out[:, :s] if rowwise else out[:s]


def _walk_rows_kernel(A, B, G, perms, sm, sh, scale: float, tile: int,
                      passes: int):
    """The (m, numblks·NB) features of a rowwise A (m, N) on the block
    kernels. The transposed operand X (N, m) is made once (N up to a
    multiple of 128); a step of the walk is one block k and one chunk c of
    ``cols`` = steps·tile examples: H·(B_k ⊙ X_c)
    (:func:`pallas_wht.mix_chunk`, B riding in the exact ±1 factor, the
    zero rows N → NB the kernel's own), the gather of its rows by Π_k (XLA, at
    most :data:`_GATHER_COLS_MAX` wide, so in one pass), and
    :func:`pallas_wht.mix_cos_rows`: G_k scaling the gathered rows in
    float32, the second stage, the tile turned and finished, written into
    rows c·cols…, columns k·NB… of the result in place — which no pass
    initialises: every entry is written by exactly one step."""
    from libskylark_tpu.sketch import pallas_wht

    m, n = A.shape
    nb, NB = B.shape
    chunks, steps = walk_geometry(m, tile)
    cols = steps * tile
    # wherever every tile of the walk starts among the examples the operand
    # and the result keep their own extent — the last tile overhangs both,
    # reads columns of its own and is cut at m where it is written —, and the
    # zero rows N → NB are the kernel's; else the walk's padded extent
    whole = (chunks * steps - 1) * tile < m
    rows = m if whole else chunks * cols
    X = jnp.pad(A.T, ((0, -n % pallas_wht.GROUP), (0, rows - m)))

    def step(i, Z):
        k, c = i // chunks, i % chunks
        at = jnp.stack([k, c]).astype(jnp.int32)
        Y = pallas_wht.mix_chunk(X, B[k], at, tile=tile, cols=cols,
                                 passes=passes)
        return pallas_wht.mix_cos_rows(Y[perms[k]], G[k], sm[k], sh[k], Z, at,
                                       tile=tile, outscale=scale,
                                       passes=passes)

    Z = jax.lax.fori_loop(0, nb * chunks, step,
                          jax.lax.empty((rows, nb * NB), A.dtype))
    return Z if rows == m else Z[:m]


@functools.lru_cache(maxsize=None)
def _features_program():
    """The compiled apply, built at the first dense operand so that
    importing the sketch layer never pulls the engine."""
    from libskylark_tpu.engine.compiled import compiled

    return compiled(fastfood_features, name="sketch.fastfood_features",
                    static_argnames=("spec", "rowwise", "kernel", "tile",
                                     "passes"))


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

    def features_plan(self, A, rowwise: bool):
        """``(kernel, tile)`` of :func:`fastfood_features` for this operand,
        from the shapes and the device alone, or the reason (a string) the
        eager chain keeps it: another core than ``wht`` (no Kronecker split
        exists for a DCT block), a class the registry does not hold (the bare
        ``FastRFT`` base), another dtype than float32, a matmul precision the
        user pinned (``SKYLARK_MATMUL_PRECISION`` or an ambient
        ``jax.default_matmul_precision``: it governs the chain), an operand that
        lies on more than one device (the chain's batched FUT runs under
        XLA's partitioner; the program's transposed copy would gather it).
        A traced operand shows no placement and is taken as the dense
        sketches take it: the kernel on a process of one device, else the
        XLA route, which is right under any sharding. The block kernels
        serve a rowwise operand with 1024 ≤ NB ≤ 16384 on a TPU
        (``pallas_wht.plan``: eight MXU groups at least, a block its VMEM
        plan holds); elsewhere the stages are ``fut.wht_blocks``, exact
        bfloat16 factors against the three-way split operand on a TPU,
        float32 off it."""
        if self._fut_name != "wht":
            return f"fut={self._fut_name}"
        if _REGISTRY.get(self.sketch_type) is not type(self):
            # the program rebuilds the transform from its registered name
            return f"family={self.sketch_type}"
        if A.dtype != jnp.float32:
            return f"dtype={A.dtype}"
        if _precision_pinned_by_user():
            # a user's matmul-precision pin governs the chain's WHT
            # (:func:`fut_apply_policy`); the program's stages state their own
            return "precision=pinned"
        traced = isinstance(A, jax.core.Tracer)
        if not traced and len(A.devices()) > 1:
            return f"devices={len(A.devices())}"
        on_tpu = jax.default_backend() == "tpu"
        if (on_tpu and rowwise
                and (not traced or jax.device_count() == 1)):
            tile = self.kernel_tile(A.shape[0])
            if tile:
                return "pallas_wht", tile
        return ("xla_bf16x3" if on_tpu else "xla_f32"), 0

    @staticmethod
    def _kernel_passes() -> int:
        """bfloat16 parts of the operand a kernel product takes: all three
        but under ``set_pallas_precision("bf16")``, the regime a user opts
        into for throughput and the benchmark's control runs."""
        from libskylark_tpu.sketch import params as sketch_params

        return 1 if sketch_params.get_pallas_precision() == "bf16" else 3

    def kernel_tile(self, m: int, interpret: bool = False) -> int:
        """The block kernels' free-axis tile for m examples, 0 where
        ``pallas_wht.plan`` declines a (NB, tile) stage array."""
        from libskylark_tpu.sketch import pallas_wht    # pulls pallas

        if self._NB > pallas_wht.BLOCK_ROWS:
            return 0
        tile = mix_tile(self._NB, m)
        served = pallas_wht.plan((self._NB, tile), jnp.float32, interpret)
        return tile if served is not None else 0

    def _features(self, A: jnp.ndarray, rowwise: bool) -> jnp.ndarray:
        """The dense apply: the one ``sketch.fastfood_features`` program
        (under a caller's trace: part of the caller's program), or the
        eager chain where :meth:`features_plan` names a reason."""
        with _trace.span("sketch.plan"):
            plan = self.features_plan(A, rowwise)
        m = A.shape[0] if rowwise else A.shape[1]
        NB, nb = self._NB, self._numblks
        attrs = {"path": "features", "family": self.sketch_type,
                 "route": "chain", "kernel": "xla", "blocks": nb,
                 "block_len": NB, "elements": 2 * nb * NB * m,
                 "finisher": "cos", "features": m * self._S}
        if isinstance(plan, str):
            attrs["reason"] = plan
            if isinstance(A, jax.core.Tracer):
                return self._chain(A, rowwise)
            with _trace.span("sketch.dispatch", attrs):
                out = self._chain(A, rowwise)
        else:
            kernel, tile = plan
            statics = dict(
                spec=(self.sketch_type, self._N, self._S,
                      tuple(sorted(self._extra_params().items()))),
                rowwise=rowwise, kernel=kernel, tile=tile)
            if kernel == "pallas_wht":
                statics["passes"] = self._kernel_passes()
                attrs["passes"] = statics["passes"]
            key_data = self._alloc.key_data
            if isinstance(A, jax.core.Tracer):
                return fastfood_features(key_data, A, **statics)
            attrs.update(route="fastfood_blocks", kernel=kernel, tile=tile,
                         finisher="cos_turns")
            with _trace.span("sketch.dispatch", attrs):
                out = _features_program()(key_data, A, **statics)
        _FEATURES.inc_always(attrs["features"], family=self.sketch_type,
                             route=attrs["route"])
        return out

    def _chain(self, A: jnp.ndarray, rowwise: bool) -> jnp.ndarray:
        # the chain is written for row-major input; the transpose feeds
        # it either way
        return self._features_rows(A) if rowwise else self._features_rows(A.T).T

    def _apply_columnwise(self, A: jnp.ndarray) -> jnp.ndarray:
        return self._features(A, rowwise=False)

    def _apply_rowwise(self, A: jnp.ndarray) -> jnp.ndarray:
        # On a v5e the one program of :func:`fastfood_features`: XLA's
        # transpose and row gather around two passes of sketch/pallas_wht.py
        # (``mix_chunk``, ``mix_cos_rows``) a block and chunk. The fused single-kernel chain of
        # sketch/pallas_fastfood.py (one HBM read of A, one write of the
        # features) is not on this path: Mosaic rejects both its variants
        # on a v5e (PERF.md); it is reachable by explicit pin only.
        return self._features(A, rowwise=True)

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
