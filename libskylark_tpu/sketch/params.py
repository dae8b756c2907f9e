"""Global sketch tuning knobs (ref: sketch/sketch_params.hpp:15-36).

``blocksize`` — column-panel width for memory-bounded dense apply (0 disables
blocking: "better performance, much more memory", ref comment). The reference
default is 1000 columns; we default to 0 (unblocked) because XLA fuses
generation into the matmul and HBM is large — callers with huge N opt in.

``factor`` — regime-selection threshold for distributed apply
(ref: sketch/sketch_params.hpp:19).
"""

from libskylark_tpu.base import env as _env

_blocksize = 0
_factor = 20


def get_blocksize() -> int:
    return _blocksize


def set_blocksize(b: int) -> None:
    global _blocksize
    _blocksize = int(b)


def get_factor() -> int:
    return _factor


def set_factor(f: int) -> None:
    global _factor
    _factor = int(f)


# ``auto_block_bytes`` — even with ``blocksize`` unset (0), a dense apply
# whose full virtual operator would exceed this many bytes switches to
# the panel-blocked path automatically (the memory-safety default the
# reference gets from blocksize=1000; our default unblocked mode is the
# fast path for everything that fits comfortably in HBM).
_auto_block_bytes = 2 << 30  # 2 GiB


def get_auto_block_bytes() -> int:
    return _auto_block_bytes


def set_auto_block_bytes(b: int) -> None:
    b = int(b)
    if b <= 0:
        raise ValueError(f"auto_block_bytes must be positive, got {b}")
    global _auto_block_bytes
    _auto_block_bytes = b


# ``use_pallas`` — route dense-transform applies through the fused Pallas
# TPU kernel (sketch/pallas_dense.py) when the input/backend qualify. The
# sketch operator entries are bit-exact either way; only the contraction
# precision differs (see ``pallas_precision``).
_use_pallas = True


def get_use_pallas() -> bool:
    return _use_pallas


def set_use_pallas(on: bool) -> None:
    global _use_pallas
    _use_pallas = bool(on)


# ``pallas_precision`` — contraction regime inside the fused kernel.
# "bf16x3" (default): 3-pass error-compensated bf16 split — f32-grade
# rounding at roughly twice the MXU rate of full-f32 passes;
# checked ON CHIP against the XLA path at 1e-4
# (tests/test_pallas_dense.py::test_fused_on_chip_matches_xla and, at
# the headline width, chip_smoke.py). "f32": full-f32 passes
# (Precision.HIGHEST), the conservative regime. "bf16": single-pass bf16
# inputs + f32 accumulation — fastest, but rounds the contraction at
# ~2⁻⁸ relative (outside the oracle for large N); throughput-only work
# opts in explicitly. "bf16gen2": the OPERATOR is defined as
# scale × bf16-rounding of the UNIT generated stream — rounding applies
# to the unit-variance entries before the f32 scale multiply
# (statistically equivalent sketch — a Gaussian rounded at 2⁻⁸ keeps
# its JL guarantee; deterministic and seed-reproducible like every
# regime) — and only the DATA side is
# error-compensated (hi/lo, 2 passes): f32-grade accuracy w.r.t. that
# operator at 2/3 the MXU passes of bf16x3 (pass-count ceiling 216 vs
# 144 GB/s on the headline config). Because its operator VALUES differ
# from the f32 stream at ~2⁻⁸, it is strictly opt-in and its oracle
# compares against an XLA apply of the SAME rounded operator
# (tests/test_pallas_dense.py).
_pallas_precision = "bf16x3"


def get_pallas_precision() -> str:
    return _pallas_precision


def set_pallas_precision(p: str) -> None:
    if p not in ("f32", "bf16x3", "bf16", "bf16gen2"):
        raise ValueError(
            "pallas_precision must be 'f32', 'bf16x3', 'bf16' or "
            f"'bf16gen2', got {p!r}"
        )
    global _pallas_precision
    _pallas_precision = p


# ``pallas_m_tile`` — rows (columnwise: columns) of A per fused-kernel
# grid step. What a larger tile buys depends on where the operator lives
# between m-tiles (pallas_dense.operator_residency). Measured on a v5e at
# 65536 × 8192 → 1024, bf16x3, ms a blocking apply (PERF.md §6, PR 27):
#   regenerated in every m-tile sweep (no residency does that to a big
#   S any more — "per_tile" is a single tile: the whole virtual operator
#   on the VPU, Threefry + inverse-CDF at ≈ 46 G entries/s, and a step
#   costs generation PLUS matmul): 43.3 / 31.7 / 26.1 / 22.7 at m_tile
#   512 / 1024 / 2048 / 4096, i.e. device time 17.2 + 23.2 × 512/m_tile;
#   "hbm" (big S: generated once an apply in 0.42 ms, the tile only sets
#   how often the planes are read back and how many grid steps run,
#   0.37 µs each): 24.8 / 23.1 / 22.9 / 22.0 at 256 / 512 / 1024 / 2048,
#   and 22.1 / 21.4 / 21.2 at 512 / 1024 / 2048 with the two-block k
#   step the kernel now takes (pallas_dense._plane_step_cols).
#   Columnwise, 8192 × 65536 → 1024 × 65536 (PR 36): 42.7 regenerated,
#   20.2 under "hbm" at 512 columns a tile and a step, 21.4 at 256 × 512
#   or 512 × 256, 23.0 at 256 × 256.
#   With the limit the package passes since PR 49 (PERF.md §5 and §6):
#   20.08 / 19.57 / 19.28 / 21.54 rowwise and 19.99 / 19.51 / 19.31 / 21.47
#   columnwise at 512 / 1024 / 2048 / 4096 — the gain flattens at 2048
#   and 4096 rows (101 MiB of VMEM asked) are slower than 512; the
#   results of all four tiles are bit-equal. "f32" rowwise alone loses
#   (41.0 at 2048 against 37.0 at 512) and keeps 512.
# 512 is the largest power of two whose plan fits Mosaic's 16 MiB default
# scoped VMEM at s_dim = 1024 (_vmem_estimate plans 11 MiB, Mosaic needs
# 9.4–9.9); 1024 needs 17.0 MiB and Mosaic refused it on the chip. A v5e
# core has 128 MiB of VMEM, and since PR 49 the one call the larger tile
# pays in — the "hbm" residency's contraction over several k steps,
# pallas_dense._planes_call — passes ``vmem_limit_bytes``: its own
# fitted plan plus a slack, under a cap of half the core's VMEM as
# pltpu.get_tpu_info() reports it (pallas_dense._vmem_cap; no TPU to ask,
# or a 16 MiB core: no growth, every plan as before). So the setter's
# default is None, "the planner's choice": 512 as the request every
# other kernel's plan starts from, grown for that contraction to the
# largest power of two ≤ 2048 that divides the operand's tiled extent and
# fits the cap (pallas_dense._grown_rows; 2048 at the headline shape). A
# number — ``m_tile=`` at the call site or set_pallas_m_tile — is a
# REQUEST, as it always was: fitted to the operand and shrunk where the
# default scope refuses it (_qualify), never grown; set 512 to pin the
# old plan. (A request above 512 that the scope lets through, s_dim ≤ 768,
# now runs at the 512-row plan's k step under a limit of its own: the same
# bits as 512 rows give.) A sweep passes ``m_tile=`` or calls
# set_pallas_m_tile.
_pallas_m_tile = None


def get_pallas_m_tile() -> int | None:
    return _pallas_m_tile


def set_pallas_m_tile(t: int | None) -> None:
    """Request a row tile, or None to hand the choice back to the
    planner."""
    if t is not None:
        t = int(t)
        if t < 8:
            raise ValueError(f"pallas_m_tile must be >= 8, got {t}")
    global _pallas_m_tile
    _pallas_m_tile = t


# ``auto_materialize`` — automatic materialize-and-reuse dispatch for
# OperatorCache transforms: the Nth EAGER apply of one transform
# instance pins its operator in device memory (jit-traced applies never
# count — a trace runs once). The steady-state-serving complement of the
# virtual-operator default: one-shot sketches keep paying zero HBM,
# repeated applies amortize generation to zero automatically. Bounded by
# ``auto_materialize_bytes`` so huge operators (which the blocked apply
# exists for) never pin. Auto-pinning only ever happens where the
# materialized apply is the SAME contraction as the virtual one (the
# plain XLA path); applies that route through the fused TPU kernel are
# never auto-switched — the kernel's bf16x3/accumulation-order numerics
# differ from a cached gemm, and the Nth eager apply must not silently
# change results vs the first (OperatorCache._materialize_changes_numerics;
# explicit materialize() remains the visible way to choose the cached
# regime on TPU). SKYLARK_AUTO_MATERIALIZE=0 disables the dispatch.
_auto_materialize = _env.AUTO_MATERIALIZE.get()
_auto_materialize_after = 3
_auto_materialize_bytes = 64 * 1024 * 1024


def get_auto_materialize() -> bool:
    return _auto_materialize


def set_auto_materialize(on: bool) -> None:
    global _auto_materialize
    _auto_materialize = bool(on)


def get_auto_materialize_after() -> int:
    return _auto_materialize_after


def set_auto_materialize_after(n: int) -> None:
    n = int(n)
    if n < 1:
        raise ValueError(f"auto_materialize_after must be >= 1, got {n}")
    global _auto_materialize_after
    _auto_materialize_after = n


def get_auto_materialize_bytes() -> int:
    return _auto_materialize_bytes


def set_auto_materialize_bytes(b: int) -> None:
    b = int(b)
    if b <= 0:
        raise ValueError(
            f"auto_materialize_bytes must be > 0, got {b} "
            "(use set_auto_materialize(False) to disable the dispatch)")
    global _auto_materialize_bytes
    _auto_materialize_bytes = b
