"""Pallas TPU kernel: sign and Hadamard-mix a long columnwise axis, block by
block, in one pass over the operand.

The full stages of the FJLT's mix-and-sample contraction (sketch/fut.py,
``wht_blocks``) for an operand whose transform axis is its rows: a grid
step holds ``block`` consecutive rows × ``tile`` columns in VMEM and
leaves there H_block · (D ⊙ A). The axis inside a block folds as (group,
128):

* the inner factor runs on the MXU, group by group: (H_128 · diag(d_g)) ·
  A_g — the Rademacher signs of the group's 128 rows scale the *columns*
  of the ±1 factor, which stays exact in bfloat16, so only the operand is
  split (hi + mid + lo, three single-pass products, float32 accumulation);
* the outer factor is whole-vreg butterflies over the groups on the VPU:
  u ± v of (128 × tile) slabs — no lane or sublane ever moves.

The operand is read once and the mixed matrix written once (the one
workspace of an apply); ``fut.sample_outer`` then gathers the sampled rows
of the last, across-block factor. VMEM is bounded by the block, not by
the axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from libskylark_tpu.sketch.cos_turns import cos_turns
from libskylark_tpu.sketch.fut import _hadamard_np

GROUP = 128                 # rows the MXU factor mixes: the MXU's side
_SLAB = 32                  # rows a butterfly step holds of each group
_LANES = 128
#: rows × columns a grid step holds. The scoped VMEM asked for is the in and
#: out tiles double-buffered (4 × 16 MiB at 16384 × 256) plus the per-group
#: temporaries; a v5e core has 128 MiB. On a v5e (PR 39, host ms a pass over
#: 2²⁰ × 1024 + the gather behind it, then 128 rows a step): 16384 × 128 21.1 +
#: 5.3, 8192 × 256 15.8 + 9.9, 16384 × 256 16.9 + 5.3 (traced 15.3 + 4.3; PR 50,
#: 256 rows a step: 15.3 + 2.9), 4096 × 512 14.2 + ≈ 20; a copy alone 13.8.
BLOCK_ROWS = 16384
TILE_COLS = 256
_VMEM_SLACK_BYTES = 16 * 1024 * 1024


def plan(shape: tuple, dtype, interpret: bool = False):
    """(block, tile) when the kernel serves a columnwise operand of
    ``shape`` = (N, m), else None: a TPU (or interpret mode), float32, N a
    power of two of at least 8 groups, m a multiple of a lane's width."""
    n, m = shape
    if not interpret and jax.default_backend() != "tpu":
        return None
    if jnp.dtype(dtype) != jnp.float32 or n & (n - 1):
        return None
    if n < 8 * GROUP or m % _LANES:
        return None
    return min(n, BLOCK_ROWS), TILE_COLS if m % TILE_COLS == 0 else _LANES


def _split_dot(hd, x, passes: int = 3):
    """hd (bf16, exact) · x (float32) at float32 grade: x = hi + mid + lo
    (``passes`` 1: hi alone, the ``"bf16"`` regime of sketch/params.py)."""
    def dot(part):
        return jax.lax.dot_general(
            hd, part, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)

    hi = x.astype(jnp.bfloat16)
    if passes == 1:
        return dot(hi)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return dot(hi) + (dot(mid) + dot(lo))


def _kernel(groups: int, h_ref, d_ref, a_ref, y_ref, passes: int = 3):
    """``a_ref`` may hold fewer groups of rows than ``groups``: the rows it
    lacks are zeros (a transform axis padded to the block's power of two),
    which no pass writes to HBM or reads back."""
    def mix(g, carry):
        rows = pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP)
        hd = (h_ref[...] * d_ref[pl.ds(g, 1), :]).astype(jnp.bfloat16)
        y_ref[rows, :] = _split_dot(hd, a_ref[rows, :], passes)
        return carry

    held = a_ref.shape[0] // GROUP
    jax.lax.fori_loop(0, held, mix, 0)
    if held < groups:
        y_ref[held * GROUP:, :] = jnp.zeros(
            ((groups - held) * GROUP, y_ref.shape[1]), jnp.float32)
    _butterflies(groups, y_ref)


def _butterflies(groups: int, y_ref):
    """H_groups ⊗ I_128 over the rows of ``y_ref``, in place."""
    # the factor over the groups: butterflies of whole (rows × tile) slabs,
    # two stages a pass where two are left (one load and one store a vreg
    # for both), _SLAB rows at a time so that a quad's sixteen vregs and
    # their sums stay in registers
    subs = GROUP // _SLAB
    h = 1
    while h < groups:
        radix = 4 if 2 * h < groups else 2

        def fly(i, carry, h=h, radix=radix):
            t, sub = i // subs, i % subs
            g0 = (t // h) * (radix * h) + t % h

            def at(k):
                return pl.ds(pl.multiple_of(
                    (g0 + k * h) * GROUP + sub * _SLAB, _SLAB), _SLAB)

            x = [y_ref[at(k), :] for k in range(radix)]
            if radix == 2:
                y_ref[at(0), :] = x[0] + x[1]
                y_ref[at(1), :] = x[0] - x[1]
                return carry
            s0, d0, s1, d1 = x[0] + x[1], x[0] - x[1], x[2] + x[3], x[2] - x[3]
            y_ref[at(0), :] = s0 + s1
            y_ref[at(1), :] = d0 + d1
            y_ref[at(2), :] = s0 - s1
            y_ref[at(3), :] = d0 - d1
            return carry

        jax.lax.fori_loop(0, groups // radix * subs, fly, 0)
        h *= radix


def _features_kernel(groups: int, outscale: float, passes: int, at_ref, h_ref,
                     g_ref, sm_ref, sh_ref, a_ref, z_any, z_ref, y_ref):
    """A Fastfood block's second stage and its finish on one tile of
    examples: ``outscale · cos(2π(sm ⊙ H(g ⊙ a) + sh))``, transposed into
    the row-major result's (tile, block) slab. ``g`` scales the operand in
    float32 before the split (a Gaussian is not exact in bfloat16, so it
    cannot ride in the factor as the signs do); ``sm`` and ``sh`` are in
    turns. ``at_ref`` and ``z_any`` only place the slab (index maps, alias)."""
    del at_ref, z_any
    hb = h_ref[...].astype(jnp.bfloat16)

    def mix(g, carry):
        rows = pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP)
        y_ref[rows, :] = _split_dot(hb, a_ref[rows, :] * g_ref[rows, :],
                                    passes)
        return carry

    jax.lax.fori_loop(0, groups, mix, 0)
    _butterflies(groups, y_ref)
    # a group's (128 features × tile examples) slab turned, then finished
    # with the features along the lanes: static slices, whole vregs
    for g in range(groups):
        lanes = slice(g * GROUP, (g + 1) * GROUP)
        t = y_ref[lanes, :].T * sm_ref[g:g + 1, :] + sh_ref[g:g + 1, :]
        z_ref[:, lanes] = cos_turns(t, outscale)


@functools.partial(jax.jit, static_argnames=("block", "tile", "interpret"))
def mix_blocks(A, D, *, block: int, tile: int, interpret: bool = False):
    """H_block · (D ⊙ A) inside each block of ``block`` rows of A (N, m),
    unnormalized, as a float32 (N, m) array. ``D`` (N,) holds the signs."""
    n, m = A.shape
    groups = block // GROUP
    return pl.pallas_call(
        functools.partial(_kernel, groups),
        grid=(n // block, m // tile),
        in_specs=[
            pl.BlockSpec((GROUP, GROUP), lambda p, j: (0, 0)),
            pl.BlockSpec((groups, GROUP), lambda p, j: (p, 0)),
            pl.BlockSpec((block, tile), lambda p, j: (p, j)),
        ],
        out_specs=pl.BlockSpec((block, tile), lambda p, j: (p, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=16 * block * tile + _VMEM_SLACK_BYTES),
        interpret=interpret,
    )(jnp.asarray(_hadamard_np(GROUP), jnp.float32),
      D.astype(jnp.float32).reshape(n // GROUP, GROUP), A)


def _hadamard_group():
    return jnp.asarray(_hadamard_np(GROUP), jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("tile", "cols", "passes", "interpret"))
def mix_chunk(X, D, at, *, tile: int, cols: int, passes: int = 3,
              interpret: bool = False):
    """:func:`mix_blocks` of one block and one chunk of the free axis:
    H_N · (D ⊙ X̃[:, c·cols:(c + 1)·cols]) as a float32 (N, cols) array, N =
    ``D``'s length one block, X̃ the operand X (rows, m) with zero rows up to
    N (rows a multiple of 128: the zeros are the kernel's, not an array's);
    ``at`` = (·, c) int32 places the chunk (an index map's offset: no slice
    of X is taken). A tile that overhangs X's m columns reads what lies
    there into columns of its own — a column's transform is its own — which
    the caller cuts."""
    n, held = D.shape[0], X.shape[0]
    groups, steps = n // GROUP, cols // tile
    return pl.pallas_call(
        lambda at_ref, *refs: _kernel(groups, *refs, passes=passes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(steps,),
            in_specs=[
                pl.BlockSpec((GROUP, GROUP), lambda j, at: (0, 0)),
                pl.BlockSpec((groups, GROUP), lambda j, at: (0, 0)),
                pl.BlockSpec((held, tile),
                             lambda j, at: (0, at[1] * steps + j)),
            ],
            out_specs=pl.BlockSpec((n, tile), lambda j, at: (0, j))),
        out_shape=jax.ShapeDtypeStruct((n, cols), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=16 * n * tile + _VMEM_SLACK_BYTES),
        interpret=interpret,
    )(at, _hadamard_group(), D.astype(jnp.float32).reshape(groups, GROUP), X)


@functools.partial(
    jax.jit, static_argnames=("tile", "outscale", "passes", "interpret"))
def mix_cos_rows(Y, g, sm, sh, Z, at, *, tile: int, outscale: float,
                 passes: int = 3, interpret: bool = False):
    """A Fastfood block's second Hadamard stage and cosine, written into the
    row-major result in place: rows [(c·steps + j)·tile, …) × columns
    [k·N, (k + 1)·N) of ``Z`` (rows, blocks·N) become ``outscale ·
    cos(2π(sm ⊙ H_N(g ⊙ Y) + sh))ᵀ`` for Y (N, cols) feature-major, N one
    block, ``at`` = (k, c) int32; every other entry of ``Z`` is kept. A tile
    that overhangs Z's rows is cut at them."""
    n, cols = Y.shape
    groups, steps = n // GROUP, cols // tile

    def feature(v):                             # (n,) → the groups' lanes
        return v.astype(jnp.float32).reshape(groups, GROUP)

    return pl.pallas_call(
        functools.partial(_features_kernel, groups, outscale, passes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(steps,),
            in_specs=[
                pl.BlockSpec((GROUP, GROUP), lambda j, at: (0, 0)),
                pl.BlockSpec((n, 1), lambda j, at: (0, 0)),
                pl.BlockSpec((groups, GROUP), lambda j, at: (0, 0)),
                pl.BlockSpec((groups, GROUP), lambda j, at: (0, 0)),
                pl.BlockSpec((n, tile), lambda j, at: (0, j)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (tile, n), lambda j, at: (at[1] * steps + j, at[0])),
            scratch_shapes=[pltpu.VMEM((n, tile), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(Z.shape, jnp.float32),
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=20 * n * tile + _VMEM_SLACK_BYTES),
        interpret=interpret,
    )(at, _hadamard_group(), g.astype(jnp.float32).reshape(n, 1),
      feature(sm), feature(sh), Y, Z)
