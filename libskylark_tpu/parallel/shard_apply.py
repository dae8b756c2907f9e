"""Sequence-parallel sketch application: explicit shard_map panel pipeline.

The reference's structural analog of long-context parallelism is scaling
the "long" dimension of a matrix past one node's memory: panel-blocked
apply with a lazily materialized operator
(ref: sketch/dense_transform_Elemental_mc_mr.hpp:87-207 panel loop,
sketch/dense_transform_data.hpp:79-152 realize_matrix_view; SURVEY.md §5
"long-context"). This module is that design made TPU-native and
*manually scheduled*: the long axis N is sharded across a mesh axis, each
device walks only its own column blocks of the virtual operator S —
generated on-device from (seed, counter), never at full size — and one
``psum`` combines the partial contractions. Memory per device:
A-shard + one (S_dim × BLOCK_COLS) panel.

This is the shard_map counterpart of the automatic path (plain
``T.apply`` on a sharded array, where XLA chooses the schedule); use it
when the panel pipeline must be explicit — ultra-long N where even the
XLA-fused apply would materialize an (S_dim × N/p) operator shard.

Works for any DenseTransform-backed sketch (JLT, CT, and the dense core
of the feature maps). The returned computation is not pre-jitted — wrap
in ``jax.jit`` at the call site like any other apply.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from libskylark_tpu.base import errors
from libskylark_tpu.parallel.mesh import ROWS
from libskylark_tpu.sketch.dense import BLOCK_COLS, DenseTransform


def _pipeline(T, A, mesh: Mesh, axis: str, seq_axis: int,
              use_pallas: bool | None = None,
              interpret: bool = False) -> jnp.ndarray:
    """Shared schedule: per-device contraction of the device's operator
    column blocks against the local A-shard along ``seq_axis``, then one
    psum (the reference's local-gemm + all_reduce pattern,
    ref: base/Gemm.hpp:84-103).

    Per-device contraction runs through the fused Pallas kernel when the
    backend/distribution qualify (``pallas_dense.fused_partial`` — each
    device receives its slice of the global block-key table via the
    sharded in_spec), else a fori_loop of XLA matmuls over on-the-fly
    panels. Ragged N (not a devices×BLOCK_COLS multiple) is zero-padded
    on the sequence axis — exact for these contractions (the reference's
    np∈{5,7} ragged-layout discipline, ref: tests/unit/CMakeLists.txt:31-33).
    """
    from libskylark_tpu.sketch import params as sketch_params
    from libskylark_tpu.sketch import pallas_dense as pd

    if not isinstance(T, DenseTransform):
        raise errors.UnsupportedError(
            "sequence-parallel apply needs a DenseTransform-backed sketch; "
            f"got {type(T).__name__}"
        )
    A = jnp.asarray(A)
    N = T.input_dim
    if A.shape[seq_axis] != N:
        raise errors.SketchError(
            f"sequence axis has {A.shape[seq_axis]} entries, transform "
            f"expects {N} (A is {A.shape})"
        )
    p = mesh.shape[axis]
    step = p * BLOCK_COLS
    pad_N = -(-N // step) * step
    if pad_N != N:
        pads = [(0, 0), (0, 0)]
        pads[seq_axis] = (0, pad_N - N)
        A = jnp.pad(A, pads)
    blocks_per_shard = pad_N // p // BLOCK_COLS
    s_dim = T.sketch_dim
    columnwise = seq_axis == 0
    if use_pallas is None:
        use_pallas = sketch_params.get_use_pallas()
    # Only take the kernel branch when it can actually run — otherwise
    # the key table is dead weight and the fallback loses vma checking.
    use_pallas = (use_pallas and (interpret or pd.available())
                  and pd.supported(T.dist, A.dtype))

    # Global block-key table, sharded so each device gets its own slice
    # (same bits as T.s_block — see pallas_dense._block_keys).
    keys_all = (pd._block_keys(T._alloc.key_data, pad_N) if use_pallas
                else None)

    def local(A_loc, keys_loc):
        d = lax.axis_index(axis)
        first = d * blocks_per_shard

        part = None
        if keys_loc is not None:
            part = pd.fused_partial(
                keys_loc, T.dist, A_loc, s_dim, seq_axis=seq_axis,
                interpret=interpret,
            )
            if part is not None:
                part = jnp.asarray(T.scale, A_loc.dtype) * part

        if part is None:
            def body(b, acc):
                Sb = T.s_block(first + b, A_loc.dtype)       # (s_dim, BC)
                seg = lax.dynamic_slice_in_dim(
                    A_loc, b * BLOCK_COLS, BLOCK_COLS, axis=seq_axis)
                return acc + (Sb @ seg if columnwise else seg @ Sb.T)

            out_shape = ((s_dim, A_loc.shape[1]) if columnwise
                         else (A_loc.shape[0], s_dim))
            # the carry must be marked device-varying to match the body
            acc0 = lax.pcast(jnp.zeros(out_shape, A_loc.dtype), axis,
                             to="varying")
            part = lax.fori_loop(0, blocks_per_shard, body, acc0)
        return lax.psum(part, axis)

    in_spec = P(axis, None) if columnwise else P(None, axis)
    if keys_all is not None:
        # check_vma off: pallas_call's out_shape carries no varying-axis
        # annotation, which the vma checker (rightly) rejects; the psum
        # above establishes the replicated output explicitly.
        fn = shard_map(local, mesh=mesh, in_specs=(in_spec, P(axis, None)),
                       out_specs=P(None, None), check_vma=False)
        return fn(A, keys_all)
    fn = shard_map(lambda A_loc: local(A_loc, None), mesh=mesh,
                   in_specs=in_spec, out_specs=P(None, None))
    return fn(A)


def columnwise(T, A, mesh: Mesh, axis: str = ROWS,
               use_pallas: bool | None = None,
               interpret: bool = False) -> jnp.ndarray:
    """S·A for A (N, m) sharded on its first (sequence) axis; returns the
    (S_dim, m) result replicated."""
    return _pipeline(T, A, mesh, axis, seq_axis=0,
                     use_pallas=use_pallas, interpret=interpret)


def rowwise(T, A, mesh: Mesh, axis: str = ROWS,
            use_pallas: bool | None = None,
            interpret: bool = False) -> jnp.ndarray:
    """A·Sᵀ for A (m, N) sharded on its second (sequence) axis; returns
    the (m, S_dim) result replicated."""
    return _pipeline(T, A, mesh, axis, seq_axis=1,
                     use_pallas=use_pallas, interpret=interpret)
