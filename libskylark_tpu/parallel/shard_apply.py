"""The dense sketch of a distributed matrix: one compiled shard_map program.

The reference's hot distributed primitive is ``JLT_t::apply`` on an
``[MC,MR]`` matrix (ref: sketch/dense_transform_Elemental_mc_mr.hpp:61-207;
SURVEY.md §3.1): each rank realizes the panels of S that face its local
columns from the counter stream (``realize_matrix_view``), runs a local gemm,
and ``El::AxpyContract`` reduce-scatters the partial products. Here a process
grid is a ``jax.sharding.Mesh`` and a distribution a ``NamedSharding``
(parallel/mesh.py), and that apply is ONE program, ``sketch.dense_mesh``
(:func:`dense_mesh`), a pure function of the transform's key words and the
operand, compiled once a (mesh, spec, shape) through ``engine.compiled``:

* ``shard_map`` over the operand's own mesh and ``PartitionSpec`` — the
  contracted axis over one mesh axis, several or none, the free axis over the
  others or none; no device ever reads another's shard of the operand;
* each device takes its slice of the block-key table of the (padded) N by its
  position along the axes that shard the contracted axis — the same bits as
  ``T.s_block``, so sharded equals unsharded for one seed — and contracts its
  shard with the kernel the one-chip apply runs (``pallas_dense``'s plan of
  the LOCAL shape: generation, the bf16x3 contraction, the scale folded into
  the planes), or with a ``fori_loop`` of XLA matmuls over on-the-fly blocks
  where the kernel declines (off the TPU, a distribution or dtype
  ``pallas_dense.supported`` refuses);
* the float32 partials are reduced over exactly those axes and the result
  stays distributed as upstream's: reduce-scattered along the sketch axis
  (``[MC,MR]`` in, ``[MC,MR]`` out) where s divides, ``psum`` to a replicated
  sketch axis where it does not, no collective where the contracted axis is
  whole on every device;
* the reduce-scatter goes behind the contraction where the device's rowwise
  "hbm" contraction holds at least two row panels (``_PANEL_TILES`` row
  tiles each; :func:`_panels`): the planes of S are made once, the
  contraction is called a panel on the whole shard with a row window of its
  grid (``pallas_dense.window_partial``: no slice of the operand), and each
  panel's partial is exchanged as a pipelined ring of ``ppermute``s — p − 1
  asynchronous steps that hand on what has been summed of one sketch-axis
  chunk and add the device's own share of the next, the chunks those of
  ``psum_scatter(tiled=True)`` — in flight while the next panel contracts and
  retired in order, each sum stored into its rows of the result in place.
  Over a pair the result is the single collective's bit for bit (a + b in
  either order). Everything else — fewer than two panels, ``psum``, the
  columnwise orientation, the XLA block loop — is one ``lax.psum_scatter``.

``DenseTransform._apply_dense`` (sketch/dense.py) sends every concrete
operand that lies on more than one device here (:func:`apply_on_mesh`), so
``T.apply(A, dimension)`` is the entry point; :func:`rowwise` and
:func:`columnwise` are thin callers of the same program for an operand the
caller has not placed, with the result's sketch axis replicated. Memory a
device: its operand shard, one partial (free extent × s; three panels'
partials where the exchange is pipelined), its result shard and the kernel's
planes. A ragged N (the local extent no multiple of
``BLOCK_COLS``) is zero-padded inside the program — exact, but XLA re-lays
the operand for it.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from libskylark_tpu.base import errors, randgen
from libskylark_tpu.parallel.mesh import ROWS
from libskylark_tpu.sketch import params as sketch_params
from libskylark_tpu.sketch.dense import BLOCK_COLS, DenseTransform
from libskylark_tpu.sketch.transform import note_apply
from libskylark_tpu.telemetry import metrics as _metrics
from libskylark_tpu.telemetry import trace as _trace

_COLLECTIVE_BYTES = _metrics.counter(
    "sketch.mesh_collective_bytes",
    "bytes one device sends in the collectives of dense applies on a mesh, "
    "by family and collective (psum_scatter | ppermute_ring | psum | none)")


def _spec_axes(spec: P, ndim: int = 2) -> tuple:
    """``spec`` as one tuple of mesh-axis names a dimension:
    ``P('rows', ('a', 'b'))`` -> ``(('rows',), ('a', 'b'))``."""
    entries = tuple(spec) + (None,) * (ndim - len(spec))
    return tuple(() if e is None else (e,) if isinstance(e, str) else tuple(e)
                 for e in entries)


def _partition(axes: tuple) -> P:
    return P(*(a or None for a in axes))


def _extent(mesh: Mesh, axes: tuple) -> int:
    return math.prod(mesh.shape[a] for a in axes)


def layout_of(A) -> tuple:
    """``(mesh, axes, None)`` of a concrete operand the program serves —
    ``axes`` as :func:`_spec_axes` — or ``(None, None, why)``: a sharding
    that names no mesh, or a spec that leaves the layout to the compiler."""
    sharding = A.sharding
    if not isinstance(sharding, NamedSharding):
        return None, None, f"sharding is a {type(sharding).__name__}"
    if P.UNCONSTRAINED in tuple(sharding.spec):
        return None, None, "spec leaves an axis unconstrained"
    return sharding.mesh, _spec_axes(sharding.spec, A.ndim), None


def _padded_n(n: int, p: int) -> int:
    step = p * BLOCK_COLS
    return -(-n // step) * step


def _collective(s_dim: int, p: int, scatter: bool) -> str:
    if p == 1:
        return "none"
    return "psum_scatter" if scatter and s_dim % p == 0 else "psum"


# Row tiles (``plan.m_tile`` rows each) a panel of the pipelined exchange
# holds. On four v5e chips, 64 tiles of 2048 rows a device (PERF.md §6,
# PR 56), device time an apply read 37.73 / 37.56 / 38.15 ms at 4 / 8 / 16
# tiles a panel (43.20 with the single collective): a call of the
# contraction costs ≈ 30 µs whatever its window, the last panel's transfer
# stays exposed (0.3 / 0.6 / 1.2 ms), and each panel's kernel is 2.6 MB of
# code on the device.
_PANEL_TILES = 8


def _panels(plan, rows: int, s_dim: int, seq_axis: int,
            collective: str) -> int:
    """Row panels the reduce-scatter is pipelined over, from what the
    program can observe; 1 is the single collective. Pipelined: the rowwise
    kernel's "hbm" contraction (the planes made once, a row window a call,
    the sketch axis in one tile) over ``rows`` local rows that are whole row
    tiles and at least two panels, reduce-scattered."""
    if (plan is None or seq_axis != 1 or collective != "psum_scatter"
            or plan.operator_residency != "hbm" or plan.s_tile != s_dim
            or rows % plan.m_tile):
        return 1
    tiles = rows // plan.m_tile
    return -(-tiles // _PANEL_TILES) if tiles >= 2 * _PANEL_TILES else 1


def collective_bytes(collective: str, p: int, part_bytes: int) -> int:
    """Bytes one device sends in the apply's collective, from the shapes: a
    reduce-scatter over ``p`` devices — the one op, or the ring of
    ``ppermute``s a panel — sends (p − 1)/p of its partial, an all-reduce
    (reduce-scatter + all-gather) twice that."""
    sent = part_bytes * (p - 1) // p
    return {"none": 0, "psum_scatter": sent, "ppermute_ring": sent,
            "psum": 2 * sent}[collective]


def dense_mesh(key_data, A, *, mesh: Mesh, spec: tuple, seq_axis: int,
               dist, s_dim: int, scale: float, plan=None,
               scatter: bool = True):
    """``scale``·A·Sᵀ (``seq_axis`` 1) or ``scale``·S·A (0) of an operand
    laid ``spec`` (:func:`_spec_axes`) over ``mesh``, S the virtual
    (s_dim × N) operator of ``dist`` under the raw key words ``key_data``
    ((2,) uint32): the body of the module docstring, traceable. ``plan`` (a
    :class:`pallas_dense.Plan` of the local shard) runs each device's
    contraction on the fused kernel, None on the XLA block loop; ``scatter``
    False keeps the sketch axis replicated (``psum``)."""
    from libskylark_tpu.sketch import pallas_dense as pd

    seq_axes, free_axes = spec[seq_axis], spec[1 - seq_axis]
    p = _extent(mesh, seq_axes)
    n = A.shape[seq_axis]
    pad_n = _padded_n(n, p)
    if pad_n != n:
        pads = [(0, 0), (0, 0)]
        pads[seq_axis] = (0, pad_n - n)
        A = jnp.pad(A, pads)
    blocks = pad_n // p // BLOCK_COLS
    collective = _collective(s_dim, p, scatter)
    panels = _panels(plan, A.shape[0] // _extent(mesh, spec[0]), s_dim,
                     seq_axis, collective)

    def pipelined(keys, A_loc):
        """The reduce-scatter behind the contraction, a row panel at a
        time: panel q's partial leaves on a ring of p − 1 ``ppermute``s —
        at step t a device hands what it has summed of the sketch-axis
        chunk of the device t + 1 places ahead to its neighbour behind and
        adds its own share of the next chunk to what arrives, so the last
        sum is its own chunk, whole: the chunks of
        ``psum_scatter(tiled=True)`` — while panel q + 1 contracts."""
        me = lax.axis_index(seq_axes)
        width = s_dim // p
        ring = [(d, (d - 1) % p) for d in range(p)]
        # the rows of S rolled so that the kernel's chunk t is the ring's
        # step t: the contraction stores what leaves apart from what stays,
        # and no pass over a partial picks a chunk out
        planes = [jnp.roll(plane, -(me + 1) * width, axis=0)
                  for plane in pd.partial_planes(keys, scale, dist=dist,
                                                 s_dim=s_dim, plan=plan)]
        rows = _PANEL_TILES * plan.m_tile
        # every row is stored below, a panel's at a time: no fill
        out = pd.unwritten((A_loc.shape[0], width), jnp.float32,
                           plan.interpret)
        sums = []

        def stored(out, q):
            return lax.dynamic_update_slice_in_dim(out, sums[q], q * rows,
                                                   axis=0)

        for q in range(panels):
            if q >= 2:
                # retired in order: panel q − 2's sum lands in its rows of
                # the result before panel q contracts, so one transfer
                # stands behind the last contraction and no more than three
                # partials are alive
                planes, out = lax.optimization_barrier(
                    (planes, stored(out, q - 2)))
            chunks = pd.window_partial(
                A_loc, planes, q * _PANEL_TILES, scale, plan=plan, chunks=p,
                count=min(rows, A_loc.shape[0] - q * rows) // plan.m_tile)
            acc = chunks[0]
            for mine in chunks[1:]:
                acc = lax.ppermute(acc, seq_axes, ring) + mine
            sums.append(acc)
        return stored(stored(out, panels - 2), panels - 1)

    def local(key_data, A_loc):
        # this device's column blocks of S: its position along the axes
        # that shard the contracted axis
        first = (lax.axis_index(seq_axes) if seq_axes else 0) * blocks
        if plan is not None:
            keys = lax.dynamic_slice_in_dim(
                pd._block_key_table(key_data, pad_n), first, blocks)
            if panels > 1:
                return pipelined(keys, A_loc)
            part = pd.fused_partial(keys, dist, A_loc, s_dim,
                                    seq_axis=seq_axis, plan=plan, scale=scale)
        else:
            key = jax.random.wrap_key_data(key_data)

            def body(b, acc):
                Sb = randgen.dense_block(key, dist, s_dim, first + b,
                                         BLOCK_COLS, A_loc.dtype)
                seg = lax.dynamic_slice_in_dim(
                    A_loc, b * BLOCK_COLS, BLOCK_COLS, axis=seq_axis)
                return acc + (seg @ Sb.T if seq_axis else Sb @ seg)

            free = A_loc.shape[1 - seq_axis]
            acc0 = jnp.zeros((free, s_dim) if seq_axis else (s_dim, free),
                             A_loc.dtype)
            if seq_axes + free_axes:    # the carry varies as the body does
                acc0 = lax.pcast(acc0, seq_axes + free_axes, to="varying")
            part = (jnp.asarray(scale, A_loc.dtype)
                    * lax.fori_loop(0, blocks, body, acc0))
        if collective == "psum_scatter":    # s stands where N stood
            return lax.psum_scatter(part, seq_axes,
                                    scatter_dimension=seq_axis, tiled=True)
        return lax.psum(part, seq_axes) if collective == "psum" else part

    out_axes = [(), ()]
    out_axes[1 - seq_axis] = free_axes
    out_axes[seq_axis] = seq_axes if collective == "psum_scatter" else ()
    # check_vma off on the kernel branch only: pallas_call's out_shape
    # carries no varying-axis annotation, which the checker (rightly)
    # rejects; the collective above establishes the result's layout
    return shard_map(local, mesh=mesh,
                     in_specs=(P(), _partition(spec)),
                     out_specs=_partition(tuple(out_axes)),
                     check_vma=plan is None)(key_data, A)


@functools.lru_cache(maxsize=None)
def _program():
    """The compiled apply, built at the first operand on a mesh."""
    from libskylark_tpu.engine.compiled import compiled

    return compiled(dense_mesh, name="sketch.dense_mesh",
                    static_argnames=("mesh", "spec", "seq_axis", "dist",
                                     "s_dim", "scale", "plan", "scatter"))


def _local_shape(shape: tuple, mesh: Mesh, spec: tuple, seq_axis: int) -> tuple:
    """A device's shard as the kernel sees it: the contracted axis padded."""
    local = [shape[d] // _extent(mesh, spec[d]) for d in (0, 1)]
    p = _extent(mesh, spec[seq_axis])
    local[seq_axis] = _padded_n(shape[seq_axis], p) // p
    return tuple(local)


def _kernel_plan(T, local: tuple, dtype, seq_axis: int,
                 use_pallas: Optional[bool], interpret: bool):
    """The device's :class:`pallas_dense.Plan` — the one-chip planner on the
    local shape — or None where the kernel is off or declines."""
    if use_pallas is None:
        use_pallas = sketch_params.get_use_pallas()
    if not use_pallas:
        return None
    from libskylark_tpu.sketch import pallas_dense as pd

    return pd._plan(T.dist, jax.ShapeDtypeStruct(local, dtype), T.sketch_dim,
                    seq_axis, None, None, interpret)


def _statics(T, A, mesh: Mesh, spec: tuple, seq_axis: int,
             use_pallas: Optional[bool], interpret: bool,
             scatter: bool) -> tuple:
    """``(local shape, static arguments of the program)`` for ``A`` laid
    ``spec`` over ``mesh``."""
    local = _local_shape(A.shape, mesh, spec, seq_axis)
    plan = _kernel_plan(T, local, A.dtype, seq_axis, use_pallas, interpret)
    return local, dict(mesh=mesh, spec=spec, seq_axis=seq_axis, dist=T.dist,
                       s_dim=T.sketch_dim, scale=T.scale, plan=plan,
                       scatter=scatter)


def _span_attrs(T, local: tuple, dtype, mesh: Mesh, spec: tuple,
                seq_axis: int, plan) -> dict:
    """The attributes of the apply's ``sketch.dispatch`` span
    (telemetry/names.py), from the shapes."""
    seq_axes = spec[seq_axis]
    p = _extent(mesh, seq_axes)
    collective = _collective(T.sketch_dim, p, True)
    panels = _panels(plan, local[0], T.sketch_dim, seq_axis, collective)
    if panels > 1:
        collective = "ppermute_ring"
    part_bytes = (local[1 - seq_axis] * T.sketch_dim
                  * jnp.dtype(jnp.float32 if plan is not None
                              else dtype).itemsize)
    attrs = {
        "path": "mesh", "route": "program", "family": T.sketch_type,
        "grid": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
        "spec": str(_partition(spec)),
        "orientation": "rowwise" if seq_axis else "columnwise",
        "local_shape": local, "kernel": "xla_blocks",
        "collective": collective, "reduce_over": seq_axes,
        "exchange": "pipelined" if panels > 1 else "single", "panels": panels,
        "collective_bytes": collective_bytes(collective, p, part_bytes),
    }
    if plan is not None:
        attrs.update(
            kernel=("pallas_planes" if plan.operator_residency == "hbm"
                    else "pallas_generate"),
            operator_residency=plan.operator_residency, m_tile=plan.m_tile,
            precision=plan.precision)
    return attrs


def route(T, A, seq_axis: int) -> tuple:
    """What ``T.apply`` will do with a concrete operand on more than one
    device, without doing it: ``(statics, attrs)`` — the program's static
    arguments and its span's attributes — or ``(None, why)`` where
    :func:`layout_of` declines and sketch/dense.py keeps its XLA route."""
    mesh, spec, why = layout_of(A)
    if mesh is None:
        return None, why
    local, statics = _statics(T, A, mesh, spec, seq_axis, None, False, True)
    return statics, _span_attrs(T, local, A.dtype, mesh, spec, seq_axis,
                                statics["plan"])


def apply_on_mesh(T, A, seq_axis: int) -> tuple:
    """``DenseTransform._apply_dense``'s route for a concrete operand on
    more than one device: ``(result, None)`` of the ``sketch.dense_mesh``
    program, or ``(None, why)`` (:func:`route`)."""
    statics, attrs = route(T, A, seq_axis)
    if statics is None:
        return None, attrs
    note_apply(path="mesh")
    key_data = T.allocation.key_data
    with _trace.span("sketch.dispatch", attrs):
        out = _program()(key_data, A, **statics)
    _COLLECTIVE_BYTES.inc_always(attrs["collective_bytes"],
                                 family=T.sketch_type,
                                 collective=attrs["collective"])
    return out, None


def serves(A) -> bool:
    """Whether :func:`apply_on_mesh` would run the program on ``A``."""
    return layout_of(A)[0] is not None


def _sequence_parallel(T, A, mesh: Mesh, axis: str, seq_axis: int,
                       use_pallas: Optional[bool], interpret: bool):
    """The thin callers' body: the operand laid with its contracted axis
    over ``axis`` of ``mesh`` and nothing else, the program with a
    replicated result — inlined under a caller's trace."""
    if not isinstance(T, DenseTransform):
        raise errors.UnsupportedError(
            "sequence-parallel apply needs a DenseTransform-backed sketch; "
            f"got {type(T).__name__}")
    A = jnp.asarray(A)
    if A.shape[seq_axis] != T.input_dim:
        raise errors.SketchError(
            f"sequence axis has {A.shape[seq_axis]} entries, transform "
            f"expects {T.input_dim} (A is {A.shape})")
    spec = [(), ()]
    spec[seq_axis] = (axis,)
    spec = tuple(spec)
    _, statics = _statics(T, A, mesh, spec, seq_axis, use_pallas, interpret,
                          False)
    if isinstance(A, jax.core.Tracer):
        return dense_mesh(T.allocation.key_data, A, **statics)
    if A.shape[seq_axis] % mesh.shape[axis] == 0:
        A = jax.device_put(A, NamedSharding(mesh, _partition(spec)))
    return _program()(T.allocation.key_data, A, **statics)


def columnwise(T, A, mesh: Mesh, axis: str = ROWS,
               use_pallas: bool | None = None,
               interpret: bool = False) -> jnp.ndarray:
    """S·A for A (N, m), its first (sequence) axis sharded over ``axis`` of
    ``mesh`` here; returns the (S_dim, m) result replicated. For an operand
    that already lies on a mesh, ``T.apply(A, COLUMNWISE)`` runs the same
    program and leaves the result distributed."""
    return _sequence_parallel(T, A, mesh, axis, 0, use_pallas, interpret)


def rowwise(T, A, mesh: Mesh, axis: str = ROWS,
            use_pallas: bool | None = None,
            interpret: bool = False) -> jnp.ndarray:
    """A·Sᵀ for A (m, N), its second (sequence) axis sharded over ``axis`` of
    ``mesh`` here; returns the (m, S_dim) result replicated. For an operand
    that already lies on a mesh, ``T.apply(A, ROWWISE)`` runs the same
    program and leaves the result distributed."""
    return _sequence_parallel(T, A, mesh, axis, 1, use_pallas, interpret)
