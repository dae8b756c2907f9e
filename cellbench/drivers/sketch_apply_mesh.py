"""Driver for one kind of operation: the rowwise dense sketch of a
distributed matrix, ``SketchTransform.apply(A, ROWWISE)`` with ``A`` laid
``[MC,MR]`` (``parallel.grid2d``) over the configuration's grid of devices —
the call upstream's ``skylark_svd`` makes for its range sketch.

Set-up builds one transform from the seed and the operands on the mesh, each
device generating its own block; a step is one apply on the next operand,
blocking on every shard of the result; the check reads sampled rows out of
the distributed operand and result and holds them to the plain reference,
which knows nothing of meshes, and the result's layout to the one the
configuration states.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from cellbench import seeds
from cellbench.drivers import lower_precision
from cellbench.references import dense_sketch as reference

CHUNK_ROWS = 8192       # rows a device generates at a time (256 MiB at n/2 = 8192)


@dataclasses.dataclass
class State:
    config: dict
    seed: int
    context_seed: int
    transform: object
    panels: list        # the resident operands, each on the whole mesh
    rowwise: object
    mesh: object
    layout: NamedSharding


@functools.partial(jax.jit, static_argnames=("mesh", "m", "n"))
def _operand(key, i, *, mesh, m: int, n: int):
    """The i-th (m × n) N(0, 1) operand laid P('rows', 'cols') over ``mesh``:
    each device fills its own block from its own key, a row chunk at a time
    (no device ever holds more than its block and one chunk)."""
    r, c = (mesh.shape[a] for a in mesh.axis_names)
    rows, cols = m // r, n // c
    chunk = min(CHUNK_ROWS, rows)

    def block(key):
        mine = jax.random.fold_in(jax.random.fold_in(key, i),
                                  lax.axis_index(mesh.axis_names))

        def fill(j, buf):
            part = jax.random.normal(jax.random.fold_in(mine, j),
                                     (chunk, cols), jnp.float32)
            return lax.dynamic_update_slice_in_dim(buf, part, j * chunk, 0)

        return lax.fori_loop(0, rows // chunk, fill,
                             jnp.zeros((rows, cols), jnp.float32))

    return shard_map(block, mesh=mesh, in_specs=P(),
                     out_specs=P(*mesh.axis_names), check_vma=False)(key)


@functools.partial(jax.jit, static_argnames=("mesh",))
def _rows(X, idx, *, mesh):
    """Rows ``idx`` of ``X`` (laid over ``mesh`` or not), whole and on every
    device: each device picks what it holds of them, the grid's rows add up
    (one holds a row, the others zeros) and its columns gather — no device
    ever holds more of ``X`` than its block."""
    row_axis, col_axis = mesh.axis_names

    def local(X_loc, idx):
        held = X_loc.shape[0]
        lo = lax.axis_index(row_axis) * held
        mine = (idx >= lo) & (idx < lo + held)
        got = jnp.where(mine[:, None],
                        X_loc[jnp.clip(idx - lo, 0, held - 1)], 0)
        return lax.all_gather(lax.psum(got, row_axis), col_axis, axis=1,
                              tiled=True)

    return shard_map(local, mesh=mesh, in_specs=(P(row_axis, col_axis), P()),
                     out_specs=P(), check_vma=False)(X, idx)


@jax.jit
def _sum_squares(X):
    return jnp.sum(X * X)


def setup(config: dict, traffic: dict, seed: int) -> State:
    from libskylark_tpu import parallel as par
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.base.context import Context

    if config["family"] != "JLT":
        raise ValueError(f"sketch_apply_mesh drives JLT, got {config['family']!r}")
    grid = tuple(config["grid"])
    devices = jax.devices()[:grid[0] * grid[1]]
    mesh = par.make_mesh(grid, devices=devices)
    context_seed = seeds.context_seed(seed)
    transform = sk.JLT(config["n"], config["s"], Context(context_seed))
    key = seeds.data_key(seed, "operand_grid2d")
    panels = [_operand(key, i, mesh=mesh, m=config["m"], n=config["n"])
              for i in range(config["panels"])]
    jax.block_until_ready(panels)
    return State(config, seed, context_seed, transform, panels, sk.ROWWISE,
                 mesh, par.grid2d(mesh))


def describe(state: State) -> dict:
    """What the dispatch will do with this operand (for the log): the mesh
    program's route and the device's kernel plan, or that the program under
    test has no mesh route."""
    from libskylark_tpu.parallel import shard_apply

    route = getattr(shard_apply, "route", None)
    if route is None:
        return {"route": "xla", "why": "the_program_has_no_mesh_route"}
    statics, attrs = route(state.transform, state.panels[0], 1)
    if statics is None:
        return {"route": "xla", "why": str(attrs).replace(" ", "_")}
    return {k: str(attrs.get(k)).replace(" ", "") for k in (
        "route", "grid", "spec", "local_shape", "kernel", "operator_residency",
        "m_tile", "precision", "collective", "reduce_over", "collective_bytes")}


def keep(state: State) -> int:
    return len(state.panels)


def step(state: State, i: int):
    return state.transform.apply(
        state.panels[i % len(state.panels)], state.rowwise).block_until_ready()


def layout_defect(state: State, out) -> int:
    """0 when ``out`` is the (m × s) result laid as the operand — sharded
    P('rows', 'cols') over the configuration's mesh, one (m/r × s/c) shard
    on each device — else 1."""
    cfg = state.config
    r, c = cfg["grid"]
    sharding = getattr(out, "sharding", None)
    laid = (isinstance(sharding, NamedSharding)
            and sharding.is_equivalent_to(state.layout, 2))
    shards = {sh.device: sh.data.shape for sh in out.addressable_shards}
    whole = (out.shape == (cfg["m"], cfg["s"]) and len(shards) == r * c
             and set(shards.values()) == {(cfg["m"] // r, cfg["s"] // c)})
    return 0 if laid and whole else 1


def check(state: State, kept: list) -> dict:
    """The numbers compared, each the worst over the kept outputs."""
    cfg = state.config
    S = reference.operator(state.context_seed, 0, cfg["s"], cfg["n"])
    got = {"rel_max": 0.0, "norm_dev": 0.0}
    defect = 0
    for i, out in kept:
        panel = state.panels[i % len(state.panels)]
        if out.shape != (panel.shape[0], cfg["s"]):
            raise AssertionError(f"served shape {out.shape}")
        defect = max(defect, layout_defect(state, out))
        idx = jnp.asarray(np.sort(seeds.rng(state.seed, f"rows.{i % len(state.panels)}")
                                  .choice(panel.shape[0], cfg["check_rows"], replace=False)))
        # the sampled rows on one device, for the reference that knows no mesh
        A_rows = jnp.asarray(np.asarray(_rows(panel, idx, mesh=state.mesh)))
        out_rows = jnp.asarray(np.asarray(_rows(out, idx, mesh=state.mesh)))
        ref = reference.apply_rows(A_rows, S)
        rel = float(jnp.max(jnp.abs(out_rows - ref)) / jnp.max(jnp.abs(ref)))
        dev = float(jnp.abs(jnp.sqrt(_sum_squares(out) / _sum_squares(panel)) - 1.0))
        got["rel_max"] = max(got["rel_max"], rel if np.isfinite(rel) else np.inf)
        got["norm_dev"] = max(got["norm_dev"], dev if np.isfinite(dev) else np.inf)
    # the guarantee the configuration states: entries i.i.d. N(0, 1/s)
    count = S.size
    got["operator_mean_z"] = abs(float(jnp.mean(S))) * (count * cfg["s"]) ** 0.5
    got["operator_var_z"] = abs(float(jnp.var(S)) * cfg["s"] - 1.0) * (count / 2.0) ** 0.5
    # last: an exact count, 0 on every sound run (tools/calibrate.py divides by
    # the largest sound reading of each number, in this order)
    got["layout_defect"] = defect
    return got


def controls(state: State) -> dict:
    """Lower-precision stand-ins for ``step``: the program's own kernel
    regimes below the shipping one (they exist on the TPU only), and the
    reference computed in bfloat16 in the program's place — each device the
    reference's bfloat16 product of its block with the columns of S that
    face it, the float32 partial products reduce-scattered, so that the
    result is laid as the program's and only the arithmetic differs."""
    cfg = state.config
    S = reference.operator(state.context_seed, 0, cfg["s"], cfg["n"])
    row_axis, col_axis = state.mesh.axis_names

    def local(A_loc, S_loc):
        return lax.psum_scatter(reference.apply_rows(A_loc, S_loc, "bf16"),
                                col_axis, scatter_dimension=1, tiled=True)

    in_bf16 = jax.jit(shard_map(
        local, mesh=state.mesh, in_specs=(P(row_axis, col_axis), P(None, col_axis)),
        out_specs=P(row_axis, col_axis), check_vma=False))

    def reference_bf16(i):
        panel = state.panels[i % len(state.panels)]
        return in_bf16(panel, S).block_until_ready()

    return {"program_bf16": lower_precision.program_at(step, state, "bf16"),
            "program_bf16gen2": lower_precision.program_at(step, state, "bf16gen2"),
            "reference_bf16": reference_bf16}
