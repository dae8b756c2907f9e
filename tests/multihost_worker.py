"""Worker for tests/test_multihost.py: one simulated HOST process.

Run as ``python multihost_worker.py <pid> <nprocs> <port>
[devices_per_proc]``. Joins the pool through the framework's own bootstrap
(``parallel.multihost.initialize_distributed`` — the MPI_Init analog,
ref: ml/skylark_ml.cpp:17-20), builds a mesh spanning every process's
devices, and checks the framework oracle ACROSS HOSTS: a sketch applied
to a row-sharded global array equals the local same-seed apply; a
cross-host psum reduction agrees with the analytic value. Prints
``MULTIHOST_OK`` on success — the parent test asserts it from every
process."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# >1 virtual devices per process → the mesh crosses hosts AND has
# intra-host device parallelism (2 hosts × 4 devices, or 4 hosts × 2 —
# the 4-host shape puts THREE host boundaries in the mesh, catching
# axis-ordering/non-adjacent-shard bugs the pairwise case can't)
DPP = int(sys.argv[4]) if len(sys.argv) > 4 else 4
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={DPP}").strip()

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np


def main() -> None:
    pid, nprocs, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]

    from libskylark_tpu.parallel import multihost

    multihost.initialize_distributed(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nprocs,
        process_id=pid,
    )
    assert multihost.process_count() == nprocs
    assert multihost.process_index() == pid
    assert multihost.is_root() == (pid == 0)

    import jax.numpy as jnp
    from jax.experimental import multihost_utils
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from libskylark_tpu.base.context import Context
    from libskylark_tpu.sketch import COLUMNWISE, CWT, JLT

    devs = jax.devices()
    n_dev = len(devs)
    assert n_dev == nprocs * DPP, \
        f"expected {nprocs * DPP} devices, {n_dev}"
    mesh = Mesh(np.array(devs), ("d",))

    # Global problem, identical in every process (same seed); each
    # process contributes only its local row shards.
    n, d, s = 64 * n_dev, 16, 32
    rng = np.random.default_rng(42)
    X = rng.standard_normal((n, d)).astype(np.float32)
    sharding = NamedSharding(mesh, P("d"))
    Xs = jax.make_array_from_callback(
        (n, d), sharding, lambda idx: X[idx])

    for name, T in (("CWT", CWT(n, s, Context(seed=3))),
                    ("JLT", JLT(n, s, Context(seed=4)))):
        want = np.asarray(T.apply(jnp.asarray(X), COLUMNWISE))
        got = multihost_utils.process_allgather(
            T.apply(Xs, COLUMNWISE), tiled=True)
        np.testing.assert_allclose(np.asarray(got), want,
                                   atol=1e-4, rtol=1e-4)
        print(f"proc {pid}: {name} cross-host oracle ok", flush=True)

    # the ml/ layer across hosts: Block-ADMM training on host-spanning
    # data must match the local same-seed oracle (P7 at process level;
    # regression guard for the jitted step closing over global arrays —
    # multi-process jax forbids that, so X/Y/factorizations are jit
    # arguments)
    from libskylark_tpu.algorithms.prox import L2Regularizer, SquaredLoss
    from libskylark_tpu.ml.admm import BlockADMMSolver

    def make_solver():
        sol = BlockADMMSolver(SquaredLoss(), L2Regularizer(), 0.01,
                              num_features=d, num_partitions=2)
        sol.maxiter = 6
        sol.tol = 0.0
        return sol

    # classification labels: the 0..k-1 validation and k inference run
    # as device reductions (np.asarray of a host-spanning Y is
    # impossible), so this also guards the label path cross-host
    Yv = (X[:, 0] > 0).astype(np.int32)
    Ys = jax.make_array_from_callback(
        (n,), NamedSharding(mesh, P()), lambda idx: Yv[idx])
    model = make_solver().train(Xs, Ys, regression=False)
    assert model.coef.is_fully_replicated
    local = make_solver().train(jnp.asarray(X), jnp.asarray(Yv),
                                regression=False)
    np.testing.assert_allclose(np.asarray(model.coef),
                               np.asarray(local.coef),
                               atol=1e-3, rtol=1e-3)
    print(f"proc {pid}: ADMM cross-host oracle ok", flush=True)

    # checkpoint/resume ACROSS HOSTS: a partial run checkpoints
    # host-spanning state (orbax multiprocess save under
    # jax.distributed), the rerun validates the resume identity — whose
    # data fingerprint takes the jitted spanning-stat path, since X/Y
    # span non-addressable devices here — and must finish bit-identical
    # to the uninterrupted run in EVERY process
    ck_root = os.environ.get("SKYLARK_MH_TMP")
    if ck_root:
        ckdir = os.path.join(ck_root, "admm_ck")
        part = make_solver()
        part.maxiter = 3
        part.train(Xs, Ys, regression=False, checkpoint=ckdir)
        full = make_solver()
        full.maxiter = 6
        resumed = full.train(Xs, Ys, regression=False, checkpoint=ckdir)
        np.testing.assert_array_equal(np.asarray(resumed.coef),
                                      np.asarray(model.coef))
        print(f"proc {pid}: ADMM cross-host checkpoint resume ok",
              flush=True)

    # the nla/algorithms layers across hosts: Krylov LSQR and randomized
    # SVD on host-spanning operands vs the local same-seed oracles
    # (eager ops and lax.while_loop take spanning operands as arguments
    # naturally — unlike a jitted closure — but only a process-level run
    # proves it)
    from libskylark_tpu.algorithms.krylov import KrylovParams, lsqr
    from libskylark_tpu.nla.svd import approximate_svd

    bvec = (X @ np.arange(d, dtype=np.float32))
    bs = jax.make_array_from_callback(
        (n,), sharding, lambda idx: bvec[idx])
    xg, _ = lsqr(Xs, bs, KrylovParams(iter_lim=30))
    xl, _ = lsqr(jnp.asarray(X), jnp.asarray(bvec),
                 KrylovParams(iter_lim=30))
    np.testing.assert_allclose(np.asarray(xg), np.asarray(xl),
                               atol=1e-3, rtol=1e-3)
    print(f"proc {pid}: LSQR cross-host oracle ok", flush=True)

    _, S_g, _ = approximate_svd(Xs, 4, Context(seed=7))
    _, S_l, _ = approximate_svd(jnp.asarray(X), 4, Context(seed=7))
    np.testing.assert_allclose(np.asarray(S_g), np.asarray(S_l),
                               atol=1e-3, rtol=1e-3)
    print(f"proc {pid}: randSVD cross-host oracle ok", flush=True)

    # raw cross-host collective sanity: psum over the host-spanning axis
    from jax import shard_map

    gx = jax.make_array_from_callback(
        (n_dev,), sharding,
        lambda idx: np.full(1, float(pid + 1), np.float32))
    out = jax.jit(shard_map(lambda v: jax.lax.psum(v, "d"), mesh=mesh,
                            in_specs=P("d"), out_specs=P("d")))(gx)
    # each process holds DPP shards of value pid+1 → psum = DPP·Σ(i+1)
    expect = float(DPP) * sum(range(1, nprocs + 1))
    got = float(np.asarray(out.addressable_shards[0].data)[0])
    assert got == expect, (got, expect)
    print(f"proc {pid}: psum across hosts = {got} MULTIHOST_OK",
          flush=True)


if __name__ == "__main__":
    main()
