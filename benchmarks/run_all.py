"""All BASELINE.md measurement configs, one JSON line each, with
per-round persistence and a regression gate.

``bench.py`` at the repo root is the driver-facing headline (config 1 at
full scale); this script measures every config so rounds can be compared
across the whole surface:

1. JLT dense sketch apply (GB/s, fused generation+matmul)
2. CWT sparse hash sketch on sparse input (M nnz/s)
2b. CWT on a MESH-DISTRIBUTED sparse input (P4/P5 path, M nnz/s)
3. FJLT + FastGaussianRFT feature maps (M rows/s)
4. Sketched least squares + randomized SVD (wall-clock)
5. KRR + Block-ADMM RLSC training (wall-clock)

Usage: python benchmarks/run_all.py [--scale small|full]
                                    [--save N] [--gate]
``--save N`` writes benchmarks/results_rN_<backend>.json; with prior
results_r*.json present, every metric is printed with its delta vs the
best prior round at the same backend+scale, and ``--gate`` exits nonzero when any metric regresses
by more than 10% (the perf ratchet for later rounds — the phase-timer
discipline of ref: ml/BlockADMM.hpp:357-365 made enforceable).

Every run also times a fixed pure-numpy CANARY kernel
(:func:`canary_seconds`) and records ``canary_normalized`` per metric:
the VM's host speed drifts ~1.5× across days (r4 drift study), so on
the CPU backend the gate compares canary-normalized ratios — a uniform
host-speed change cancels out and only genuine code/XLA-path
regressions trip it. On-chip ratios stay raw.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# metric -> direction: +1 = higher is better (throughput),
#                      -1 = lower is better (wall-clock)
DIRECTIONS = {
    "jlt_sketch_apply_GBps": +1,
    "cwt_sparse_apply_Mnnz_per_s": +1,
    "cwt_dist_sparse_apply_Mnnz_per_s": +1,
    "rft_feature_map_Mrows_per_s": +1,
    "frft_feature_map_Mrows_per_s": +1,
    "nla_wallclock_s": -1,
    "admm_train_wallclock_s": -1,
}


def canary_seconds(reps: int = 7) -> float:
    """Best-of-``reps`` wall time of a FIXED pure-numpy compute kernel
    (deterministic shapes/seed; one 768³ f64 gemm + an elementwise
    chain). The VM's effective CPU speed drifts ~1.5× across days
    (r4 host-speed drift study), so raw CPU-mesh ratios are
    not a valid cross-round signal; dividing/multiplying each metric by
    the same round's canary time cancels the host-speed factor for
    compute-bound workloads. On-chip numbers are NOT normalized — chip
    throughput doesn't ride the host clock (the canary is still
    recorded for provenance)."""
    rng = np.random.default_rng(12345)
    a = rng.standard_normal((768, 768))
    b = rng.standard_normal((768, 768))
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        c = a @ b
        c = np.tanh(c) + np.sqrt(np.abs(c) + 1.0)
        float(c.sum())
        best = min(best, time.perf_counter() - t0)
    return best


def _canary_norm(value: float, direction: int, canary_s: float) -> float:
    """Drift-normalized form of a metric value: throughput × canary_s
    (work per canary-unit of host time), wall-clock ÷ canary_s (walls in
    canary units). Both are invariant under a uniform host-speed change."""
    return value * canary_s if direction > 0 else value / canary_s


def _time_scalar(fn, *args, reps: int | None = None) -> float:
    """Best wall time of fn(*args) forced through a scalar readback.
    SKYLARK_BENCH_REPS raises the repeat count: the r4 variance study
    measured ±10% run-to-run spread for best-of-3 on
    the single-core CPU mesh — ratchet comparisons there should use
    more reps; on-chip runs are far less noisy and keep the default."""
    if reps is None:
        try:
            reps = int(os.environ.get("SKYLARK_BENCH_REPS", "3"))
        except ValueError:
            reps = 3
    out = fn(*args)
    float(out)  # warm + compile
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        float(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def bench_jlt(scale: str):
    import bench

    # regime pinned explicitly and recorded: bench.run's default tracks
    # the shipping kernel regime, which may change between rounds — the
    # round-over-round ratchet needs a fixed, labeled regime
    precision = "bf16x3"
    if scale == "full":
        gbps, secs, plan = bench.run(precision=precision)
    else:
        gbps, secs, plan = bench.run(m=1024, n=1024, s=128, repeats=2,
                                     precision=precision)
    # plan_id top-level: every measurement names the plan that served it
    return {"metric": "jlt_sketch_apply_GBps", "value": round(gbps, 3),
            "unit": "GB/s", "precision": precision, "plan": plan,
            "plan_id": plan.get("plan_id")}


def _sparse_input(scale: str):
    import scipy.sparse as sp

    from libskylark_tpu.base.sparse import SparseMatrix

    n, m, dens, s = ((1 << 20, 256, 1e-3, 4096) if scale == "full"
                     else (1 << 14, 64, 1e-2, 256))
    A = SparseMatrix.from_scipy(
        sp.random(n, m, density=dens, random_state=0, dtype=np.float64))
    return A, n, m, s


def bench_cwt_sparse(scale: str):
    from libskylark_tpu.base.context import Context
    from libskylark_tpu.sketch import CWT

    A, n, m, s = _sparse_input(scale)
    T = CWT(n, s, Context(seed=1))
    f = jax.jit(lambda r, c, v: jnp.sum(jnp.abs(
        jnp.zeros((s, m), v.dtype).at[T.bucket_indices()[r], c].add(
            T.values(v.dtype)[r] * v))))
    r, c, v = A.coo()
    best = _time_scalar(f, r, c, v)
    return {"metric": "cwt_sparse_apply_Mnnz_per_s",
            "value": round(A.nnz / best / 1e6, 3), "unit": "Mnnz/s"}


def bench_cwt_dist_sparse(scale: str):
    """BASELINE config 2 on a MESH-DISTRIBUTED sparse input: the P4/P5
    path (shard_map local scatter + psum; ref:
    sketch/hash_transform_CombBLAS.hpp)."""
    from libskylark_tpu import parallel as par
    from libskylark_tpu.base.context import Context
    from libskylark_tpu.base.dist_sparse import distribute_sparse
    from libskylark_tpu.sketch import COLUMNWISE, CWT

    A, n, m, s = _sparse_input(scale)
    n_dev = len(jax.devices())
    mesh = (par.square_mesh() if n_dev >= 4 else par.make_mesh())
    axes = (dict(row_axis="rows", col_axis="cols")
            if len(mesh.axis_names) > 1 and mesh.shape.get("cols", 1) > 1
            else dict(row_axis=mesh.axis_names[0]))
    D = distribute_sparse(A, mesh, **axes)
    T = CWT(n, s, Context(seed=1))
    f = jax.jit(lambda: jnp.sum(jnp.abs(T.apply(D, COLUMNWISE))))
    best = _time_scalar(f)
    return {"metric": "cwt_dist_sparse_apply_Mnnz_per_s",
            "value": round(A.nnz / best / 1e6, 3), "unit": "Mnnz/s",
            "devices": n_dev}


def bench_feature_maps(scale: str):
    from libskylark_tpu.base.context import Context
    from libskylark_tpu.ml.kernels import Gaussian
    from libskylark_tpu.sketch import ROWWISE

    n, d, s = (65536, 256, 4096) if scale == "full" else (4096, 64, 512)
    X = jnp.asarray(np.random.default_rng(0).standard_normal((n, d)),
                    jnp.float32)
    out = {}
    for tag in ("regular", "fast"):
        T = Gaussian(d, sigma=2.0).create_rft(s, Context(seed=2), tag)
        f = jax.jit(lambda X: jnp.sum(jnp.abs(T.apply(X, ROWWISE))))
        best = _time_scalar(f, X)
        out[tag] = round(n / best / 1e6, 3)
    return {"metric": "rft_feature_map_Mrows_per_s", "value": out["regular"],
            "unit": "Mrows/s", "fast": out["fast"]}


def bench_frft(scale: str):
    """Fastfood at high input dimension — the regime it exists for
    (SHGΠHB beats the dense frequency-matrix GEMM,
    ref: sketch/FRFT_Elemental.hpp, sketch/FUT.hpp:225-347). The WHT core
    runs as the kron-factored MXU matmul (sketch/fut.py). Reported with
    the dense-RFT rows/s on the SAME config so the speedup is in the
    record (r2 finding: FRFT was 4× slower than RFT; the criterion is
    ≥2× faster at d ≥ 4096)."""
    from libskylark_tpu.base.context import Context
    from libskylark_tpu.sketch import ROWWISE
    from libskylark_tpu.sketch.frft import FastGaussianRFT
    from libskylark_tpu.sketch.rft import GaussianRFT

    n, d, s = (16384, 4096, 4096) if scale == "full" else (2048, 512, 512)
    X = jnp.asarray(np.random.default_rng(8).standard_normal((n, d)),
                    jnp.float32)
    out = {}
    T_frft = FastGaussianRFT(d, s, Context(seed=9), sigma=2.0)
    for tag, T in (
        ("frft", T_frft),
        ("rft", GaussianRFT(d, s, Context(seed=9), sigma=2.0)),
    ):
        f = jax.jit(lambda X, T=T: jnp.sum(jnp.abs(T.apply(X, ROWWISE))))
        out[tag] = round(n / _time_scalar(f, X) / 1e6, 3)
    # the FastRFT apply is the XLA chain on every path; the record says so
    return {"metric": "frft_feature_map_Mrows_per_s", "value": out["frft"],
            "unit": "Mrows/s", "rft_same_config": out["rft"],
            "speedup_vs_rft": round(out["frft"] / out["rft"], 3),
            "path": "xla_chain_jit"}


def bench_nla(scale: str):
    from libskylark_tpu.base.context import Context
    from libskylark_tpu.nla.least_squares import fast_least_squares
    from libskylark_tpu.nla.svd import approximate_svd

    m, n, k = (262144, 512, 10) if scale == "full" else (8192, 128, 6)
    rng = np.random.default_rng(3)
    A = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    b = A @ jnp.asarray(rng.standard_normal(n), jnp.float32)

    t0 = time.perf_counter()
    x = fast_least_squares(A, b, Context(seed=4))
    x = x[0] if isinstance(x, tuple) else x
    float(jnp.sum(jnp.abs(x)))
    t_ls = time.perf_counter() - t0

    t0 = time.perf_counter()
    U, S, V = approximate_svd(A, k, Context(seed=5))
    float(jnp.sum(S))
    t_svd = time.perf_counter() - t0
    return {"metric": "nla_wallclock_s",
            "value": round(t_ls + t_svd, 3), "unit": "s",
            "least_squares_s": round(t_ls, 3), "svd_s": round(t_svd, 3)}


def bench_admm(scale: str):
    from libskylark_tpu.algorithms.prox import HingeLoss, L2Regularizer
    from libskylark_tpu.base.context import Context
    from libskylark_tpu.ml.admm import BlockADMMSolver
    from libskylark_tpu.ml.kernels import Gaussian

    n, d, s, iters = ((16384, 128, 2048, 10) if scale == "full"
                      else (1024, 32, 256, 5))
    rng = np.random.default_rng(6)
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.int64)
    solver = BlockADMMSolver.from_kernel(
        Context(seed=7), HingeLoss(), L2Regularizer(), 0.01, s,
        Gaussian(d, sigma=3.0), num_partitions=4)
    solver.maxiter = iters
    solver.tol = 0.0
    t0 = time.perf_counter()
    solver.train(X, y)
    wall = time.perf_counter() - t0
    return {"metric": "admm_train_wallclock_s", "value": round(wall, 3),
            "unit": "s", "iters": iters}


def _prior_bests(scale: str, backend: str,
                 exclude: str | None = None
                 ) -> tuple[dict[str, float], dict[str, float]]:
    """One pass over results_r*.json → (best raw, best canary-normalized)
    value per metric, best respecting the metric's direction. Only
    rounds recorded at the SAME scale and backend are comparable — a
    full-scale TPU round must not gate a small-scale CPU run.
    ``exclude`` drops the round's OWN save file: on a --resume pass it
    matches the glob, and comparing a record against itself would
    overwrite its genuine cross-round ratio with a spurious 1.0.

    Normalization uses each RECORD's own ``canary_s`` (stored at
    measurement time, r5+) falling back to the file-level canary;
    records with neither can't be normalized and feed only the raw
    ratchet."""
    best: dict[str, float] = {}
    best_norm: dict[str, float] = {}
    for p in glob.glob(os.path.join(HERE, "results_r*.json")):
        if exclude is not None and os.path.abspath(p) == \
                os.path.abspath(exclude):
            continue
        try:
            with open(p) as fh:
                recs = json.load(fh)
        except Exception:
            continue
        if recs.get("scale") != scale or recs.get("backend") != backend:
            continue
        file_canary = recs.get("canary_s")
        for rec in recs.get("results", []):
            m, v = rec.get("metric"), rec.get("value")
            if m not in DIRECTIONS or not isinstance(v, (int, float)):
                continue
            d = DIRECTIONS[m]
            if m not in best or (v - best[m]) * d > 0:
                best[m] = v
            canary = rec.get("canary_s", file_canary)
            if isinstance(canary, (int, float)) and canary > 0:
                nv = _canary_norm(v, d, canary)
                if m not in best_norm or (nv - best_norm[m]) * d > 0:
                    best_norm[m] = nv
    return best, best_norm


def _existing_results(path: str, scale: str, backend: str) -> dict[str, dict]:
    """Metric → record from a previous (possibly partial) save of the same
    round at the same scale+backend, for carry-through and ``--resume``.
    A scale mismatch REFUSES the run outright: backend is in the filename
    but scale is not, so persisting would silently replace the other
    scale's round file (e.g. a --scale small spot-check destroying
    full-scale TPU evidence)."""
    try:
        with open(path) as fh:
            old = json.load(fh)
    except FileNotFoundError:
        return {}
    except Exception:
        sys.exit(f"refusing --save: {path} exists but is unreadable; "
                 "move it aside or pick another round number")
    if old.get("scale") != scale:
        sys.exit(f"refusing --save: {path} holds a scale="
                 f"{old.get('scale')!r} round; this run is scale={scale!r}."
                 " Pick another round number or move the file aside.")
    if old.get("backend") != backend:
        return {}
    out = {}
    for r in old.get("results", []):
        if not r.get("metric"):
            continue
        if (isinstance(r.get("value"), (int, float))
                and not isinstance(r.get("canary_s"), (int, float))
                and isinstance(old.get("canary_s"), (int, float))):
            # pre-per-record-canary save: attach the file-level canary
            # the values were measured under, so a --resume on a
            # different-speed day normalizes them correctly (and
            # _persist doesn't re-stamp them under today's canary)
            r = dict(r)
            r["canary_s"] = old["canary_s"]
        out[r["metric"]] = r
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=["small", "full"], default="full")
    ap.add_argument("--save", type=int, metavar="ROUND", default=None,
                    help="persist results as results_rROUND_<backend>.json")
    ap.add_argument("--gate", action="store_true",
                    help="exit 1 if any metric regresses >10%% vs the "
                         "best prior round")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench-name substrings or exact "
                         "metric names to run")
    ap.add_argument("--resume", action="store_true",
                    help="with --save: skip configs whose saved record "
                         "already has a non-null value (recovery after "
                         "a killed run)")
    args = ap.parse_args()
    if args.resume and args.save is None:
        sys.exit("--resume requires --save (there is no file to resume "
                 "from or persist to)")

    regressed = []
    benches = (
        (bench_jlt, "jlt_sketch_apply_GBps"),
        (bench_cwt_sparse, "cwt_sparse_apply_Mnnz_per_s"),
        (bench_cwt_dist_sparse, "cwt_dist_sparse_apply_Mnnz_per_s"),
        (bench_feature_maps, "rft_feature_map_Mrows_per_s"),
        (bench_frft, "frft_feature_map_Mrows_per_s"),
        (bench_nla, "nla_wallclock_s"),
        (bench_admm, "admm_train_wallclock_s"),
    )
    if args.only:
        # bench-name substrings or EXACT metric names — substring matching
        # on metrics would make some benches unselectable alone
        # ("rft_feature_map_Mrows_per_s" is a substring of the frft metric)
        wanted = [s.strip() for s in args.only.split(",") if s.strip()]
        selected = [
            (fn, metric) for fn, metric in benches
            if any(s in fn.__name__ or s == metric for s in wanted)
        ]
        if not selected:
            names = ", ".join(f"{fn.__name__}/{m}" for fn, m in benches)
            sys.exit(f"--only {args.only!r} matched no bench "
                     f"(available: {names})")
        benches = tuple(selected)
    # backend in the filename: a round records the CPU-mesh and the
    # on-chip suites as separate artifacts (one path per round made
    # them overwrite each other); _prior_best reads both layouts
    save_path = (os.path.join(
        HERE, f"results_r{args.save:02d}_{jax.default_backend()}.json")
        if args.save is not None else None)
    # loaded whenever a save file exists: EVERY existing record is seeded
    # into the (metric-keyed, insertion-ordered) results map, so a kill
    # at any point — including mid-config — persists a superset of what
    # the file already held. Selected configs replace
    # their record in place when their measurement completes; --resume
    # additionally skips re-measuring selected configs already captured.
    existing = (_existing_results(save_path, args.scale,
                                  jax.default_backend())
                if save_path else {})
    results: dict[str, dict] = dict(existing)
    prior, prior_norm = _prior_bests(args.scale, jax.default_backend(),
                                     exclude=save_path)
    canary_s = round(canary_seconds(), 6)
    on_cpu = jax.default_backend() == "cpu"
    print(f"# canary_s={canary_s}", file=sys.stderr)

    def _persist():
        # after EVERY config, atomically: a run killed mid-suite (a chip
        # call has a time limit) must not lose the configs already
        # measured
        out = {"round": args.save, "scale": args.scale,
               "backend": jax.default_backend(),
               "canary_s": canary_s,
               "results": list(results.values())}
        tmp = save_path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(out, fh, indent=1)
        os.replace(tmp, save_path)

    for fn, metric in benches:
        kept = existing.get(metric) if args.resume else None
        if kept is not None and kept.get("value") is not None:
            # resumed records fall through to the gate computation below —
            # a regression measured just before a kill must still fail
            # the --gate run that resumes it (prior takes the BEST across
            # rounds, so the resumed value cannot mask itself)
            rec = dict(kept)
            rec["resumed"] = True
        else:
            try:
                rec = fn(args.scale)
            except Exception as e:  # record failure under its REAL metric
                rec = {"metric": metric, "value": None,
                       "error": f"{type(e).__name__}: {e}"}
            rec["backend"] = jax.default_backend()
            if isinstance(rec.get("value"), (int, float)):
                # the canary travels WITH the record: a --resume pass on
                # a different-speed day must normalize each value by the
                # canary measured alongside it, not by today's
                rec["canary_s"] = canary_s
        m, v = rec.get("metric"), rec.get("value")
        rec_canary = rec.get("canary_s")
        if (m in DIRECTIONS and isinstance(v, (int, float))
                and isinstance(rec_canary, (int, float))):
            rec["canary_normalized"] = round(
                _canary_norm(v, DIRECTIONS[m], rec_canary), 6)
        if m in DIRECTIONS and (m in prior or m in prior_norm):
            if isinstance(v, (int, float)):
                d = DIRECTIONS[m]
                gate_ratio = None
                if m in prior:
                    ratio = (v / prior[m]) if d > 0 else (prior[m] / v)
                    rec["vs_best_prior"] = round(ratio, 4)
                    gate_ratio = ratio
                if m in prior_norm and isinstance(rec_canary,
                                                 (int, float)):
                    nv = _canary_norm(v, d, rec_canary)
                    nratio = ((nv / prior_norm[m]) if d > 0
                              else (prior_norm[m] / nv))
                    rec["vs_best_prior_canary_norm"] = round(nratio, 4)
                    if on_cpu:
                        # on the CPU mesh the raw ratio confounds code
                        # changes with host-speed drift (r4 EVIDENCE);
                        # the normalized ratio is the gated signal there
                        gate_ratio = nratio
                if gate_ratio is not None and gate_ratio < 0.9:
                    regressed.append((m, gate_ratio))
            else:
                # a previously-measured config that now crashes is the
                # worst regression, not a free pass
                regressed.append((m, 0.0))
        held = results.get(metric)
        if (rec.get("value") is None and held is not None
                and held.get("value") is not None):
            # a failed RE-measurement must not destroy captured evidence:
            # keep the good record, note the failure alongside (the gate
            # above still saw the crash)
            err = rec.get("error") or "remeasure failed"
            rec = dict(held)
            rec["remeasure_error"] = err
        results[metric] = rec
        print(json.dumps(rec), flush=True)
        if save_path is not None:
            _persist()

    if save_path is not None:
        print(f"# saved {save_path}", file=sys.stderr)

    if args.gate and regressed:
        for m, r in regressed:
            print(f"# REGRESSION {m}: {r:.3f}x of best prior",
                  file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
