"""Chip smoke: sketch apply -> microbatch serve -> randomized solver -> a
few training slices, once, on a TPU, through the entry points a user calls.

    python chip_smoke.py              # needs a TPU; exits non-zero without
    python chip_smoke.py --rehearse   # tiny sizes on the CPU, kernels in
                                      # interpret mode; every line labelled

It is the quickest proof that the system still starts on the chip, not a
benchmark: the seconds it prints are for the record (compile vs run), named
with the device, and nothing is derived from them. One process, no
subprocess. Every step reads a scalar back (``block_until_ready`` alone can
return before a failure surfaces), compares with a plain-XLA ``highest``
reference (``sketch_params.set_use_pallas(False)``) or, where the XLA path
itself serves, a host numpy oracle; any failure exits non-zero. The last stdout line is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Width is the repo's headline shape (BASELINE config 1 scaled to one chip):
m, n, s = 8192, 8192, 1024, f32, JLT; feature maps at BASELINE.md's
16384 x 4096 -> 4096 and at the rft_features_apply cell's 32768 x 440 ->
16384. Writes only ``chiprun_out/chip_smoke/`` and the
compile cache (``JAX_COMPILATION_CACHE_DIR`` when set, else
``benchmarks/.jax_cache``).
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
REHEARSE = "--rehearse" in sys.argv[1:]
if REHEARSE:
    os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["SKYLARK_SESSION_DIR"] = os.path.join(OUT_DIR, "sessions")

import jax  # noqa: E402
import numpy as np  # noqa: E402

if REHEARSE:
    jax.config.update("jax_num_cpu_devices", 4)  # so that step f runs too

ORACLE = 1e-4  # the reference's sketch-determinism tolerance (BASELINE.md)

# (full, rehearsal) sizes
M, N, S = (256, 512, 64) if REHEARSE else (8192, 8192, 1024)
RFT_ROWS, RFT_D = (64, 512) if REHEARSE else (16384, 4096)
SERVE_ROWS = 64 if REHEARSE else 2048
LSQ_M, LSQ_N = (2048, 32) if REHEARSE else (65536, 512)
KRR_ROWS, KRR_D, KRR_FEATURES = (256, 8, 64) if REHEARSE else (8192, 64, 1024)

_DEVICE: dict = {}
_CACHE = {"hits": 0, "misses": 0}


def count_cache_event(name: str, **_) -> None:
    """jax.monitoring listener: persistent compile cache hits and misses."""
    prefix = "/jax/compilation_cache/cache_"
    if name.startswith(prefix):
        kind = name[len(prefix):]
        _CACHE[kind] = _CACHE.get(kind, 0) + 1


def say(step: str, **fields) -> None:
    tag = "[smoke REHEARSAL-ON-CPU]" if REHEARSE else "[smoke]"
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"{tag} {step} platform={_DEVICE['platform']} "
          f"device_kind={_DEVICE['kind']!r} devices={_DEVICE['count']} "
          f"{body}", flush=True)


def scalar(x) -> float:
    """Force the value to the host: the scalar read-back of every step."""
    import jax.numpy as jnp

    v = float(jnp.abs(jnp.asarray(x)).max())
    if not np.isfinite(v):
        raise AssertionError(f"non-finite result (max |x| = {v})")
    return v


def timed(fn):
    """(result, first_s, run_s): the first call compiles, the second is
    the steady run; both end in a scalar read-back."""
    t0 = time.perf_counter()
    scalar(fn())
    t1 = time.perf_counter()
    out = fn()
    scalar(out)
    t2 = time.perf_counter()
    return out, t1 - t0, t2 - t1


def close(got, ref, what: str, tol: float = ORACLE) -> float:
    got, ref = np.asarray(got), np.asarray(ref)
    if got.shape != ref.shape:
        raise AssertionError(f"{what}: shape {got.shape} != {ref.shape}")
    err = float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))
    if not err <= tol:
        raise AssertionError(f"{what}: rel-max error {err:.3e} > {tol:.0e}")
    return err


@contextlib.contextmanager
def xla_reference():
    """The plain-XLA path at ``highest`` matmul precision (the package
    default): kernels off for the duration."""
    from libskylark_tpu.sketch import params as sketch_params

    prev = sketch_params.get_use_pallas()
    sketch_params.set_use_pallas(False)
    try:
        yield
    finally:
        sketch_params.set_use_pallas(prev)


def rft_oracle(R, n: int, X) -> np.ndarray:
    """scale·cos(X·Wᵀ ⊙ sc + b) on the host in float64, from the
    transform's explicit frequency panel."""
    W = np.asarray(R.w_panel(0, n), np.float64)
    arg = (np.asarray(X, np.float64) @ W.T
           * np.asarray(R.row_scales(), np.float64)
           + np.asarray(R.shifts(), np.float64))
    return R.outscale * np.cos(arg)


def fastfood_oracle(T, X) -> np.ndarray:
    """The explicit Sm·H·G·Π·H·B chain per block on the host in float64,
    block-major feature order (as tests/test_sketch_fast.py assembles
    the operator)."""
    import jax.numpy as jnp
    import scipy.linalg

    NB, nb = T._NB, T._numblks
    H = scipy.linalg.hadamard(NB).astype(np.float64)
    scal = np.sqrt(NB) * T._fut.scale()
    B = np.asarray(T._B(jnp.float32), np.float64)
    G = scal * np.asarray(T._G(jnp.float32), np.float64)
    Sm = scal * np.asarray(T._Sm(jnp.float32), np.float64).reshape(nb, NB)
    perms = np.asarray(T._perms())
    X = np.asarray(X, np.float64)
    Xp = np.pad(X, ((0, 0), (0, NB - X.shape[1])))
    W = np.concatenate(
        [(((Xp * B[i]) @ H)[:, perms[i]] * G[i]) @ H * Sm[i]
         for i in range(nb)], axis=1)
    shifts = np.asarray(T.shifts(), np.float64)
    return T.scale * np.cos(W[:, :shifts.shape[0]] + shifts)


def launches(jitted: dict):
    """Snapshot of the jit caches of named kernel launchers; the returned
    function lists the ones traced since — which kernel served a call."""
    before = {k: f._cache_size() for k, f in jitted.items()}
    return lambda: [k for k, f in jitted.items()
                    if f._cache_size() > before[k]]


def feature_kernels(family: str):
    """Snapshot of the program's ``sketch.features`` counter for
    ``family``; the returned function lists the kernels whose count moved
    since — which kernel served a feature map's apply."""
    from libskylark_tpu.telemetry import metrics

    counter = metrics.registry().counter("sketch.features")
    names = ("pallas_planes", "pallas_generate", "xla")
    before = {k: counter.value(family=family, kernel=k) for k in names}
    return lambda: [k for k in names
                    if counter.value(family=family, kernel=k) > before[k]]


def dispatch_span(call) -> dict:
    """The attributes of the ``sketch.dispatch`` span one ``call`` opens
    (telemetry on for its duration): which program served an apply."""
    from libskylark_tpu import telemetry
    from libskylark_tpu.telemetry import trace

    was = telemetry.enabled()
    trace.clear_finished()
    telemetry.set_enabled(True)
    try:
        scalar(call())
        spans = [s for s in trace.finished_spans()
                 if s.name == "sketch.dispatch"]
    finally:
        telemetry.set_enabled(was)
        trace.clear_finished()
    if len(spans) != 1:
        raise AssertionError(f"{len(spans)} sketch.dispatch spans an apply")
    return dict(spans[0].attrs)


def report(step: str, first_s: float, run_s: float, **fields) -> None:
    say(step, compile_s=f"{max(first_s - run_s, 0.0):.2f}",
        run_s=f"{run_s:.4f}", **fields)


# ---------------------------------------------------------------------------
# b. sketch
# ---------------------------------------------------------------------------


def step_sketch() -> None:
    import jax.numpy as jnp
    import scipy.sparse as sp

    from libskylark_tpu import Context, SparseMatrix
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.sketch import hash as sk_hash
    from libskylark_tpu.sketch import pallas_dense as pd

    rng = np.random.default_rng(1)
    A = jnp.asarray(rng.standard_normal((M, N), dtype=np.float32))
    dense_launchers = {"pallas_dense.rowwise": pd._fused_call,
                       "pallas_dense.columnwise": pd._fused_call_cw,
                       "pallas_dense.rft_cos": pd._fused_call_cos}

    # JLT through SketchTransform.apply -> try_pallas_apply -> Mosaic
    T = sk.JLT(N, S, Context(seed=0))
    for dim, name in ((sk.ROWWISE, "rowwise"), (sk.COLUMNWISE, "columnwise")):
        operand = A if dim == sk.ROWWISE else A.T
        seq_axis = 1 if dim == sk.ROWWISE else 0
        with xla_reference():
            ref = np.asarray(T.apply(operand, dim))

        def fused(label):
            """One apply through the dispatch (interpret mode off-TPU,
            where the dispatch declines); the fused kernel must serve."""
            served = launches(dense_launchers)
            if REHEARSE:
                apply = getattr(pd, f"{name}_apply")
                out, first, run = timed(lambda: apply(
                    T.allocation.key, T.dist, operand, S, T.scale,
                    interpret=True))
            else:
                out, first, run = timed(lambda: T.apply(operand, dim))
            by = served()
            if by != [f"pallas_dense.{name}"]:
                raise AssertionError(
                    f"JLT {name} {label}: served by {by or 'xla'}, "
                    "not the fused kernel")
            err = close(out, ref, f"JLT {name} {label}")
            return out, first, run, err

        for precision in ("f32", "bf16x3"):
            sk.params.set_pallas_precision(precision)
            _out, first, run, err = fused(precision)
            report(f"sketch.JLT.{name}", first, run, shape=f"{M}x{N}->{S}",
                   backend=f"pallas_dense.{name}", precision=precision,
                   err=f"{err:.2e}")

        # where the operator lives between m-tiles at this orientation
        plan = pd.effective_plan(T.dist, operand.shape, operand.dtype, S,
                                 seq_axis, interpret=REHEARSE)
        say(f"sketch.JLT.{name}", plan=plan["plan_id"],
            operator_residency=plan["operator_residency"])

    # random Fourier features at the same width: generation + matmul +
    # cos epilogue in one kernel, against the host oracle on some rows
    # ... and at speech widths, where the map EXPANDS: 440 inputs (ragged:
    # padded to 512 inside the program) to a 16384-feature block, the
    # result tiled along s (the cell rft_features_apply's shape). The
    # apply is one program (sketch.rft_features); its counter names the
    # kernel that served, and it must be the fused one.
    rows = min(M, 256)
    wide = (64, 440, 4096) if REHEARSE else (32768, 440, 16384)
    for (fm, fn, fs), sigma in (((M, N, S), float(np.sqrt(N))), (wide, 30.0)):
        Xf = A if (fm, fn) == (M, N) else jnp.asarray(
            rng.standard_normal((fm, fn), dtype=np.float32))
        R = sk.GaussianRFT(fn, fs, Context(seed=16), sigma=sigma)
        plan = pd.effective_plan(R.dist, Xf.shape, Xf.dtype, fs, 1,
                                 interpret=REHEARSE, epilogue=True)
        if REHEARSE:
            served = launches(dense_launchers)
            out, first, run = timed(lambda: pd.rft_rowwise_apply(
                R.subkey(0), R.dist, Xf, fs, R.inscale, R.outscale,
                R.row_scales(), R.shifts(), interpret=True))
            by = served()
            fused = by == ["pallas_dense.rft_cos"]
        else:
            served = feature_kernels("GaussianRFT")
            out, first, run = timed(lambda: R.apply(Xf, sk.ROWWISE))
            by = served()
            fused = by in (["pallas_planes"], ["pallas_generate"])
        if not fused or not plan["kernel"] or (
                fs == wide[2] and plan["s_tile"] >= fs):
            raise AssertionError(
                f"GaussianRFT {fm}x{fn}->{fs}: served by {by or 'xla'} "
                f"under plan {plan}, not the fused cos-epilogue kernel")
        err = close(np.asarray(out)[:rows], rft_oracle(R, fn, Xf[:rows]),
                    "GaussianRFT (cos epilogue) vs host oracle")
        report("sketch.GaussianRFT.fused", first, run,
               shape=f"{fm}x{fn}->{fs}", backend=by[0], plan=plan["plan_id"],
               operator_residency=plan["operator_residency"],
               err=f"{err:.2e}")

    # SRHT: FJLT with the Walsh-Hadamard mixer, against its dense
    # operator panel on a slice of rows
    F = sk.FJLT(N, S, Context(seed=2), fut="wht")
    served = launches(dense_launchers)
    out, first, run = timed(lambda: F.apply(A, sk.ROWWISE))
    rows = min(M, 256)
    ref = np.asarray(A[:rows]) @ np.asarray(F.operator_panel(0, N)).T
    err = close(np.asarray(out)[:rows], ref, "FJLT(wht) vs operator panel")
    report("sketch.FJLT_wht.rowwise", first, run, shape=f"{M}x{N}->{S}",
           backend=(served() or ["xla"])[0], err=f"{err:.2e}")
    # the same sketch columnwise (the Blendenpik call): on a TPU the
    # block-mix kernel of sketch/pallas_wht.py, then the sampled rows
    At = jnp.asarray(A.T)
    kernel = F.mix_plan(At, False)[0]
    if jax.default_backend() == "tpu" and kernel != "pallas_blocks":
        raise AssertionError(f"columnwise FJLT(wht) planned {kernel}")
    out, first, run = timed(lambda: F.apply(At, sk.COLUMNWISE))
    err = close(np.asarray(out)[:, :rows], ref.T,
                "FJLT(wht) columnwise vs operator panel")
    report("sketch.FJLT_wht.columnwise", first, run, shape=f"{N}x{M}->{S}",
           backend=kernel, err=f"{err:.2e}")

    # the FJLT's default mixer, the DCT, at a height that is no power of two
    # (the least-squares solvers' default sketch): the blocked DFT of
    # sketch/fut.py in one program, against the dense sampled-cosine operator
    # built on the host in float64. 96000 = 40·75·32: stage one's 75 rows a
    # slab are padded to 80 and its 38 outputs to 40 (whole 8-row tiles),
    # a 33rd block of slabs keeps the gathered rows off whole index tiles
    # (fut.dft_pads); whole rows of the operand (384 columns) are gathered
    # where they lie, and all 40 slabs of the sampled digit fit one pass
    # (fjlt.dft_slabs)
    Nd, Sd, Md = (1000, 64, 40) if REHEARSE else (96000, 1024, 384)
    Fd = sk.FJLT(Nd, Sd, Context(seed=22))
    Ad = jnp.asarray(rng.standard_normal((Nd, Md), dtype=np.float32))
    plan = Fd.mix_plan(Ad, False)
    if plan is None or plan[0] != "xla_dft":
        raise AssertionError(f"columnwise FJLT(dct) of {Nd} planned {plan}")
    out, first, run = timed(lambda: Fd.apply(Ad, sk.COLUMNWISE))
    k = np.asarray(Fd.sample_indices(), np.int64)[:, None]
    j = np.arange(Nd, dtype=np.int64)[None, :]
    operator = (np.cos(np.pi * ((k * (2 * j + 1)) % (4 * Nd)) / (2.0 * Nd))
                * np.asarray(Fd.diagonal(), np.float64)[None, :]
                * np.sqrt(2.0 / Sd))
    err = close(out, operator @ np.asarray(Ad, np.float64),
                "FJLT(dct) columnwise vs dense cosine operator", tol=2e-6)
    report("sketch.FJLT_dct.columnwise", first, run, shape=f"{Nd}x{Md}->{Sd}",
           backend=plan[0], factors="x".join(map(str, plan[1])), tile=plan[2],
           err=f"{err:.2e}")

    # CountSketch, dense operand and the same operand as a SparseMatrix
    C = sk.CWT(N, S, Context(seed=3))
    h = np.asarray(C.bucket_indices())
    v = np.asarray(C.values())
    An = np.asarray(A)
    ref = np.zeros((S, M), np.float32)
    np.add.at(ref, h, v[:, None] * An.T)
    out, first, run = timed(lambda: C.apply(A.T, sk.COLUMNWISE))
    err = close(out, ref, "CWT dense vs host scatter")
    report("sketch.CWT.dense", first, run, shape=f"{N}x{M}->{S}",
           backend="xla", err=f"{err:.2e}")
    Asp = sp.random(N, M, density=0.01, format="csc", dtype=np.float32,
                    random_state=np.random.default_rng(4))
    ref = np.zeros((S, M), np.float32)
    np.add.at(ref, h, v[:, None] * Asp.toarray())
    Sm = SparseMatrix.from_scipy(Asp)
    program = sk_hash._sparse_program()     # one compiled XLA program an apply
    ran = program.stats.executions
    out, first, run = timed(lambda: C.apply(Sm, sk.COLUMNWISE))
    err = close(out, ref, "CWT sparse vs host scatter")
    report("sketch.CWT.sparse", first, run, nnz=Asp.nnz,
           backend=(f"xla:{program.name}" if program.stats.executions > ran
                    else "xla:eager"), err=f"{err:.2e}")
    # the same program rowwise: on a TPU the kernel that builds each result
    # tile in VMEM adds the terms up, held here to XLA's scatter-add of the
    # same lanes (the terms of a cell in another order: last ulp)
    from libskylark_tpu.sketch import sparse_serve

    Srows = SparseMatrix.from_scipy(Asp.T.tocsr())
    lanes = Srows.csr_device()
    kernel = sparse_serve.sparse_kernel(Srows.shape, S, int(lanes[0].shape[0]),
                                        lanes[0].dtype, True)
    if not REHEARSE and kernel != "pallas_rows":
        raise AssertionError(f"rowwise sparse CWT took {kernel} on a TPU")
    ref = jax.jit(sparse_serve.cwt_sparse_serve_apply,
                  static_argnames=("s_dim", "rowwise", "shape"))(
        jax.random.key_data(C._alloc.key), *lanes, s_dim=S, rowwise=True,
        shape=Srows.shape)
    out, first, run = timed(lambda: C.apply(Srows, sk.ROWWISE))
    err = close(out, ref, "CWT sparse rowwise vs xla scatter", tol=1e-6)
    report("sketch.CWT.sparse_rows", first, run, nnz=Asp.nnz,
           kernel=kernel, err=f"{err:.2e}")

    # feature maps at BASELINE.md's shape, dense RFT and Fastfood, each
    # against its explicit operator on the host for some rows
    X = jnp.asarray(rng.standard_normal((RFT_ROWS, RFT_D), dtype=np.float32))
    rows = min(RFT_ROWS, 128)
    sigma = float(np.sqrt(RFT_D))
    for R, oracle in (
            (sk.GaussianRFT(RFT_D, RFT_D, Context(seed=5), sigma=sigma),
             lambda R: rft_oracle(R, RFT_D, X[:rows])),
            (sk.FastGaussianRFT(RFT_D, RFT_D, Context(seed=5), sigma=sigma),
             lambda R: fastfood_oracle(R, X[:rows]))):
        name = type(R).sketch_type
        served = feature_kernels(name)      # Fastfood counts nothing: xla
        out, first, run = timed(lambda: R.apply(X, sk.ROWWISE))
        backend = (served() or ["xla"])[0]
        err = close(np.asarray(out)[:rows], oracle(R),
                    f"{name} vs host oracle")
        report(f"sketch.{name}", first, run,
               shape=f"{RFT_ROWS}x{RFT_D}->{RFT_D}", backend=backend,
               err=f"{err:.2e}")


# ---------------------------------------------------------------------------
# c. serve
# ---------------------------------------------------------------------------


def _storm(ex, submit, n_requests: int, warm: int):
    """Warm one full cohort, then ``n_requests`` more; returns (results,
    engine misses+recompiles inside the window, seconds)."""
    from libskylark_tpu import engine

    for f in [submit(i) for i in range(warm)]:
        f.result(timeout=900)
    before = engine.stats()
    t0 = time.perf_counter()
    outs = [f.result(timeout=900)
            for f in [submit(i) for i in range(n_requests)]]
    dt = time.perf_counter() - t0
    after = engine.stats()
    compiles = ((after.misses - before.misses)
                + (after.recompiles - before.recompiles))
    return outs, compiles, dt


def _serve_check(ex, label: str) -> dict:
    st = ex.stats()
    reasons = st["kernel"]["by_reason"]
    if "mosaic-reject" in reasons:
        raise AssertionError(f"{label}: Mosaic rejected a flush: {reasons}")
    if st["failed"] or st["flush_failures"]:
        raise AssertionError(f"{label}: failed requests: {st['failed']}")
    if st["mesh"] and st["mesh"]["flush_devices_min"] != st["mesh"]["devices"]:
        raise AssertionError(
            f"{label}: a flush's output sat on fewer devices than the "
            f"mesh has: {st['mesh']}")
    return {"flushes": st["flushes"],
            "backends": {k: v["flushes"]
                         for k, v in st["kernel"]["by_backend"].items()},
            "declined": {k: v["declined_flushes"]
                         for k, v in reasons.items()},
            **({"mesh": st["mesh"]} if st["mesh"] else {})}


def step_serve(mesh=None) -> None:
    import jax.numpy as jnp

    from libskylark_tpu import Context, engine
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.algorithms.regression import solve_l2_sketched

    rng = np.random.default_rng(6)
    max_batch = 8
    where = "mesh." if mesh is not None else ""
    T = sk.JLT(N, S, Context(seed=7))
    ops = [rng.standard_normal((SERVE_ROWS, N), dtype=np.float32)
           for _ in range(4)]
    with xla_reference():
        refs = [np.asarray(T.apply(jnp.asarray(a), sk.ROWWISE)) for a in ops]

    # the dispatch's own choice, then the batched Mosaic kernel pinned
    for kernel in (None, "pallas"):
        if kernel and mesh is not None:
            continue
        label = f"{where}serve.sketch[{kernel or 'default'}]"
        with engine.MicrobatchExecutor(
                max_batch=max_batch, linger_us=2_000_000, mesh=mesh,
                kernel=kernel) as ex:
            n_req = 4 * max_batch
            outs, compiles, dt = _storm(
                ex, lambda i: ex.submit_sketch(T, ops[i % 4],
                                               dimension=sk.ROWWISE),
                n_req, warm=max_batch)
            for i, out in enumerate(outs):
                err = close(out, refs[i % 4], f"{label} request {i}")
            info = _serve_check(ex, label)
        if compiles:
            raise AssertionError(
                f"{label}: {compiles} compiles after warm-up")
        if kernel == "pallas" and set(info["backends"]) != {"pallas"}:
            raise AssertionError(
                f"{label}: pinned kernel declined: {info}")
        say(label, requests=n_req, operand=f"{SERVE_ROWS}x{N}->{S}",
            window_s=f"{dt:.3f}", compiles_after_warmup=compiles,
            err=f"{err:.2e}", **info)

    # sketched least squares through the same executor tier
    m_ls, n_ls = N, 64
    Ts = sk.JLT(m_ls, 8 * n_ls, Context(seed=8))
    As = [rng.standard_normal((m_ls, n_ls), dtype=np.float32)
          for _ in range(4)]
    Bs = [rng.standard_normal((m_ls, 4), dtype=np.float32) for _ in range(4)]
    with xla_reference():
        refs = [np.asarray(solve_l2_sketched(
            jnp.asarray(a), jnp.asarray(b), Ts)) for a, b in zip(As, Bs)]
    with engine.MicrobatchExecutor(max_batch=4, linger_us=2_000_000,
                                   mesh=mesh) as ex:
        outs, compiles, dt = _storm(
            ex, lambda i: ex.submit_solve(As[i], Bs[i], Ts), 4, warm=4)
        for i, out in enumerate(outs):
            err = close(out, refs[i], f"serve.solve request {i}", tol=1e-3)
        info = _serve_check(ex, "serve.solve")
    if compiles:
        raise AssertionError(f"serve.solve: {compiles} compiles after warm-up")
    say(f"{where}serve.solve", requests=4, operand=f"{m_ls}x{n_ls}",
        window_s=f"{dt:.3f}", compiles_after_warmup=compiles,
        err=f"{err:.2e}", **info)


# ---------------------------------------------------------------------------
# d. solve
# ---------------------------------------------------------------------------


def planted(m: int, n: int, rank: int, seed: int):
    """A = U diag(sigma) V^T with known, geometrically decaying sigma."""
    rng = np.random.default_rng(seed)
    U, _ = np.linalg.qr(rng.standard_normal((m, rank)))
    V, _ = np.linalg.qr(rng.standard_normal((n, rank)))
    sigma = 100.0 * 0.7 ** np.arange(rank)
    return ((U * sigma) @ V.T).astype(np.float32), sigma


def step_solve(sharding=None) -> None:
    import jax.numpy as jnp

    from libskylark_tpu import Context, nla

    put = ((lambda x: jax.device_put(x, sharding)) if sharding is not None
           else jnp.asarray)
    rank = 10
    A_host, sigma = planted(M, N, 2 * rank, seed=9)
    A = put(A_host)
    params = nla.ApproximateSVDParams(num_iterations=2)
    factors = []

    def svd():
        factors[:] = nla.approximate_svd(A, rank, Context(seed=10), params)
        return factors[1]

    out, first, run = timed(svd)
    err = float(np.max(np.abs(np.asarray(out) / sigma[:rank] - 1.0)))
    if not err <= 1e-3:
        raise AssertionError(f"rand-SVD sigma rel error {err:.2e} > 1e-3")
    if sharding is not None:
        for factor, x in zip("USV", factors):
            on_all_devices(x, f"sharded rand-SVD {factor}")
    report("solve.approximate_svd", first, run, shape=f"{M}x{N}", rank=rank,
           sigma_err=f"{err:.2e}",
           sharded=sharding is not None)
    if sharding is not None:
        return

    rng = np.random.default_rng(11)
    A_ls = rng.standard_normal((LSQ_M, LSQ_N), dtype=np.float32)
    x_true = rng.standard_normal(LSQ_N)
    b = (A_ls.astype(np.float64) @ x_true
         + 0.1 * rng.standard_normal(LSQ_M)).astype(np.float32)
    x_ref = np.linalg.lstsq(A_ls.astype(np.float64), b.astype(np.float64),
                            rcond=None)[0]
    Aj, bj = jnp.asarray(A_ls), jnp.asarray(b)
    iters = []

    def solve():
        x, it = nla.fast_least_squares(Aj, bj, Context(seed=12))
        iters.append(int(it))
        return x

    out, first, run = timed(solve)
    if iters[-1] <= 0:
        raise AssertionError("Blendenpik fell back to the exact solver")
    err = float(np.linalg.norm(np.asarray(out, np.float64) - x_ref)
                / np.linalg.norm(x_ref))
    if not err <= 1e-3:
        raise AssertionError(f"Blendenpik vs numpy lstsq: {err:.2e} > 1e-3")
    report("solve.fast_least_squares", first, run,
           shape=f"{LSQ_M}x{LSQ_N}", lsqr_iters=iters[-1],
           err=f"{err:.2e}")


# ---------------------------------------------------------------------------
# e. train
# ---------------------------------------------------------------------------


def step_train() -> None:
    from libskylark_tpu import engine, train
    from libskylark_tpu.train import TrainJobSpec

    rng = np.random.default_rng(13)
    X = rng.standard_normal((KRR_ROWS, KRR_D)).astype(np.float32)
    Y = np.sin(X[:, 0]).astype(np.float32)
    ops = {"X": X, "Y": Y}
    hyper = {"num_features": KRR_FEATURES, "num_partitions": 4,
             "lam": 1e-2, "sigma": 4.0, "seed": 14, "tol": 1e-2}

    # slice by slice through the slice engine the job manager drives
    eng = train.make_engine("admm_krr", dict(hyper), ops)
    state, objectives, secs = eng.init(), [], []
    for _ in range(4):
        t0 = time.perf_counter()
        state = eng.step(state, 2)
        objectives.append(float(state["objective"]))
        secs.append(time.perf_counter() - t0)
    if not np.all(np.isfinite(objectives)):
        raise AssertionError(f"non-finite ADMM objective: {objectives}")
    if any(b > a * (1 + 1e-3) for a, b in zip(objectives, objectives[1:])):
        raise AssertionError(f"ADMM objective increased: {objectives}")
    say("train.admm_krr.slices", compile_s=f"{secs[0] - secs[-1]:.2f}",
        run_s=f"{secs[-1]:.4f}", rows=KRR_ROWS, features=KRR_FEATURES,
        objectives=",".join(f"{o:.5g}" for o in objectives))

    # the same job as best-effort work of a serving executor
    with engine.MicrobatchExecutor(max_batch=4) as ex:
        t0 = time.perf_counter()
        handle = ex.submit_train_job(
            TrainJobSpec(solver="admm_krr", hyper=dict(hyper),
                         budget_iters=400, slice_iters=2), operands=ops)
        res = handle.result(timeout=900)
        dt = time.perf_counter() - t0
        tstats = ex.stats()["train"]
    if not (res["converged"] and np.isfinite(res["objective"])
            and res["objective"] <= objectives[0]):
        raise AssertionError(f"train job did not converge: {res}")
    scalar(res["coef"])
    say("train.admm_krr.job", wall_s=f"{dt:.2f}",
        iterations=res["iterations"], objective=f"{res['objective']:.5g}",
        slices=tstats.get("slices_run"))


# ---------------------------------------------------------------------------
# f. four chips
# ---------------------------------------------------------------------------


def on_all_devices(x, what: str) -> None:
    devs = {s.device for s in x.addressable_shards}
    if len(devs) != len(jax.devices()):
        raise AssertionError(
            f"{what}: shards on {len(devs)} of {len(jax.devices())} devices")


def step_mesh() -> None:
    import jax.numpy as jnp

    import __graft_entry__ as graft
    from libskylark_tpu import Context
    from libskylark_tpu import parallel as par
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.parallel import shard_apply

    mesh = par.make_mesh((2, 2), devices=jax.devices()[:4])
    rng = np.random.default_rng(15)
    A_host = rng.standard_normal((M, N), dtype=np.float32)
    A = par.distribute(A_host, par.grid2d(mesh))
    on_all_devices(A, "grid2d operand")
    if not REHEARSE:  # the CPU client reports no memory statistics
        scalar(A)
        used = {d.id: d.memory_stats()["bytes_in_use"]
                for d in jax.devices()[:4]}
        if not all(v >= A_host.nbytes // 4 for v in used.values()):
            raise AssertionError(
                f"a device holds less than its operand shard: {used}")
        say("mesh.memory", after="grid2d operand", bytes_in_use=used)

    # sharded == unsharded, same seed (the reference's determinism oracle)
    T = sk.JLT(N, S, Context(seed=0))
    with xla_reference():
        ref = np.asarray(T.apply(jnp.asarray(A_host), sk.ROWWISE))
    with par.use_mesh(mesh):
        out, first, run = timed(lambda: T.apply(A, sk.ROWWISE))
        served = dispatch_span(lambda: T.apply(A, sk.ROWWISE))
    on_all_devices(out, "sharded JLT output")
    err = close(out, ref, "sharded JLT vs unsharded")
    # which program served it: the span of parallel/shard_apply.py's route
    if (served.get("route"), out.sharding.spec) != (
            "program", par.grid2d(mesh).spec):
        raise AssertionError(
            f"T.apply of a grid2d operand was served by {served}, "
            f"result laid {out.sharding.spec}")
    report("mesh.JLT.grid2d", first, run, mesh="2x2", err=f"{err:.2e}",
           route=served["route"], kernel=served["kernel"],
           collective=served["collective"],
           result=str(out.sharding.spec).replace(" ", ""))
    with par.use_mesh(mesh):
        step_solve(sharding=par.grid2d(mesh))

    # the fused kernel inside shard_map: one Mosaic program per device
    mesh1 = par.make_mesh(devices=jax.devices()[:4])
    A_cols = par.distribute(A_host, par.col_sharded(mesh1))
    piped = jax.jit(lambda X: shard_apply.rowwise(
        T, X, mesh1, interpret=REHEARSE))
    if not REHEARSE and "tpu_custom_call" not in piped.lower(
            A_cols).as_text():
        raise AssertionError("shard_map pipeline lowered without the kernel")
    out, first, run = timed(lambda: piped(A_cols))
    on_all_devices(out, "shard_map kernel output")
    err = close(out, ref, "shard_map kernel vs unsharded")
    report("mesh.shard_apply.rowwise", first, run, mesh="4",
           backend="pallas_dense.fused_partial", err=f"{err:.2e}")

    t0 = time.perf_counter()
    graft.dryrun_multichip(4)
    say("mesh.dryrun_multichip", wall_s=f"{time.perf_counter() - t0:.2f}")

    step_serve(mesh=mesh1)


# ---------------------------------------------------------------------------


def main() -> int:
    want = "cpu" if REHEARSE else "tpu"
    dev = jax.devices()[0]
    if dev.platform != want:
        print(f"chip_smoke: needs a {want} backend, found "
              f"{dev.platform!r}; nothing run", file=sys.stderr)
        return 1
    _DEVICE.update(platform=dev.platform, kind=dev.device_kind,
                   count=len(jax.devices()))

    import jaxlib

    from libskylark_tpu import engine

    os.makedirs(OUT_DIR, exist_ok=True)
    engine.enable_persistent_cache(
        os.path.join(HERE, "benchmarks", ".jax_cache"))
    jax.monitoring.register_event_listener(count_cache_event)
    say("start", jax=jax.__version__, jaxlib=jaxlib.__version__,
        compile_cache=jax.config.jax_compilation_cache_dir)

    t0 = time.perf_counter()
    steps = [("sketch", step_sketch), ("serve", step_serve),
             ("solve", step_solve), ("train", step_train)]
    if len(jax.devices()) >= 4:
        steps.append(("mesh", step_mesh))
    failed = []
    for name, fn in steps:
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — report every phase, then fail
            import traceback

            traceback.print_exc()
            say(f"{name} FAILED", error=repr(e)[:300])
            failed.append(name)
    if len(jax.devices()) < 4:
        say("mesh", skipped=f"{len(jax.devices())} device")
    say("done", wall_s=f"{time.perf_counter() - t0:.1f}",
        compile_cache_hits=_CACHE["hits"],
        compile_cache_misses=_CACHE["misses"],
        failed=",".join(failed) or "none")
    if failed:
        return 1
    if not REHEARSE:
        print(json.dumps({"ok": True, "device": {
            "platform": _DEVICE["platform"], "kind": _DEVICE["kind"],
            "count": _DEVICE["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
