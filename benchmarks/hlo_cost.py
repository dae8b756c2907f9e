"""Compiled-HLO cost analysis as a hardware-free perf regression artifact.

CPU wall-clock drifts with the host and on-chip numbers need chip
time. XLA's compiled cost model needs neither: for a fixed jitted computation at
fixed shapes, ``flops`` and ``bytes accessed`` are deterministic
properties of the lowered HLO — a dispatch change that materializes an
extra operator, doubles a contraction, or breaks a fusion shows up as a
step change in these numbers with zero hardware and zero timing noise.

Covers the BASELINE.md configs' XLA paths (the Pallas kernel itself is
chip-only — its guard is the on-chip oracle battery, not this file):

- jlt_xla: headline dense sketch apply (8192x8192 -> s=1024), XLA path
- rft:     GaussianRFT feature map (65536x256 -> 4096)
- frft:    FastGaussianRFT Fastfood chain (16384x4096 -> 4096)
- cwt:     sparse hash scatter at full scale (2^20 rows, nnz ~ 268k)
- svd:     randomized SVD (262144x512, k=10) end-to-end jit

``--save N`` writes benchmarks/hlo_cost_r{N:02d}.json; ``--gate``
compares against the newest committed hlo_cost_r*.json and exits 1 when
any shared config's flops or bytes grew >10% (new configs are free;
vanished configs fail). Run by script/ci — the drift-proof half of the
r5 perf ratchet (the canary-normalized wall-clock half lives in
run_all.py).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# metrics whose growth the gate checks, with a 10% tolerance: flops and
# traffic are THE cost model; temp bytes catch a fusion break that
# spills an intermediate without changing either
GATED_KEYS = ("flops", "bytes_accessed", "temp_bytes")
TOLERANCE = 1.10


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def _analyze(name: str, jitted, *avals) -> dict:
    """Lower+compile ``jitted`` at ``avals`` and return its XLA cost /
    memory analysis as a flat record. Deterministic for fixed shapes and
    toolchain — zero hardware, zero timing noise."""
    compiled = jitted.lower(*avals).compile()
    ca = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    return {
        "config": name,
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
    }


def cfg_jlt_xla():
    """Headline config's XLA path: virtual-panel generation + one gemm
    (the sharded-apply workhorse; on TPU the Pallas kernel serves the
    eager single-device case instead)."""
    from libskylark_tpu.base.context import Context
    from libskylark_tpu.sketch import JLT, ROWWISE
    from libskylark_tpu.sketch import params as sketch_params

    m, n, s = 8192, 8192, 1024
    T = JLT(n, s, Context(seed=3))
    prev = sketch_params.get_use_pallas()
    sketch_params.set_use_pallas(False)
    try:
        f = jax.jit(lambda X: T.apply(X, ROWWISE))
        return _analyze("jlt_xla", f, _sds((m, n)))
    finally:
        sketch_params.set_use_pallas(prev)


def cfg_rft():
    from libskylark_tpu.base.context import Context
    from libskylark_tpu.sketch import ROWWISE
    from libskylark_tpu.sketch.rft import GaussianRFT

    n, d, s = 65536, 256, 4096
    T = GaussianRFT(d, s, Context(seed=2), sigma=2.0)
    f = jax.jit(lambda X: T.apply(X, ROWWISE))
    return _analyze("rft", f, _sds((n, d)))


def cfg_frft():
    from libskylark_tpu.base.context import Context
    from libskylark_tpu.sketch import ROWWISE
    from libskylark_tpu.sketch.frft import FastGaussianRFT

    n, d, s = 16384, 4096, 4096
    T = FastGaussianRFT(d, s, Context(seed=9), sigma=2.0)
    f = jax.jit(lambda X: T.apply(X, ROWWISE))
    return _analyze("frft", f, _sds((n, d)))


def cfg_cwt():
    from libskylark_tpu.base.context import Context
    from libskylark_tpu.sketch import CWT

    n, m, s = 1 << 20, 256, 4096
    nnz = 268435  # scipy.sparse.random(n, m, density=1e-3) nnz, fixed
    T = CWT(n, s, Context(seed=1))
    h, vals = T.bucket_indices(), T.values(jnp.float32)
    f = jax.jit(lambda r, c, v: jnp.zeros((s, m), v.dtype)
                .at[h[r], c].add(vals[r] * v))
    return _analyze("cwt", f, _sds((nnz,), jnp.int32),
                    _sds((nnz,), jnp.int32), _sds((nnz,)))


def cfg_svd():
    from libskylark_tpu.base.context import Context
    from libskylark_tpu.nla.svd import approximate_svd

    m, n, k = 262144, 512, 10
    ctx = Context(seed=5)
    f = jax.jit(lambda A: approximate_svd(A, k, ctx))
    return _analyze("svd", f, _sds((m, n)))


CONFIGS = (cfg_jlt_xla, cfg_rft, cfg_frft, cfg_cwt, cfg_svd)


def _newest_prior(exclude: str | None) -> tuple[int, dict] | None:
    best = None
    for p in glob.glob(os.path.join(HERE, "hlo_cost_r*.json")):
        if exclude and os.path.abspath(p) == os.path.abspath(exclude):
            continue
        mm = re.search(r"hlo_cost_r(\d+)\.json$", p)
        if not mm:
            continue
        try:
            with open(p) as fh:
                doc = json.load(fh)
        except Exception:
            continue
        rnd = int(mm.group(1))
        if best is None or rnd > best[0]:
            best = (rnd, doc)
    return best


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--save", type=int, metavar="ROUND", default=None)
    ap.add_argument("--gate", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated config-name substrings")
    args = ap.parse_args()

    configs = CONFIGS
    if args.only:
        want = [s.strip() for s in args.only.split(",") if s.strip()]
        configs = tuple(c for c in configs
                        if any(w in c.__name__ for w in want))
        if not configs:
            sys.exit(f"--only {args.only!r} matched nothing")

    rows = []
    for cfg in configs:
        try:
            row = cfg()
        except Exception as e:
            row = {"config": cfg.__name__.removeprefix("cfg_"),
                   "error": f"{type(e).__name__}: {e}"}
        rows.append(row)
        print(json.dumps(row), flush=True)

    doc = {"backend": jax.default_backend(),
           "jax_version": jax.__version__,
           "results": rows}

    save_path = (os.path.join(HERE, f"hlo_cost_r{args.save:02d}.json")
                 if args.save is not None else None)
    prior = _newest_prior(exclude=save_path)

    if save_path:
        tmp = save_path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(doc, fh, indent=1)
        os.replace(tmp, save_path)
        print(f"# saved {save_path}", file=sys.stderr)

    if args.gate:
        failures = []
        if prior is None:
            print("# gate: no prior hlo_cost_r*.json — nothing to "
                  "compare (first round records the baseline)",
                  file=sys.stderr)
            return
        rnd, pdoc = prior
        if pdoc.get("jax_version") != jax.__version__:
            # the cost model is XLA's own: a toolchain bump can move
            # every number without any repo change — report, don't fail
            print(f"# gate: prior r{rnd} used jax "
                  f"{pdoc.get('jax_version')}, this is {jax.__version__}"
                  " — comparison is informational only", file=sys.stderr)
        prior_rows = {r.get("config"): r
                      for r in pdoc.get("results", [])}
        ran = {r["config"] for r in rows}
        for name, prow in prior_rows.items():
            if args.only and name not in ran:
                continue  # a scoped run doesn't judge unran configs
            if "error" in prow:
                continue
            row = next((r for r in rows if r["config"] == name), None)
            if row is None or "error" in row:
                failures.append((name, "config vanished or now fails"))
                continue
            for key in GATED_KEYS:
                was, now = prow.get(key), row.get(key)
                if not was or now is None:
                    continue
                if now > was * TOLERANCE:
                    failures.append(
                        (name, f"{key} grew {now / was:.3f}x "
                               f"({was:.3e} -> {now:.3e})"))
        if failures and pdoc.get("jax_version") == jax.__version__:
            for name, why in failures:
                print(f"# HLO-COST REGRESSION {name}: {why}",
                      file=sys.stderr)
            sys.exit(1)
        for name, why in failures:
            print(f"# (informational) {name}: {why}", file=sys.stderr)


if __name__ == "__main__":
    main()
