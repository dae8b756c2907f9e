"""chip_smoke.py cannot rot between chip runs: its rehearsal mode runs the
same steps at tiny sizes on the CPU (kernels interpreted), and its default
invocation must refuse — non-zero exit, no result line, nothing run — when
JAX finds no TPU."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*args, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the smoke sizes its own virtual devices
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)


def test_default_invocation_refuses_without_a_tpu():
    out = _run(timeout=120)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "needs a tpu backend" in out.stderr


def test_rehearsal_runs_every_step_on_cpu():
    out = _run("--rehearse", timeout=600)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    # every line labelled; a rehearsal never prints the result object
    assert all(ln.startswith("[smoke REHEARSAL-ON-CPU] ") for ln in lines)
    assert all("platform=cpu" in ln for ln in lines)
    steps = {ln.split()[2] for ln in lines}
    for want in ("sketch.JLT.rowwise", "sketch.JLT.columnwise",
                 "sketch.GaussianRFT.fused", "sketch.FJLT_wht.rowwise",
                 "sketch.CWT.sparse", "sketch.CWT.sparse_rows",
                 "sketch.GaussianRFT",
                 "sketch.FastGaussianRFT", "serve.sketch[pallas]",
                 "serve.solve", "solve.approximate_svd",
                 "solve.fast_least_squares", "train.admm_krr.job",
                 "mesh.shard_apply.rowwise", "mesh.dryrun_multichip"):
        assert want in steps, (want, sorted(steps))
    # each JLT orientation names the plan it ran and where the operator
    # lived; the pipelined-generation leg is gone
    assert sum("sketch.JLT." in ln and "plan=pallas/" in ln
               and "operator_residency=" in ln for ln in lines) == 2
    # the feature map at the headline width and at speech widths (440
    # inputs, the result tiled along s): the fused cos kernel both times
    fused = [ln for ln in lines if "sketch.GaussianRFT.fused" in ln]
    assert len(fused) == 2 and all("backend=pallas_dense.rft_cos" in ln
                                   for ln in fused)
    assert "x440->" in fused[1] and "/st" in fused[1]
    assert not any("/pipe" in ln for ln in lines)
    # the rowwise sparse leg says which program added the terms up
    assert sum("sketch.CWT.sparse_rows" in ln and "kernel=xla_scatter" in ln
               for ln in lines) == 1
    assert "failed=none" in lines[-1]
