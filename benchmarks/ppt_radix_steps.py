"""TensorSketch's spectral products formed by bucket class, read on the chip
outside the cell: at the cell's shape (60,000 × 784 → 16384, q = 3) and a
block of 4096 examples, by radix R — (a) the class ordering and the 2R
products of one sketch alone, ms a block; (b) the whole program
``sketch.tensorsketch_features``, ms an apply, and its distance from the
radix 1 program on the same operand.

    python3 benchmarks/ppt_radix_steps.py [radix ...]

Prints one line a reading; a TPU is wanted (on a CPU it runs tiny shapes
and says so: no number of that run is a device number).
"""
import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.getcwd())
from libskylark_tpu.base.context import Context                 # noqa: E402
from libskylark_tpu.ml import kernels                           # noqa: E402
from libskylark_tpu.sketch import ppt                           # noqa: E402


def _time(f, *args, repeat=5):
    out = f(*args)
    jax.block_until_ready(out)
    best = []
    for _ in range(repeat):
        t = time.perf_counter()
        out = f(*args)
        jax.block_until_ready(out)
        best.append(time.perf_counter() - t)
    return 1e3 * float(np.median(best)), out


def _breakdown(compiled, args, tag, top=16):
    """The device ops of one traced apply, ms, largest first (a TPU only)."""
    import shutil
    import tempfile

    from cellbench import trace

    where = tempfile.mkdtemp(prefix=f"ppt_radix_{tag}_")
    with jax.profiler.trace(where):
        jax.block_until_ready(compiled(*args))
    sums = {}
    for (plane, _), events in trace.load(trace.find_xplane(where)).items():
        if plane.startswith(trace.DEVICE_PLANE):
            for name, _, dur in trace.leaves(events):
                short = trace.short_op_name(name)
                sums[short] = sums.get(short, 0.0) + dur * 1e-6
    shutil.rmtree(where, ignore_errors=True)
    total = sum(sums.values())
    print(f"  traced apply: {total:.2f} ms in {len(sums)} ops")
    for name, ms in sorted(sums.items(), key=lambda kv: -kv[1])[:top]:
        print(f"    {ms:8.2f}  {name}")


def main(radices):
    chip = jax.devices()[0].platform == "tpu"
    rows, n, s, q, block = (60000, 784, 16384, 3, 4096) if chip else (64, 100, 2048, 3, 32)
    print(f"device {jax.devices()[0].device_kind} rows {rows} n {n} s {s} q {q}"
          + ("" if chip else "  (CPU: shapes cut, no device number)"))
    T = kernels.Polynomial(n, q=q, c=1.0, gamma=1.0 / n).create_rft(s, Context(63))
    spec = (T.sketch_type, n, s, tuple(sorted(T._extra_params().items())))
    A = jnp.asarray(np.random.default_rng(63).standard_normal((rows, n)), jnp.float32)
    Xb = A[:block]
    cwt = T._cwts[0]
    h, v = cwt.bucket_indices(), cwt.values(jnp.float32)
    sample = None
    for r in radices:
        row_block = 0
        if isinstance(r, str):          # "4:1024": another block of the walk
            r, row_block = (int(a) for a in r.split(":"))
            print(f"row_block {row_block}")
        cols = ppt.class_cols(n, r)
        counts = np.bincount(np.asarray(h) % r, minlength=r)
        print(f"radix {r} class_cols {cols} k_tiles {ppt.k_tiles(n, 'float32', r)} "
              f"largest class {counts.max()}")

        # (a) one sketch's product alone: operator made outside, the block's
        # examples ordered and packed inside
        if r == 1:
            W = ppt.packed(ppt.spectral_operator(h, v, s), 1)

            def product(Xb, W):
                return jnp.dot(ppt.packed(Xb, 0), W,
                               precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=jnp.float32)
            ms, _ = _time(jax.jit(product), Xb, W)
        else:
            W, order, _ = jax.jit(functools.partial(
                ppt.class_operator, s=s, radix=r))(h, v)

            def product(Xb, W, order):
                x = ppt.class_ordered(Xb, [order], r, "float32")[0]
                return ppt.class_sums(x, W)
            ms, _ = _time(jax.jit(product), Xb, W, order)
        print(f"  product alone (ordering inside): {ms:.3f} ms a block, "
              f"{ms * 3 * -(-rows // block):.1f} ms an apply of three")

        # (b) the whole program
        program = jax.jit(functools.partial(
            ppt.tensorsketch_features, spec=spec, rowwise=True, radix=r,
            row_block=row_block))
        t = time.perf_counter()
        compiled = program.lower(T._alloc.key_data, A).compile()
        compile_s = time.perf_counter() - t
        ms, out = _time(compiled, T._alloc.key_data, A, repeat=4)
        rows_checked = np.asarray(out[:256])
        if sample is None:
            sample = rows_checked
        rel = float(np.abs(rows_checked - sample).max() / np.abs(sample).max())
        del out
        print(f"  whole program: {ms:.2f} ms an apply  (compile {compile_s:.1f} s; "
              f"first 256 rows against the first: {rel:.2e})")
        if chip:
            _breakdown(compiled, (T._alloc.key_data, A), f"r{r}")


if __name__ == "__main__":
    main([a if ":" in a else int(a) for a in sys.argv[1:]] or [1, 2, 4, 8])
