"""Plain reference of the FJLT with upstream's own mixer, the DCT,
columnwise (the Blendenpik sketch as libSkylark's FFTW build runs it):

    S·A = √(N/s) · R · (C_N / √(2N)) · D · A

with D a random ±1 diagonal, C_N the unnormalized DCT-II

    (C_N x)_k = 2 · Σ_j x_j · cos(π·k·(2j + 1) / (2N))        (FFTW REDFT10)

and R the rows ``idx`` sampled uniformly with replacement. N is any height:
nothing is padded, padding would be another operator.

It follows the published definitions, not the program's code:

* Blendenpik (Avron, Maymounkov, Toledo, SISC 32(3), 2010) sec. 4: mix the
  rows with a sign diagonal and a DCT (m padded to a multiple of 1000 so that
  FFTW's DCT is fast), sample γ·n of them uniformly; libSkylark
  ``sketch/FUT.hpp:138-140`` plans ``fftw_r2r`` REDFT10 and scales it
  1/√(2N) (``FUT.hpp:55-56``), ``sketch/FJLT_data.hpp:83-86`` draws the
  samples with replacement and ``sketch/FJLT_Elemental.hpp:144-174`` scales
  them by √(N/s). The k = 0 row of that scale has squared norm 2, every
  other 1: E‖S·A‖²_F = ‖A‖²_F·(1 + O(1/N));
* D and idx are the streams of ``references/srht.py`` (``streams``: an
  allocation's sub-stream 0 the signs, 1 the sampled coordinates, a draw
  reduced mod N in wrapping 32-bit arithmetic — for an N that is no power
  of two the high word of the draw counts), and so are the laws they are
  held to (``law_z_scores``);
* :func:`apply_cols` is the reference of the check: the float64 DCT-II of
  the sign-flipped columns on the host (scipy's pocketfft, ``type=2``,
  unnormalized — tied to the cosine sum above by this module's own test),
  the sampled rows scaled;
* :func:`cosine_sum_cols` is the cosine sum itself, on the device in
  float32, a block of the axis at a time with the phase k·(2j + 1) mod 4N
  reduced in 32-bit integers: what the controls run, with the operand cut
  to fewer bfloat16 parts or with the **cosine table rounded to bfloat16**
  — what a factor contracted in a single bfloat16 pass would serve.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from cellbench.references.srht import _values, law_z_scores, streams  # noqa: F401

BLOCK = 1000        # rows of the axis a step of the cosine sum takes


def apply_cols(A_cols, D, idx, precision: str = "highest") -> np.ndarray:
    """S·A_cols (s × columns, float64 on the host) for a block of columns
    A_cols (N × columns). ``precision`` cuts the operand's values as
    ``references/srht.py`` ``_values`` does (the controls); the transform
    is float64 whatever it says."""
    import scipy.fft

    n, s = A_cols.shape[0], idx.shape[0]
    x = np.asarray(D, np.float64)[:, None] * np.asarray(
        _values(jnp.asarray(A_cols), precision), np.float64)
    mixed = scipy.fft.dct(x, type=2, axis=0, norm=None, workers=-1)
    return mixed[np.asarray(idx)] * ((n / s) ** 0.5 / (2.0 * n) ** 0.5)


def _cosines(phase, period: int):
    """cos(2π·phase/period) for uint32 ``phase`` in [0, period), float32:
    the angle folded into [0, π] in integers first."""
    folded = jnp.minimum(phase, jnp.uint32(period) - phase)     # cos is even
    return jnp.cos(folded.astype(jnp.float32) * jnp.float32(2.0 * np.pi / period))


@functools.partial(jax.jit, static_argnames=("n", "precision", "table"))
def _cosine_sum(A_cols, D, idx, step, *, n: int, precision: str, table: str):
    s, cols = idx.shape[0], A_cols.shape[1]
    k = idx.astype(jnp.uint32)
    period = jnp.uint32(4 * n)
    odd = 2 * jnp.arange(BLOCK, dtype=jnp.uint32) + 1           # 2·j_lo + 1
    inside = (k[:, None] * odd[None, :]) % period               # < 2³¹ at N = 10⁶

    def block(b, acc):
        # k·(2j + 1) = (2·BLOCK·k mod 4N)·b + k·(2·j_lo + 1),  j = b·BLOCK + j_lo
        phase = ((step * b.astype(jnp.uint32)) % period)[:, None] + inside
        phase = jnp.where(phase >= period, phase - period, phase)
        C = 2.0 * _cosines(phase, 4 * n)
        if table == "bf16":
            C = jax.lax.reduce_precision(C, exponent_bits=8, mantissa_bits=7)
        rows = jax.lax.dynamic_slice(A_cols, (b * BLOCK, 0), (BLOCK, cols))
        signs = jax.lax.dynamic_slice(D, (b * BLOCK,), (BLOCK,))
        return acc + jnp.dot(C, signs[:, None] * _values(rows, precision),
                             precision=jax.lax.Precision.HIGHEST)

    acc = jax.lax.fori_loop(0, n // BLOCK, block, jnp.zeros((s, cols), jnp.float32))
    return acc * jnp.float32((n / s) ** 0.5 / (2.0 * n) ** 0.5)


def cosine_sum_cols(A_cols, D, idx, precision: str = "highest",
                    table: str = "float32") -> jax.Array:
    """S·A_cols by the cosine sum of the definition, float32 on the device:
    Σ_j over blocks of ``BLOCK`` rows — every column against one table of
    a block, 2·cos(π·k·(2j + 1)/(2N)) made from integer phases
    (``table="bf16"``: rounded to bfloat16 after that; the table is the
    cost, 4·10⁹ cosines at the cell's size). N must be a multiple of
    ``BLOCK`` with 2·BLOCK·N·4 < 2³² (the 32-bit products)."""
    n = A_cols.shape[0]
    if n % BLOCK or (n // BLOCK) * 4 * n >= 1 << 32 or n * (2 * BLOCK) >= 1 << 31:
        raise ValueError(f"the blocked cosine sum does not take N = {n}")
    step = np.asarray((2 * BLOCK * np.asarray(idx, np.int64)) % (4 * n), np.uint32)
    return _cosine_sum(A_cols, D, idx, jnp.asarray(step), n=n,
                       precision=precision, table=table)
