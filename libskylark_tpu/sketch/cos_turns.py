"""cos of a phase given in TURNS: an exact reduction and one short polynomial.

The random Fourier feature maps take ``cos(x·w/σ + b)`` of 10⁸–10⁹ values
an apply, and the stock float32 ``cos`` spends most of its 60–90 vector
operations a value on a range reduction that is sound for every float32
radian (2/π has to be carried to ~150 bits). A phase formed in turns
needs none of it: ``t − round(t)`` is exact in float32 for every finite
``t`` (below 2²³ the difference of two floats half an ulp-multiple apart
is representable; from 2²³ on ``t`` is an integer and the difference is
0), so what is left is one odd polynomial on a quarter turn.

Plain ``jnp`` arithmetic: the same function finishes a result tile inside
a Pallas kernel (sketch/pallas_dense.py ``_finisher``) and runs outside
one. 16 vector operations a value (the rounding counted as one), no
table, no branch.
"""

from __future__ import annotations

import math

import jax.numpy as jnp

TURN = 2.0 * math.pi        # radians a turn: phase_in_turns = radians / TURN

# sin(2πw) = w·P(w²) on |w| ≤ ¼, P's coefficients lowest degree first:
# the float64 minimax fit (error 3.3e-9) rounded to float32 one
# coefficient at a time, the rest refitted around it — the output of
# ``python script/fit_cos_turns.py`` (tests/test_cos_turns.py holds the
# two together). Five are enough: float32 Horner leaves 1.6e-7, fifty
# times the fit's own error, so a sixth would buy nothing.
_SIN_TURNS = (
    6.283185005187988,
    -41.341617584228516,
    81.59864807128906,
    -76.49604797363281,
    39.13410949707031,
)


def cos_turns(t, outscale: float = 1.0):
    """``outscale · cos(2π·t)`` of a float32 phase ``t`` in turns.

    Contract (tests/test_cos_turns.py): for every finite float32 ``t``,
    ``|cos_turns(t) − cos(2πt)| ≤ 3e-7`` against float64 (measured
    1.7e-7 on a v5e, whose vector unit has no fused multiply-add; the
    stock float32 cosine's own is 0.7e-7) — the reduction
    ``r = t − round(t)`` is exact, so the bound does not grow with
    ``|t|``; forming ``t`` rounds the phase by ``|t|·2⁻²⁴`` turns, the
    same work as the ``|x|·2⁻²⁴`` radians the stock route rounds by.
    With another ``outscale`` (folded into the coefficients, which rounds
    each of them once more) the error is ≤ 4e-7·outscale. The result
    lies in ``[−outscale, outscale]`` whatever the phase — Horner's rule
    in float32 overshoots the peak by an ulp, hence the clip; an integer
    ``t`` gives exactly 1 (at another ``outscale``: it, to that rounding
    of the coefficients); ``±inf`` and ``NaN`` give ``NaN``, as the stock
    cosine does.

    The fold: cos(2πr) = sin(2π(¼ − |r|)) with w = ¼ − |r| in [−¼, ¼],
    where the odd polynomial of :data:`_SIN_TURNS` serves."""
    r = t - jnp.round(t)
    w = 0.25 - jnp.abs(r)
    u = w * w
    coef = [outscale * c for c in _SIN_TURNS]
    p = coef[-1]
    for c in coef[-2::-1]:
        p = p * u + c
    return jnp.clip(w * p, -outscale, outscale)
