"""The recipe behind ``libskylark_tpu/sketch/cos_turns.py``'s coefficients.

    python script/fit_cos_turns.py            # prints the five coefficients

sin(2πw) on |w| ≤ ¼ as the odd polynomial w·P(w²), P of degree 4:

1. Remez exchange in float64 over the basis w^(2k+1) on (0, ¼] (a
   Chebyshev system: the error equioscillates on six points), absolute
   error — 3.3e-9 with five coefficients, a fiftieth of what float32's
   rounding leaves of Horner's rule (1.6e-7), so a sixth buys nothing.
2. The coefficients are rounded to float32 one at a time, lowest degree
   first, the rest refitted around each rounded one (c₀ ≈ 2π rounded
   alone would cost ¼·2π·2⁻²⁵ = 4.7e-8 at the quarter turn).

``tests/test_cos_turns.py`` holds the module's coefficients to this
script's output and the function to its error contract. numpy only;
takes a second.
"""

from __future__ import annotations

import numpy as np

N_COEF = 5


def _remez(n_coef: int, fixed: tuple = ()) -> tuple[list, float]:
    """Minimax fit of sin(2πw) − Σ fixed_k·w^(2k+1) by the remaining odd
    powers up to w^(2·n_coef − 1) on [0, ¼]: the coefficients (the fixed
    ones first) and the largest error."""
    powers = [2 * k + 1 for k in range(len(fixed), n_coef)]
    grid = 0.125 * (1.0 - np.cos(np.linspace(0.0, np.pi, 40001)))

    def target(w):
        return np.sin(2 * np.pi * w) - sum(
            c * w ** (2 * k + 1) for k, c in enumerate(fixed))

    m = len(powers) + 1
    # Chebyshev points of (0, ¼] as the first reference (w = 0 is a root
    # of every basis function and of the target: never a reference point)
    ref = 0.125 * (1.0 - np.cos(np.pi * (np.arange(m) + 0.5) / m))
    for _ in range(50):
        A = np.stack([ref ** p for p in powers]
                     + [(-1.0) ** np.arange(m)], axis=1)
        sol = np.linalg.solve(A, target(ref))
        coef, level = sol[:-1], abs(sol[-1])
        err = sum(c * grid ** p for c, p in zip(coef, powers)) - target(grid)
        # one extremum of the error between each pair of its sign changes
        edges = np.flatnonzero(np.diff(np.sign(err)) != 0) + 1
        peaks = np.array([p[np.argmax(np.abs(err[p]))]
                          for p in np.split(np.arange(grid.size), edges)
                          if np.abs(err[p]).max() > 0])
        peaks = peaks[np.argsort(-np.abs(err[peaks]))[:m]]
        if peaks.size != m or np.abs(err).max() <= level * (1 + 1e-6):
            break
        ref = np.sort(grid[peaks])
    return list(fixed) + [float(c) for c in coef], float(np.abs(err).max())


def fit(n_coef: int = N_COEF) -> tuple[list, float]:
    """Steps 1–2: the float32 coefficients (as Python floats, lowest
    degree first) and the float64 minimax error of step 1."""
    _, minimax = _remez(n_coef)
    fixed: list = []
    for k in range(n_coef):
        coef, _ = _remez(n_coef, tuple(fixed))
        fixed.append(float(np.float32(coef[k])))
    return fixed, minimax


if __name__ == "__main__":
    coefficients, minimax = fit()
    print(f"float64 minimax error, {N_COEF} coefficients: {minimax:.3g}")
    for c in coefficients:
        print(f"    {c!r},")
