"""Plain reference of the rank-k randomized SVD (Halko, Martinsson & Tropp
2011, Alg. 4.4 + 5.1; ``libSkylark nla/svd.hpp`` ApproximateSVD): Gaussian
range sketch of width k', q power iterations with a QR after every product,
SVD of the projected panel, truncation to k. Householder QR and a dense SVD
from ``jnp.linalg``; every product at float32 ``highest`` unless a lower
``precision`` is asked for, which is what the controls do.

Also the planted operand and the five error numbers the check compares.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def _split(x):
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def matmul(a, b, precision: str = "highest"):
    """a·b at ``highest`` (float32), ``high`` (three bfloat16 passes:
    hi·hi + hi·lo + lo·hi) or ``bf16`` (one pass), float32 accumulation."""
    if precision == "highest":
        return jnp.matmul(a, b, precision=_HIGHEST)

    def bf16(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)

    if precision == "bf16":
        return bf16(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))
    if precision == "high":
        (ah, al), (bh, bl) = _split(a), _split(b)
        return bf16(ah, bh) + (bf16(ah, bl) + bf16(al, bh))
    raise ValueError(f"unknown precision {precision!r}")


def _orthonormal_columns(key, rows: int, cols: int):
    """A (rows × cols) matrix with orthonormal columns from a Gaussian one,
    by two Cholesky-QR passes (a tall Gaussian panel is well conditioned)."""
    X = jax.random.normal(key, (rows, cols), jnp.float32)
    for _ in range(2):
        R = jnp.linalg.cholesky(matmul(X.T, X), upper=True)
        X = jax.scipy.linalg.solve_triangular(R.T, X.T, lower=True).T
    return X


def spectrum(config: dict) -> jax.Array:
    """The planted singular values: ``rank`` of them fall geometrically over
    ``sigma_top``, the other ``planted_rank - rank`` over ``sigma_tail``."""
    k, r = config["rank"], config["planted_rank"]
    (t0, t1), (l0, l1) = config["sigma_top"], config["sigma_tail"]
    top = t0 * (t1 / t0) ** (jnp.arange(k) / max(k - 1, 1))
    tail = l0 * (l1 / l0) ** (jnp.arange(r - k) / max(r - k - 1, 1))
    return jnp.concatenate([top, tail]).astype(jnp.float32)


def planted(key, config: dict):
    """(A, U0, sigma, V0): A = U0·diag(sigma)·V0ᵀ, float32, of exact rank
    ``planted_rank``, so its top-``rank`` singular triplets are known."""
    ku, kv = jax.random.split(key)
    r = config["planted_rank"]
    U0 = _orthonormal_columns(ku, config["m"], r)
    V0 = _orthonormal_columns(kv, config["n"], r)
    sigma = spectrum(config)
    return matmul(U0 * sigma, V0.T), U0, sigma, V0


def randomized_svd(A, k: int, kp: int, q: int, key, precision: str = "highest"):
    """(U, s, V) of rank k. The plain algorithm; ``precision`` below
    ``highest`` makes it the control."""
    G = jax.random.normal(key, (A.shape[1], kp), jnp.float32)
    Q = jnp.linalg.qr(matmul(A, G, precision))[0]
    for _ in range(q):
        Z = jnp.linalg.qr(matmul(A.T, Q, precision))[0]
        Q = jnp.linalg.qr(matmul(A, Z, precision))[0]
    Ub, s, Vt = jnp.linalg.svd(matmul(Q.T, A, precision), full_matrices=False)
    return matmul(Q, Ub[:, :k], precision), s[:k], Vt[:k].T


def errors(A, U, s, V, U0, sigma, V0, rows, cols) -> dict:
    """The five numbers compared, all at float32 ``highest``:

    subspace_err  ‖X − P·X‖_F/√k for X = U against the planted top-k left
                  subspace and X = V against the right one, the larger;
    elem_err      max |U·diag(s)·Vᵀ − A_k| over the sampled rows × cols,
                  relative to max |A_k| there (A_k the planted truncation);
    ritz_resid    ‖A·V − U·diag(s)‖_F / ‖s‖₂ (one more pass over A);
    sigma_err     max |s − sigma[:k]| / sigma[0];
    orth_err      max |UᵀU − I| and |VᵀV − I|, the larger.
    """
    k = s.shape[0]
    U0k, V0k, sk = U0[:, :k], V0[:, :k], sigma[:k]

    def outside(X, B):
        return jnp.linalg.norm(X - matmul(B, matmul(B.T, X))) / jnp.sqrt(k)

    def gram_dev(X):
        return jnp.max(jnp.abs(matmul(X.T, X) - jnp.eye(k, dtype=X.dtype)))

    truth = matmul(U0k[rows] * sk, V0k[cols].T)
    got = matmul(U[rows] * s, V[cols].T)
    out = {
        "subspace_err": jnp.maximum(outside(U, U0k), outside(V, V0k)),
        "elem_err": jnp.max(jnp.abs(got - truth)) / jnp.max(jnp.abs(truth)),
        "ritz_resid": jnp.linalg.norm(matmul(A, V) - U * s) / jnp.linalg.norm(s),
        "sigma_err": jnp.max(jnp.abs(s - sk)) / sigma[0],
        "orth_err": jnp.maximum(gram_dev(U), gram_dev(V)),
    }
    return {name: float(v) for name, v in out.items()}
