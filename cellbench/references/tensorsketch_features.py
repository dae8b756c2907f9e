"""Plain reference of TensorSketch features for the polynomial kernel (Pham &
Pagh, "Fast and scalable polynomial kernels via explicit feature maps", KDD
2013), rowwise: for an example x and degree q,

    z(x) = IFFT( ∏_{k<q} FFT( √γ·C_k x + √c·s'_k·e_{h'_k} ) ),   real,

with C_k the k-th CountSketch into S buckets ((C_k x)[b] = Σ_{j: h_k(j)=b}
s_k(j)·x_j, h_k uniform on 0..S−1, s_k Rademacher, independent across k) and
(h'_k, s'_k) one more bucket and sign a factor: the homogeneity term, which
makes the map that of (γ⟨x, y⟩ + c)^q and not of ⟨x, y⟩^q. Said otherwise:
z(x) is the CountSketch of the tensor power x'^{⊗q} of x' = (√γ·x, √c) under
the bucket Σ_k g_k(j_k) mod S and the sign ∏_k t_k(j_k), g_k = (h_k, h'_k),
t_k = (s_k, s'_k) — :func:`tensor_power_sketch` states that form outright —
so that E⟨z(x), z(y)⟩ = ⟨x', y'⟩^q = (γ⟨x, y⟩ + c)^q.

It follows the published definition, not the program's code: each
CountSketch is an explicit scatter-add, each transform ``jnp.fft`` of the
whole length, in float32 / complex64; everything random is rebuilt from
(context seed, allocation counter, child path) alone by JAX's own generator:

* an allocation's key is ``fold_in(key(seed), counter)`` of JAX's Threefry
  generator; child k of it (the k-th CountSketch's own allocation) is
  ``fold_in(allocation key, k)``, and sub-stream t of any key is
  ``fold_in(key, t)``: sub-streams 0 and 1 of child k hold its buckets and
  its signs over the n input coordinates, sub-streams 100 and 101 of the
  parent the q homogeneity buckets and signs (libSkylark
  ``sketch/PPT_data.hpp:94-106`` builds q ``CWT_data_t`` and one hash in
  this order);
* streams, uniform integers and signs are laid out as
  ``references/sparse_hash.py`` spells out (chunks of 4096, Threefry-2x32-20,
  a bucket from two 32-bit draws reduced mod S, a sign from a word's top
  bit).

Departures, each stated where it is made:

* from the paper: the paper sketches x for the homogeneous kernel ⟨x, y⟩^q
  and appends a constant coordinate for the inhomogeneous one; upstream
  (``PPT_Elemental.hpp:155-185``) adds √c·s'_k to bucket h'_k of the k-th
  CountSketch instead, which is the CountSketch of that appended coordinate
  — upstream's form, and the reference's;
* from upstream: upstream draws every stream from one counter range of the
  context through Random123 and Boost's samplers, so the VALUES differ and
  the laws are upstream's; upstream's FFTW plans are r2c / c2r, here the
  full complex transform of the definition.
"""

from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

from cellbench.references.sparse_hash import CHUNK, _chunk_key, _chunk_words


def _buckets(stream_key, count: int, s: int):
    span, mult = jnp.uint32(s), jnp.uint32((1 << 32) % s)
    out = []
    for chunk_id in range(-(-count // CHUNK)):
        key = _chunk_key(stream_key, chunk_id)
        high = _chunk_words(jax.random.fold_in(key, 0))
        low = _chunk_words(jax.random.fold_in(key, 1))
        out.append(((high % span) * mult + low % span) % span)
    return jnp.concatenate(out)[:count].astype(jnp.int32)


def _signs(stream_key, count: int):
    words = jnp.concatenate([_chunk_words(_chunk_key(stream_key, c))
                             for c in range(-(-count // CHUNK))])[:count]
    return jnp.where((words >> jnp.uint32(31)) == 0, 1.0, -1.0).astype(
        jnp.float32)


def streams(context_seed: int, counter: int, n: int, s: int, q: int) -> dict:
    """The map's random parts for allocation ``counter`` of a context seeded
    ``context_seed``: ``h`` (q, n) int32 buckets and ``v`` (q, n) ±1 signs of
    the q CountSketches, ``hh`` (q,) and ``hv`` (q,) the homogeneity hash
    (and ``s`` itself)."""
    if not 0 < s < (1 << 16):
        raise ValueError(f"s must lie in (0, 65536), got {s}")
    alloc = jax.random.fold_in(jax.random.key(context_seed), counter)
    children = [jax.random.fold_in(alloc, k) for k in range(q)]
    return {
        "s": s,
        "h": jnp.stack([_buckets(jax.random.fold_in(c, 0), n, s)
                        for c in children]),
        "v": jnp.stack([_signs(jax.random.fold_in(c, 1), n) for c in children]),
        "hh": _buckets(jax.random.fold_in(alloc, 100), q, s),
        "hv": _signs(jax.random.fold_in(alloc, 101), q),
    }


def _low(x, precision: str):
    """A value as the reference reads it: as it is (``"highest"``) or
    rounded to bfloat16 (``"bf16"``, the control; reduce_precision, not a
    cast and back, which a compiled function may elide)."""
    if precision == "highest":
        return x
    if precision != "bf16":
        raise ValueError(f"unknown precision {precision!r}")
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@functools.partial(jax.jit, static_argnames=("s", "precision"))
def _features(X_rows, h, v, hh, hv, sg, sc, *, s: int, precision: str):
    P = None
    x = _low(X_rows, precision)
    for k in range(h.shape[0]):
        # the CountSketch: ±√γ·x_j added into bucket h_k(j), then the
        # homogeneity term into bucket h'_k
        W = jnp.zeros((X_rows.shape[0], s), jnp.float32)
        W = W.at[:, h[k]].add(sg * v[k][None, :] * x)
        W = W.at[:, hh[k]].add(sc * hv[k])
        F = jnp.fft.fft(_low(W, precision), axis=1)
        if precision == "bf16":      # each spectrum as one bfloat16 part
            F = jax.lax.complex(_low(F.real, precision), _low(F.imag, precision))
        P = F if P is None else P * F
    return jnp.real(jnp.fft.ifft(P, axis=1))


def features(X_rows, parts: dict, gamma: float, c: float,
             precision: str = "highest") -> jax.Array:
    """z(x) (r × s) of the rows ``X_rows`` (r × n). ``precision``
    ``"highest"`` is the reference; ``"bf16"`` (the operand, each
    CountSketch and each spectrum rounded to bfloat16) is the control: the
    reference put in the program's place one precision below."""
    with jax.default_matmul_precision("highest"):
        return _features(X_rows, parts["h"], parts["v"], parts["hh"],
                         parts["hv"], jnp.float32(math.sqrt(gamma)),
                         jnp.float32(math.sqrt(c)), s=int(parts["s"]),
                         precision=precision)


def polynomial_kernel(X_rows, gamma: float, c: float, q: int) -> jax.Array:
    """k(x_i, x_j) = (γ⟨x_i, x_j⟩ + c)^q over the rows."""
    gram = jnp.dot(X_rows, X_rows.T, precision=jax.lax.Precision.HIGHEST)
    return (jnp.float32(gamma) * gram + jnp.float32(c)) ** q


def kernel_variance(X_rows, gamma: float, c: float, q: int, s: int) -> jax.Array:
    """Var⟨z(x_i), z(x_j)⟩ over the draw of the q hashes, pair by pair. With
    x' = (√γ·x, √c), a = ⟨x', y'⟩², b = ‖x'‖²‖y'‖², e = Σ_i x'_i²y'_i²: a
    factor's four indices pair up as (j = j', l = l'), weight a; (j = l,
    j' = l'), weight b − e; or (j = l', j' = l), weight a − e; the product's
    terms collide with probability 1/S unless every factor pairs the first
    way (the mean's square), and mixed second and third pairings collide
    twice (order 1/S², left out):

        Var = [ (a + b − e)^q − a^q + (2a − e)^q − a^q ] / S

    — for q = 1 the CountSketch's (b + a − 2e)/S. The constant coordinate
    makes e as large as c², so it is not dropped."""
    hi = jax.lax.Precision.HIGHEST
    inner = jnp.float32(gamma) * jnp.dot(X_rows, X_rows.T, precision=hi) + jnp.float32(c)
    a = inner * inner
    diag = jnp.diagonal(inner)
    b = diag[:, None] * diag[None, :]
    sq = X_rows * X_rows
    e = jnp.float32(gamma * gamma) * jnp.dot(sq, sq.T, precision=hi) + jnp.float32(c * c)
    return ((a + b - e) ** q - a ** q + (2.0 * a - e) ** q - a ** q) / s


def _augmented(parts: dict) -> tuple:
    """(g, t): each factor's buckets and signs over the n + 1 coordinates of
    x' — the n of its CountSketch, then the homogeneity term's."""
    g = np.concatenate([np.asarray(parts["h"]), np.asarray(parts["hh"])[:, None]],
                       axis=1).astype(np.int64)
    t = np.concatenate([np.asarray(parts["v"], np.float64),
                        np.asarray(parts["hv"], np.float64)[:, None]], axis=1)
    return g, t


def tensor_power_sketch(X_rows, parts: dict, gamma: float, c: float) -> np.ndarray:
    """The same z(x) from the definition's other statement, in float64 on
    the host: the CountSketch of the explicit tensor power x'^{⊗q},
    x' = (√γ·x, √c), entry (j_0, …, j_{q−1}) added with sign ∏ t_k(j_k) into
    bucket Σ g_k(j_k) mod S. (n + 1)^q terms a row: small n only (the
    tests')."""
    X = np.asarray(X_rows, np.float64)
    s, q = int(parts["s"]), parts["h"].shape[0]
    g, t = _augmented(parts)
    Xp = np.concatenate([math.sqrt(gamma) * X,
                         np.full((X.shape[0], 1), math.sqrt(c))], axis=1)
    out = np.zeros((X.shape[0], s))
    for js in itertools.product(range(Xp.shape[1]), repeat=q):
        bucket = sum(int(g[k, j]) for k, j in enumerate(js)) % s
        term = np.ones(X.shape[0])
        for k, j in enumerate(js):
            term = term * t[k, j] * Xp[:, j]
        out[:, bucket] += term
    return out


def law_z_scores(parts: dict, bins: int) -> dict:
    """How far the streams are from the laws the configuration states, each
    factor's hash taken over its n + 1 coordinates (the n of its CountSketch
    and the homogeneity term's): the buckets uniform on 0..S−1 — with
    n + 1 ≪ S a chi-square over the S buckets themselves sees little, so the
    z of two chi-squares over ``bins`` bins each, of the buckets' high part
    (⌊h·bins/S⌋: the range and its coverage) and of their low part
    (h mod bins: a stride, a parity) — and the signs fair (z of their mean);
    each the worst over the q factors. A bucket outside 0..S−1 is a miss of
    every bin."""
    s = int(parts["s"])
    g, t = _augmented(parts)
    count = g.shape[1]
    expected = count / bins
    chi_z = sign_z = 0.0
    for k in range(g.shape[0]):
        inside = g[k][(g[k] >= 0) & (g[k] < s)]
        for part in (inside * bins // s, inside % bins):
            hist = np.bincount(part, minlength=bins).astype(np.float64)
            chi2 = float(((hist - expected) ** 2).sum() / expected) \
                + (count - inside.size) * bins
            chi_z = max(chi_z, abs(chi2 - (bins - 1)) / (2.0 * (bins - 1)) ** 0.5)
        sign_z = max(sign_z, abs(t[k].mean()) * count ** 0.5)
    return {"bucket_chi2_z": chi_z, "sign_mean_z": sign_z}
