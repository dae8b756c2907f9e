"""Plain reference of the hash sketch (CountSketch, CWT) of a sparse operand
whose result stays sparse: every stored nonzero (r, c, x) contributes v(c)·x
to (r, h(c)) — rowwise; v(r)·x to (h(r), c) columnwise — exactly once, the
terms of one cell summed, the result canonical CSR (a row's columns ascending
and distinct). h and v are rebuilt from (context seed, allocation counter)
alone, on the host, in numpy; the sums are float64. It imports nothing of the
program (ref: libSkylark sketch/hash_transform_local_sparse.hpp:12-152, CSC →
CSC with duplicates summed).

The stream definition it follows (README "Stream format", format 3):

* a key is two 32-bit words; ``key(seed)`` is (0, seed) for a seed under
  2³²; ``fold_in(key, w)`` is the cipher Threefry-2x32-20 (Salmon et al.,
  SC'11) of the counter (0, w) under ``key``, both output words the new key;
* an allocation's key is ``fold_in(key(seed), counter)`` (libSkylark
  base/context.hpp), sub-stream ``t`` of it ``fold_in(key, t)``: 0 holds the
  buckets, 1 the signs (sketch/hash_transform_data.hpp ``row_idx``,
  ``row_value``);
* a stream lies in chunks of 4096; chunk ``c`` has the key
  ``fold_in(fold_in(stream key, c >> 31), c & (2³¹ − 1))``, and the cipher of
  the counters (j, j + 2048), j < 2048, under it gives positions j and j +
  2048 of the chunk;
* a uniform integer on [0, s) takes two such draws, under
  ``fold_in(chunk key, 0)`` (high word) and ``fold_in(chunk key, 1)`` (low
  word): ((high mod s)·(2³² mod s) + low mod s) mod s in wrapping 32-bit
  arithmetic — for a power of two, low mod s;
* a Rademacher sign is +1 where the word's top bit is 0, else −1
  (sketch/CWT_data.hpp).
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp

CHUNK = 4096
_MASK31 = (1 << 31) - 1
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
_PARITY = 0x1BD11BDA


def threefry2x32(k0, k1, c0, c1):
    """Threefry-2x32, 20 rounds, on numpy uint32 arrays (broadcast)."""
    k0, k1, c0, c1 = (np.asarray(a, np.uint32) for a in (k0, k1, c0, c1))
    ks = (k0, k1, k0 ^ k1 ^ np.uint32(_PARITY))
    with np.errstate(over="ignore"):
        x0, x1 = c0 + ks[0], c1 + ks[1]
        for group in range(5):
            for r in _ROTATIONS[group % 2]:
                x0 = x0 + x1
                x1 = ((x1 << np.uint32(r)) | (x1 >> np.uint32(32 - r))) ^ x0
            x0 = x0 + ks[(group + 1) % 3]
            x1 = x1 + ks[(group + 2) % 3] + np.uint32(group + 1)
    return x0, x1


def fold_in(key, word):
    return threefry2x32(key[0], key[1], np.uint32(0), word)


def _stream_words(stream_key, n: int, sub=None) -> np.ndarray:
    """The first ``n`` 32-bit draws of the stream under ``stream_key``
    (``sub``: the draw's own fold of each chunk key, for a distribution of
    several draws)."""
    chunks = -(-n // CHUNK)
    cid = np.arange(chunks, dtype=np.int64)
    key = fold_in(fold_in(stream_key, (cid >> 31).astype(np.uint32)),
                  (cid & _MASK31).astype(np.uint32))
    if sub is not None:
        key = fold_in(key, np.uint32(sub))
    j = np.arange(CHUNK // 2, dtype=np.uint32)[None, :]
    lane0, lane1 = threefry2x32(key[0][:, None], key[1][:, None], j,
                                j + np.uint32(CHUNK // 2))
    return np.concatenate([lane0, lane1], axis=1).reshape(-1)[:n]


def streams(context_seed: int, counter: int, n: int, s: int) -> tuple:
    """(h, v): the bucket in [0, s) (int32) and the sign ±1 (float32) of each
    of the ``n`` hashed coordinates, for allocation ``counter`` of a context
    seeded ``context_seed``."""
    if not 0 < s <= 1 << 31 or not 0 <= context_seed < 1 << 32:
        raise ValueError(f"s {s} or seed {context_seed} out of range")
    alloc = fold_in((np.uint32(0), np.uint32(context_seed)), np.uint32(counter))
    high = _stream_words(fold_in(alloc, np.uint32(0)), n, sub=0)
    low = _stream_words(fold_in(alloc, np.uint32(0)), n, sub=1)
    span, mult = np.uint32(s), np.uint32((1 << 32) % s)
    with np.errstate(over="ignore"):
        h = ((high % span) * mult + low % span) % span
    words = _stream_words(fold_in(alloc, np.uint32(1)), n)
    v = np.where(words >> np.uint32(31) == 0, 1.0, -1.0)
    return h.astype(np.int32), v.astype(np.float32)


def _values(x: np.ndarray, precision: str) -> np.ndarray:
    """The operand's values as the reference reads them: ``"highest"`` as
    they are; ``"bf16"`` each rounded to bfloat16 (round to nearest even on
    the float32 bits) — the control one precision below."""
    x = np.asarray(x, np.float32)
    if precision == "bf16":
        bits = x.view(np.uint32)
        bits = (bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16)) & np.uint32(1))
                ) & np.uint32(0xFFFF0000)
        return bits.view(np.float32)
    if precision != "highest":
        raise ValueError(f"unknown precision {precision!r}")
    return x


def apply_csr(indptr, indices, data, h, v, s: int, shape: tuple,
              rowwise: bool = True, precision: str = "highest"
              ) -> sp.csr_matrix:
    """The sketch of the CSR operand ``(indptr, indices, data)`` of ``shape``
    as canonical float64 CSR: (rows × s) rowwise, (s × cols) columnwise. The
    relabelled triplets are summed per cell in float64 (scipy's
    ``sum_duplicates``: a per-row sort, then a running sum)."""
    # scipy sums duplicates in place, row pointers included: a copy of them
    indptr, indices = np.array(indptr), np.asarray(indices)
    x = _values(data, precision).astype(np.float64)
    if rowwise:
        Z = sp.csr_matrix((v[indices].astype(np.float64) * x, h[indices],
                           indptr), shape=(shape[0], s))
    else:
        rows = np.repeat(np.arange(shape[0]), np.diff(indptr))
        Z = sp.coo_matrix((v[rows].astype(np.float64) * x,
                           (h[rows], indices)), shape=(s, shape[1])).tocsr()
    Z.sum_duplicates()
    Z.sort_indices()
    return Z


def law_z_scores(h, v, s: int, bins: int) -> tuple:
    """(chi-square z of the buckets ``h`` against uniform on [0, s), z of the
    signs' mean against 0). The buckets are counted in ``bins`` classes two
    ways — by their high part (h · bins // s) and by their low part (h mod
    bins) — and the larger z is given: a law broken in the range shows in
    the first, one broken in the low bits in the second."""
    h = np.asarray(h, np.int64)
    n = h.shape[0]
    worst = 0.0
    for classes in (h * bins // s, h % bins):
        counts = np.bincount(classes, minlength=bins).astype(np.float64)
        chi2 = float(np.sum((counts - n / bins) ** 2) / (n / bins))
        worst = max(worst, abs(chi2 - (bins - 1)) / (2.0 * (bins - 1)) ** 0.5)
    return worst, abs(float(np.mean(v))) * n ** 0.5
