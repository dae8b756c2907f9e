"""Everything random in a run is a pure function of ``--seed``.

``--seed`` is any non-negative integer, also above 2**32. ``jax.random.key``
silently keeps only the low 32 bits of such a seed (2**32 + 5 gives the key
of 5), so the seed never reaches JAX directly: it is spread by numpy's
``SeedSequence`` (arbitrary-precision entropy) into 32-bit words first.
"""

from __future__ import annotations

import zlib

import numpy as np


def _sequence(seed: int, tag: str) -> np.random.SeedSequence:
    if seed < 0:
        raise ValueError(f"--seed must be non-negative, got {seed}")
    return np.random.SeedSequence(entropy=int(seed),
                                  spawn_key=(zlib.crc32(tag.encode()),))


def words(seed: int, tag: str, n: int) -> np.ndarray:
    """``n`` uint32 words for the stream ``tag`` of ``seed``."""
    return _sequence(seed, tag).generate_state(n, np.uint32)


def context_seed(seed: int) -> int:
    """The library ``Context`` seed of a run: 31 bits, so that it survives
    every int32/uint32 the library or JAX may put it through."""
    return int(words(seed, "context", 1)[0] & 0x7FFFFFFF)


def data_key(seed: int, tag: str):
    """A JAX PRNG key for generating operands on the device."""
    import jax

    return jax.random.wrap_key_data(
        jax.numpy.asarray(words(seed, tag, 2), dtype=jax.numpy.uint32))


def rng(seed: int, tag: str) -> np.random.Generator:
    """A numpy generator (for drawing which rows and entries are compared)."""
    return np.random.default_rng(_sequence(seed, tag))
