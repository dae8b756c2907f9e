"""Golden bits of every stream family (stream format 3).

The virtual random streams define every sketch operator; serialized
sketches, resumed sessions and re-executed distributed shards all rely on
``(seed, counter, position) -> sample`` never moving. The streams are
written in the explicit integer ops of base/threefry.py precisely so that a
JAX upgrade cannot move them; these sha256 pins are the check that it did
not — and the tripwire for the one family (Gamma) that still samples through
``jax.random``. A digest that changes means ``STREAM_FORMAT`` must be bumped
(sketch/transform.py), never that the golden is quietly refreshed.
"""

import hashlib

import jax
import jax.random as jr
import numpy as np
import pytest

from libskylark_tpu.base import randgen
from libskylark_tpu.base import threefry as tf
from libskylark_tpu.base.context import Context
from libskylark_tpu.sketch.transform import SketchTransform

# [4000, 8300) of allocation 0 of Context(seed=42): a chunk boundary inside,
# both halves of a chunk's cipher lanes covered
GOLDEN_SLICES = {
    "uniform": (randgen.Uniform(0.0, 1.0), "float32",
                "afda848253b6229a39c8e51ba922ee55"
                "9f2ab50b960fb32479766beacbc208b4"),
    "uniform_int_pow2": (randgen.UniformInt(0, 1023), "int32",
                         "cfb6dea5b43e9a6c025ac089dce692b1"
                         "b92b11bdf9f29e5d91dda7b42e5ca9b0"),
    "uniform_int_100": (randgen.UniformInt(0, 99), "int32",
                        "9bae2b05b4929fbc9af9e96fc26af3d3"
                        "22175de496dc0c13e77d912e90bf7a1e"),
    "rademacher": (randgen.Rademacher(), "float32",
                   "cf946a1d516f491921869acc893909a6"
                   "646b23fe996ecb4b2caba090ee3d01d5"),
    "normal": (randgen.Normal(), "float32",
               "4665c6aa9ac8318ceafeb44993217257"
               "e8272eae9bb519071a66c5b6e4a16a5b"),
    "cauchy": (randgen.Cauchy(), "float32",
               "3146de7dee2d6d355c5f1b1de0656b3c"
               "2e66204ac6f9f5f2f6e8bdb59787f0c8"),
    "exponential": (randgen.Exponential(), "float32",
                    "0ab5083fabfa019e921f55706ca43c57"
                    "309b07063312d1d388de91dd205e0321"),
    "standard_levy": (randgen.StandardLevy(), "float32",
                      "e2fc0fa9837451bd0bf5f934341c1854"
                      "88e47b9c4a1e8c0f547a9c2f0b233fef"),
    "gamma": (randgen.Gamma(1.5, 2.0), "float32",
              "3870bfbebff8506796021c8897805966"
              "c81253cdf8c777bc33ac555ba720dc15"),
}
GOLDEN_PANEL = ("0c2b80f7b592cbac127aa4dc1d3e3231"
                "e7146d68d455dc5d166a7830092311b3")


def _sha(x, dtype) -> str:
    return hashlib.sha256(np.ascontiguousarray(
        np.asarray(x, dtype)).tobytes()).hexdigest()


def test_goldens_are_for_the_current_format():
    assert SketchTransform.STREAM_FORMAT == 3


@pytest.mark.parametrize("family", sorted(GOLDEN_SLICES))
def test_stream_slice_golden(family):
    dist, dtype, want = GOLDEN_SLICES[family]
    key = Context(seed=42).allocate().key
    got = randgen.stream_slice(key, dist, 4000, 8300, dtype=dtype)
    assert got.shape == (4300,)
    assert _sha(got, dtype) == want


def test_dense_panel_golden():
    key = Context(seed=42).allocate().key
    P = randgen.dense_panel(key, randgen.Normal(), 8, 0, 16, 256, "float32")
    assert _sha(P, "float32") == GOLDEN_PANEL


def test_stream_chunks_equals_stream_slice():
    key = Context(seed=7).allocate().key
    for dist, dtype in ((randgen.Normal(), "float32"),
                        (randgen.UniformInt(0, 99), "int32")):
        a = randgen.stream_chunks(key, dist, 1, 2, dtype=dtype)
        b = randgen.stream_slice(key, dist, randgen.CHUNK,
                                 3 * randgen.CHUNK, dtype=dtype)
        assert np.array_equal(np.asarray(a), np.asarray(b))


# n = 47,236 spans twelve chunks; the indices are unsorted, repeat, and hold
# both ends of a chunk's two cipher lanes, a chunk boundary and the last element
AT_N = 47_236
AT_IDX = np.array([4096, 0, 2047, 30_000, 2048, 4095, AT_N - 1, 2047, 0,
                   8191, 8192, 12, 45_056, 30_000, 4097], np.int32)
AT_DISTS = {
    "uniform_int_pow2": (randgen.UniformInt(0, 1023), "int32"),   # high draw dead
    "uniform_int_1000": (randgen.UniformInt(0, 999), "int32"),
    "uniform_int_above_2_16": (randgen.UniformInt(0, 99_999), "int32"),
    "rademacher": (randgen.Rademacher(), "float32"),
    "uniform_int_offset": (randgen.UniformInt(-5, 58), "int32"),  # low != 0, span 64
}


@pytest.mark.parametrize("how", ["eager", "jit", "vmap"])
@pytest.mark.parametrize("family", sorted(AT_DISTS))
def test_stream_at_equals_the_indexed_slice(family, how):
    """``stream_at`` computes at each index what ``stream_slice`` tabulates:
    the same bits, from a table never built."""
    dist, dtype = AT_DISTS[family]
    key = Context(seed=42).allocate().key
    want = np.asarray(randgen.stream_slice(key, dist, 0, AT_N, dtype=dtype))

    def at(idx):
        return randgen.stream_at(key, dist, idx, dtype=dtype)

    if how == "vmap":       # three lanes of their own indices, 2-D inside
        idx = np.stack([AT_IDX, AT_IDX[::-1], (AT_IDX * 7) % AT_N])
        got = jax.vmap(jax.jit(at))(idx.reshape(3, 5, 3))
        assert got.shape == (3, 5, 3)
        got = np.asarray(got).reshape(3, -1)
    else:
        idx = AT_IDX
        got = np.asarray((jax.jit(at) if how == "jit" else at)(idx))
    assert got.dtype == np.dtype(dtype)
    assert np.array_equal(got, want[idx])


def test_stream_at_reaches_chunk_ids_past_2_31_with_64_bit_indices():
    """``chunk_key``'s high word: zero for every 32-bit index, the chunk
    id's bits 31 and up for a 64-bit one."""
    key = Context(seed=42).allocate().key
    base = (1 << 43) + 4090         # chunk 2³¹, six elements short of its end
    for dist, dtype in AT_DISTS.values():
        want = randgen.stream_slice(key, dist, base, base + 12, dtype=dtype)
        with jax.enable_x64():
            got = randgen.stream_at(
                key, dist, base + np.arange(12, dtype=np.int64), dtype=dtype)
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_stream_at_needs_fixed_draws_and_integer_indices():
    key = Context(seed=42).allocate().key
    with pytest.raises(ValueError, match="fixed-draw"):
        randgen.stream_at(key, randgen.Gamma(1.5, 2.0), AT_IDX)
    with pytest.raises(TypeError, match="integer"):
        randgen.stream_at(key, randgen.Rademacher(), AT_IDX.astype(np.float32))


def test_key_derivation_matches_installed_jax():
    """``threefry.fold_in`` is the cipher ``jax.random.fold_in`` runs on
    threefry keys: allocation keys (Context, still ``jax.random``) and
    chunk keys (explicit) live in one key space."""
    key = jr.key(11)
    kd = jr.key_data(key)
    for data in (0, 1, 12345, (1 << 31) - 1):
        assert np.array_equal(np.asarray(tf.fold_in(kd, data)),
                              np.asarray(jr.key_data(jr.fold_in(key, data))))
    assert np.array_equal(
        np.asarray(jr.key_data(randgen.chunk_key(key, 5))),
        np.asarray(jr.key_data(jr.fold_in(jr.fold_in(key, 0), 5))))
    assert isinstance(randgen.chunk_key(key, 5), jax.Array)
