"""An allocation's key is a pure function of three Python integers: derived
once, on the host, by ``base/threefry.py``'s cipher (bit-equal to what
``jax.random`` gives on the installed JAX), kept outside the dataclass, and
handed to each apply's one program as an argument — a warm apply issues no
``jax.random`` dispatch for it."""

import dataclasses
import itertools
import pickle
import sys
import threading

import jax
import jax.numpy as jnp
import jax.random as jr
import numpy as np
import pytest
import scipy.sparse as sp

from libskylark_tpu import SparseMatrix, telemetry
from libskylark_tpu import sketch as sk
from libskylark_tpu.base import context as context_mod
from libskylark_tpu.base.context import Allocation, Context
from libskylark_tpu.telemetry import metrics, trace

SEEDS = [0, 1, 42, 2**31 - 1, 2**31, 2**32 - 1, 2**32, 2**40 + 5, 2**63 - 1,
         -1, -5]
COUNTERS = [0, 3, 2**31]
PATHS = [(), (5,), (1, 2)]


def by_jax(seed, counter, path):
    key = jr.fold_in(jr.key(seed), counter)
    for p in path:
        key = jr.fold_in(key, p)
    return key


def hits():
    return metrics.registry().counter("stream.key_cache").value(result="hit")


def misses():
    return metrics.registry().counter("stream.key_cache").value(result="miss")


@pytest.fixture
def empty_cache():
    context_mod._KEY_CACHE.clear()
    yield context_mod._KEY_CACHE
    context_mod._KEY_CACHE.clear()


# ---------------------------------------------------------------------------
# the bits
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed,counter,path",
                         list(itertools.product(SEEDS, COUNTERS, PATHS)))
def test_host_replay_is_bit_equal_to_jax_random(seed, counter, path,
                                                empty_cache):
    want = np.asarray(jr.key_data(by_jax(seed, counter, path)))
    alloc = Allocation(seed, counter, path)
    words = alloc.key_words
    assert isinstance(words, np.ndarray) and words.dtype == np.uint32
    assert not words.flags.writeable
    assert np.array_equal(words, want)
    assert np.array_equal(np.asarray(alloc.key_data), want)
    assert alloc.key_data.dtype == jnp.uint32
    key = alloc.key
    assert key.dtype == by_jax(seed, counter, path).dtype and key.shape == ()
    assert np.array_equal(np.asarray(jr.key_data(key)), want)


@pytest.mark.parametrize("seed", [2**63, -2**63 - 1, 2**80])
def test_a_seed_jax_refuses_is_refused_the_same_way(seed):
    with pytest.raises(OverflowError) as theirs:
        jr.key(seed)
    with pytest.raises(OverflowError) as ours:
        Allocation(seed, 0).key_words
    assert str(ours.value) == str(theirs.value)


@pytest.mark.parametrize("word", [2**32, -1, 2**40])
def test_a_counter_jax_refuses_is_refused_the_same_way(word):
    with pytest.raises(OverflowError) as theirs:
        jr.fold_in(jr.key(0), word)
    for alloc in (Allocation(0, word), Allocation(0, 1, (word,))):
        with pytest.raises(OverflowError) as ours:
            alloc.key
        assert str(ours.value) == str(theirs.value)


def test_a_seed_addressed_endpoint_keys_like_jax_random():
    from libskylark_tpu.engine.serve import _seed_key_data

    for seed in SEEDS:
        got = _seed_key_data(seed)
        assert got.dtype == np.uint32
        assert np.array_equal(got, np.asarray(jr.key_data(jr.key(seed))))


# ---------------------------------------------------------------------------
# the cache
# ---------------------------------------------------------------------------


def test_equal_allocations_share_one_derivation(empty_cache):
    first = Context(seed=77).allocate()
    h, m = hits(), misses()
    key = first.key
    assert (hits() - h, misses() - m) == (0, 1)
    rebuilt = Allocation.from_dict(first.to_dict())
    assert rebuilt is not first
    assert rebuilt.key is key and Allocation(77, 0).key is key
    assert rebuilt.key_data is first.key_data
    assert rebuilt.key_words is first.key_words
    assert (hits() - h, misses() - m) == (6, 1)
    assert len(empty_cache) == 1
    child = first.child(4)
    assert np.array_equal(
        child.key_words, np.asarray(jr.key_data(jr.fold_in(key, 4))))
    assert Allocation(77, 0, [4]).key is child.key      # a list path too


def test_host_words_first_then_the_device_array(empty_cache):
    alloc = Allocation(78, 2)
    h, m = hits(), misses()
    words = alloc.key_words                 # miss: no device involved
    assert empty_cache[(78, 2, ())].key is None
    data = alloc.key_data                   # miss: the device array is new
    assert alloc.key_words is words and alloc.key_data is data   # hits
    assert (hits() - h, misses() - m) == (2, 2)
    assert np.array_equal(np.asarray(data), words)


def test_the_cache_is_no_part_of_the_dataclass(empty_cache):
    a = Allocation(5, 9, (1, 2))
    before = (a.to_dict(), hash(a), pickle.dumps(a), dict(vars(a)))
    a.key, a.key_data, a.key_words
    assert (a.to_dict(), hash(a), pickle.dumps(a), dict(vars(a))) == before
    assert vars(a) == {"seed": 5, "counter": 9, "path": (1, 2)}
    assert [f.name for f in dataclasses.fields(a)] == [
        "seed", "counter", "path"]
    b = pickle.loads(pickle.dumps(a))
    assert b == a and hash(b) == hash(a) and b.key is a.key
    c = dataclasses.replace(a, counter=10)
    assert c != a and c.path == (1, 2)
    assert not np.array_equal(c.key_words, a.key_words)
    assert a.to_dict() == {"seed": 5, "counter": 9, "path": [1, 2]}
    with pytest.raises(dataclasses.FrozenInstanceError):
        a.seed = 6


@pytest.mark.parametrize("how", ["jit", "vmap", "shard_map"])
def test_first_access_under_a_trace_keeps_no_tracer(how, empty_cache):
    alloc = Allocation(91, ["jit", "vmap", "shard_map"].index(how))
    want = np.asarray(jr.key_data(by_jax(alloc.seed, alloc.counter, ())))

    def body(x):            # both accessors, first touched while tracing
        return (x + alloc.key_data[1]) ^ jr.key_data(alloc.key)[0]

    x = jnp.arange(8, dtype=jnp.uint32)
    if how == "jit":
        got = jax.jit(body)(x)
    elif how == "vmap":
        got = jax.vmap(body)(x.reshape(4, 2)).reshape(8)
    else:
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.asarray(jax.devices()[:4]), ("d",))
        got = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("d"),
                                    out_specs=P("d")))(x)
    assert np.array_equal(np.asarray(got),
                          (np.arange(8, dtype=np.uint32) + want[1]) ^ want[0])
    kept = empty_cache[(alloc.seed, alloc.counter, ())]
    for value in (kept.key, kept.data):
        assert value is not None and not isinstance(value, jax.core.Tracer)
    assert isinstance(kept.data, jax.Array)
    # a later eager access works, and gives what the trace saw
    assert np.array_equal(np.asarray(alloc.key_data), want)
    assert np.array_equal(np.asarray(jr.key_data(alloc.key)), want)
    assert np.array_equal(np.asarray(body(x)), np.asarray(got))


def test_eight_threads_agree_on_one_allocation(empty_cache):
    alloc = Allocation(123, 7, (3,))
    want = np.asarray(jr.key_data(by_jax(123, 7, (3,))))
    seen, errors = [[] for _ in range(8)], []
    start = threading.Barrier(8)

    def hammer(mine):
        try:
            start.wait(timeout=30)
            for i in range(300):
                if i % 50 == 0:        # some threads find the cache emptied
                    empty_cache.clear()
                mine.append((alloc.key_words, alloc.key_data, alloc.key))
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=hammer, args=(s,)) for s in seen]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert errors == []
    assert all(len(mine) == 300 for mine in seen)
    for words, data, key in itertools.chain.from_iterable(seen):
        assert np.array_equal(words, want)
    # the device values: distinct objects at most once a derivation
    datas = {id(d): d for mine in seen for _, d, _ in mine}
    keys = {id(k): k for mine in seen for _, _, k in mine}
    assert all(np.array_equal(np.asarray(d), want) for d in datas.values())
    assert all(np.array_equal(np.asarray(jr.key_data(k)), want)
               for k in keys.values())


def test_the_cache_stays_bounded_over_fresh_allocations(empty_cache):
    ctx = Context(seed=9)
    kept = ctx.allocate()
    kept_words = kept.key_words.copy()
    largest = 0
    for i in range(100_000):
        ctx.allocate().key_words
        if i % 1000 == 0:
            largest = max(largest, len(empty_cache))
    largest = max(largest, len(empty_cache))
    assert 0 < largest <= context_mod._KEY_CACHE_SIZE
    assert ctx.counter == 100_001
    # emptied along the way: the long-lived one derives again, same bits
    assert np.array_equal(kept.key_words, kept_words)
    assert np.array_equal(
        Allocation(9, 99_999).key_words,
        np.asarray(jr.key_data(by_jax(9, 99_999, ()))))


def test_each_access_is_one_span_that_says_whether_it_hit(empty_cache):
    before = metrics._ENABLED
    trace.clear_finished()
    telemetry.set_enabled(True)
    try:
        alloc = Allocation(31, 1, (2,))
        alloc.key, alloc.key, alloc.key_data, alloc.key_words
        spans = [s for s in trace.finished_spans() if s.name == "stream.key"]
    finally:
        metrics._ENABLED = before
        trace.clear_finished()
    assert [s.attrs for s in spans] == [
        {"what": "allocation", "path_len": 1, "cached": c}
        for c in (False, True, True, True)]
    assert all(s.duration_s > 0 for s in spans)


# ---------------------------------------------------------------------------
# the routes: a warm apply issues no jax.random dispatch for its key
# ---------------------------------------------------------------------------


def _refuse(name):
    def refused(*args, **kwargs):
        raise AssertionError(f"jax.random.{name} called by a warm apply")
    return refused


@pytest.fixture
def interpreted_dense(monkeypatch):
    """The dense dispatch into the fused kernel, interpreted (off the TPU
    the dispatch declines it; steered here, in the test)."""
    from libskylark_tpu.sketch import dense as dense_mod
    from libskylark_tpu.sketch import pallas_dense

    def interpreted(key, dist, A, s_dim, scale, which):
        return getattr(pallas_dense, which)(key, dist, A, s_dim, scale,
                                            interpret=True)

    monkeypatch.setattr(dense_mod, "try_pallas_apply", interpreted)


@pytest.fixture
def interpreted_features(monkeypatch):
    from libskylark_tpu.sketch import rft

    plan = rft.RFT._kernel_plan
    monkeypatch.setattr(rft.RFT, "_kernel_plan",
                        lambda self, A, interpret=False: plan(self, A, True))


def _dense(shape, seed=4):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(shape), jnp.float32)


def _route(name):
    if name in ("jlt_rowwise", "jlt_columnwise"):
        T = sk.JLT(512, 64, Context(61))
        rowwise = name == "jlt_rowwise"
        return T, _dense((24, 512) if rowwise else (512, 24)), (
            sk.ROWWISE if rowwise else sk.COLUMNWISE)
    if name == "cwt_sparse":
        A = SparseMatrix.from_scipy(sp.random(
            40, 300, density=0.05, format="csr", dtype=np.float32,
            random_state=np.random.default_rng(6)))
        return sk.CWT(300, 32, Context(62)), A, sk.ROWWISE
    return sk.GaussianRFT(440, 256, Context(63), sigma=30.0), _dense(
        (40, 440)), sk.ROWWISE


@pytest.mark.parametrize(
    "name", ["jlt_rowwise", "jlt_columnwise", "cwt_sparse", "rft_features"])
def test_a_warm_apply_derives_nothing(name, monkeypatch, empty_cache,
                                      interpreted_dense, interpreted_features):
    T, A, dimension = _route(name)
    cold = np.asarray(T.apply(A, dimension))
    for fn in ("key", "PRNGKey", "fold_in", "key_data", "wrap_key_data",
               "split"):
        monkeypatch.setattr(jr, fn, _refuse(fn))
    h, m = hits(), misses()
    before = metrics._ENABLED
    trace.clear_finished()
    telemetry.set_enabled(True)
    try:
        warm = np.asarray(T.apply(A, dimension))
        spans = trace.finished_spans()
    finally:
        metrics._ENABLED = before
        trace.clear_finished()
    assert (hits() - h, misses() - m) == (1, 0)
    assert np.array_equal(warm, cold)
    keyed = [s for s in spans if s.name == "stream.key"]
    assert [s.attrs["cached"] for s in keyed] == [True]
    assert keyed[0].attrs["what"] == "allocation"
    root = next(s for s in spans if s.name == "sketch.apply")
    assert keyed[0].parent_id == root.span_id
    assert root.attrs["path"] == ("sparse" if name == "cwt_sparse"
                                  else "pallas")
