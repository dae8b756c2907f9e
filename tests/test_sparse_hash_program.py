"""The compiled sparse hash apply: ``CWT|MMT|WZT.apply(SparseMatrix, …)`` is
one ``engine.compiled`` program an apply — the pure function the sparse serve
flush vmaps — on the operand's device-resident row-major lanes.

Oracles:

- *plain reference*: ``cellbench/references/sparse_hash.py`` (imports nothing
  of the program): h and, for the CountSketch, v rebuilt from the stream
  definition, and Z as a dense ``segment_sum`` over each row's own entries;
- *bit-equality* with ``apply(A.todense())`` (row-major accumulation is the
  dense ``segment_sum``'s order) and, for CWT, with the serve flush of the
  same operand;
- *one program, placed once*: the second apply of an operand compiles nothing
  and moves nothing from the host;
- the ``sketch.dispatch`` span and the ``sketch.sparse_nnz`` counter carry
  the operand's nnz and the kernel that adds the terms up (off the TPU the
  scatter; tests/test_pallas_sparse_rows.py has the rule), and the span how
  the program looks a nonzero up;
- *computed, not gathered*: the CountSketch program's jaxpr holds no
  ``gather`` (bucket and sign are ``randgen.stream_at`` at the lane), the
  MMT and WZT programs' exactly one (their value stream's table).
"""

from __future__ import annotations

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import scipy.sparse as sp

from cellbench.references import sparse_hash as reference
from libskylark_tpu import Context, engine, telemetry
from libskylark_tpu import sketch as sk
from libskylark_tpu.base import sparse as sparse_mod
from libskylark_tpu.base.sparse import SparseMatrix
from libskylark_tpu.engine import bucket
from libskylark_tpu.sketch.sparse_serve import cwt_sparse_serve_apply
from libskylark_tpu.telemetry import metrics, trace

FAMILIES = [(sk.CWT, {}), (sk.MMT, {}), (sk.WZT, {"p": 1.5})]
DIMENSIONS = [sk.ROWWISE, sk.COLUMNWISE]
N, S = 301, 24      # N is not a multiple of 128 (nor of the stream's chunk)
S_DIMS = [S, 32]    # a power of two: the bucket's high draw cancels, unciphered
SEED, COUNTER = 11, 0


def zipf_operand(rows: int, n: int, seed: int) -> sp.csr_matrix:
    """Ragged rows (one of them empty, one made of a single feature drawn
    many times) whose features follow a Zipf law over ranks scattered over
    the ids; repeated draws of one (row, feature) are summed."""
    rng = np.random.default_rng(seed)
    weights = 1.0 / np.arange(1, n + 1)
    ids = rng.permutation(n)
    r, c, v = [], [], []
    for row in range(rows):
        if row == 3:
            continue                                    # an empty row
        length = int(rng.integers(1, 40))
        feats = (np.full(7, ids[0]) if row == 5 else    # one repeated feature
                 ids[rng.choice(n, size=length, p=weights / weights.sum())])
        r += [row] * len(feats)
        c += list(feats)
        v += list(np.abs(rng.standard_normal(len(feats))))
    X = sp.coo_matrix((np.asarray(v, np.float32), (r, c)), shape=(rows, n)).tocsr()
    X.sum_duplicates()
    return X


def operand(dimension, seed=4):
    """(SparseMatrix as the apply takes it, the examples × features CSR)."""
    X = zipf_operand(37, N, seed)
    return SparseMatrix.from_scipy(X if dimension == sk.ROWWISE else X.T), X


@pytest.fixture()
def fresh():
    engine.reset()
    before = metrics._ENABLED
    trace.clear_finished()
    yield
    metrics._ENABLED = before
    trace.clear_finished()
    engine.reset()


@pytest.mark.parametrize("dimension", DIMENSIONS)
@pytest.mark.parametrize("family,kwargs", FAMILIES)
class TestAgainstTheOracles:
    def transform(self, family, kwargs, s_dim=S):
        return family(N, s_dim, Context(SEED), **kwargs)

    @pytest.mark.parametrize("s_dim", S_DIMS)
    def test_matches_the_plain_reference(self, fresh, family, kwargs, dimension,
                                         s_dim):
        T = self.transform(family, kwargs, s_dim)
        A, X = operand(dimension)
        h, v = reference.streams(SEED, COUNTER, N, s_dim)
        if family is not sk.CWT:        # the reference holds the sign law only
            v = T.values()
        want = np.asarray(reference.apply_rows(X.toarray(), h, v, s_dim))
        got = np.asarray(T.apply(A, dimension))
        if dimension == sk.COLUMNWISE:
            got = got.T
        assert got.shape == want.shape == (37, s_dim)
        scale = np.abs(want).max()
        assert np.abs(got - want).max() <= 1e-6 * scale
        assert not got[3].any()         # the empty row stays empty
        assert np.count_nonzero(got[5]) == 1    # one feature, one bucket

    @pytest.mark.parametrize("s_dim", S_DIMS)
    def test_bit_equal_to_the_densified_apply(self, fresh, family, kwargs,
                                              dimension, s_dim):
        T = self.transform(family, kwargs, s_dim)
        A, _ = operand(dimension)
        assert np.array_equal(np.asarray(T.apply(A, dimension)),
                              np.asarray(T.apply(A.todense(), dimension)))

    def test_second_apply_compiles_and_moves_nothing(self, fresh, family, kwargs,
                                                     dimension, monkeypatch):
        T = self.transform(family, kwargs)
        A, _ = operand(dimension)
        placed = []
        place = sparse_mod._place
        monkeypatch.setattr(sparse_mod, "_place",
                            lambda x: placed.append(x.shape) or place(x))
        first = np.asarray(T.apply(A, dimension))
        assert len(placed) == 3 and engine.stats().compiles == 1
        lanes = A.csr_device()
        # a second transform of the family shares the executable: the key
        # data is an argument, not a constant of the program
        T2 = family(N, S, Context(SEED + 1), **kwargs)
        second = np.asarray(T.apply(A, dimension))
        other = np.asarray(T2.apply(A, dimension))
        assert len(placed) == 3 and engine.stats().compiles == 1
        assert all(a is b for a, b in zip(lanes, A.csr_device()))
        assert np.array_equal(first, second) and not np.array_equal(first, other)

    def test_span_and_counter_carry_the_nnz(self, fresh, family, kwargs, dimension):
        T = self.transform(family, kwargs)
        A, _ = operand(dimension)
        counter = metrics.registry().counter("sketch.sparse_nnz")
        before = counter.value(family=T.sketch_type, kernel="xla_scatter")
        telemetry.set_enabled(True)
        T.apply(A, dimension).block_until_ready()
        spans = trace.finished_spans()
        root = next(s for s in spans if s.name == "sketch.apply")
        kids = [s for s in spans if s.parent_id == root.span_id]
        assert sorted(s.name for s in kids) == ["sketch.dispatch", "stream.key"]
        dispatch = next(s for s in kids if s.name == "sketch.dispatch")
        assert root.attrs["path"] == "sparse"
        assert dispatch.attrs == {"path": "sparse", "family": T.sketch_type,
                                  "nnz": A.nnz,
                                  "nnz_class": bucket.lane_class(A.nnz),
                                  "lookup": ("lane" if family is sk.CWT
                                             else "lane+table"),
                                  "kernel": "xla_scatter",
                                  # the rows kernel's body, whichever
                                  # kernel this apply took (PR 41)
                                  "walk": "flat"}
        assert counter.value(family=T.sketch_type,
                             kernel="xla_scatter") - before == A.nnz
        # the one enqueue is the engine's call, under the dispatch span
        call = next(s for s in spans if s.name == "engine.call")
        assert call.parent_id == dispatch.span_id
        assert call.attrs["name"] == "sketch.hash_sparse"
        # every apply derives its key as the dense route does: a later one
        # opens the same two children
        trace.clear_finished()
        T.apply(A, dimension).block_until_ready()
        spans = trace.finished_spans()
        root = next(s for s in spans if s.name == "sketch.apply")
        kids = [s for s in spans if s.parent_id == root.span_id]
        assert [s.name for s in kids] == ["stream.key", "sketch.dispatch"]
        # ... from what the first apply kept: no derivation, no dispatch
        assert kids[0].attrs == {"what": "allocation", "path_len": 0,
                                 "cached": True}


    def test_lookup_is_computed_not_gathered(self, family, kwargs, dimension):
        T = self.transform(family, kwargs)
        A, _ = operand(dimension)
        program = functools.partial(
            cwt_sparse_serve_apply, s_dim=S, rowwise=dimension == sk.ROWWISE,
            shape=A.shape, values=T._value_kind())
        jaxpr = jax.make_jaxpr(program)(
            jax.random.key_data(T._alloc.key), *A.csr_device())
        assert _count(jaxpr.jaxpr, "scatter-add") == 2     # row ids, result
        assert _count(jaxpr.jaxpr, "gather") == (0 if family is sk.CWT else 1)


def _count(jaxpr, primitive: str) -> int:
    """Equations of ``primitive`` in ``jaxpr`` and every jaxpr inside it."""
    found = 0
    for eqn in jaxpr.eqns:
        found += eqn.primitive.name == primitive
        for inner in jax.core.jaxprs_in_params(eqn.params):
            found += _count(inner, primitive)
    return found


@pytest.mark.parametrize("dimension", DIMENSIONS)
def test_bit_equal_to_the_serve_flush(fresh, dimension):
    """The direct apply and the sparse serve flush run one pure function
    (``sparse_serve.cwt_sparse_serve_apply``) on the same row-major lanes."""
    T = sk.CWT(N, S, Context(SEED))
    A, _ = operand(dimension)
    direct = np.asarray(T.apply(A, dimension))
    with engine.MicrobatchExecutor(max_batch=4, linger_us=1000) as ex:
        served = np.asarray(
            ex.submit_sparse(T, A, dimension=dimension).result(timeout=120))
    assert np.array_equal(direct, served)


def test_ragged_operands_of_one_lane_class_share_the_executable(fresh):
    """The lanes are padded to ``engine.bucket.lane_class``: row blocks of
    one shape whose nnz differ inside a class compile once."""
    T = sk.CWT(N, S, Context(SEED))
    blocks = [SparseMatrix.from_scipy(zipf_operand(37, N, seed))
              for seed in (7, 8, 9)]
    classes = {bucket.lane_class(A.nnz) for A in blocks}
    assert len({A.nnz for A in blocks}) == 3 and len(classes) == 1
    for A in blocks:
        got = np.asarray(T.apply(A, sk.ROWWISE))
        assert np.array_equal(got, np.asarray(T.apply(A.todense(), sk.ROWWISE)))
    assert engine.stats().compiles == 1


@pytest.mark.parametrize("nnz,lanes", [
    (0, 64), (64, 64), (65, 68), (1000, 1024), (1025, 1088),
    (15_518_925, 15_728_640), (19_398_656, 19_922_944), (1 << 25, 1 << 25)])
def test_lane_class_wastes_under_a_sixteenth(nnz, lanes):
    """A resident operand's lanes: nnz rounded up to a thirty-second of its
    power of two (the serve tier's ``nnz_class`` doubles: 19.4 M → 2²⁵)."""
    assert bucket.lane_class(nnz) == lanes
    assert lanes >= nnz and (nnz < 64 or (lanes - nnz) * 16 < nnz)
    assert bucket.lane_class(lanes) == lanes


def _count_placements(monkeypatch):
    placed = []
    place = sparse_mod._place
    monkeypatch.setattr(sparse_mod, "_place",
                        lambda x: placed.append(x.shape) or place(x))
    return placed


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_device_layouts_are_placed_once_and_derived_on_the_device(dtype, monkeypatch):
    """``csr_device`` places the row-major lanes once per value dtype;
    ``coo`` after it is derived from them on the device (no second host
    pass)."""
    X = zipf_operand(37, N, 9).astype(dtype)
    A = SparseMatrix.from_scipy(X)
    placed = _count_placements(monkeypatch)
    data, indices, indptr = A.csr_device()
    r, c, v = A.coo()
    assert len(placed) == 3
    assert v.dtype == jnp.float32 and data.shape == (bucket.lane_class(A.nnz),)
    Xc = X.tocoo()      # canonical CSR order: by row, then by column
    assert np.array_equal(np.asarray(r), Xc.row)
    assert np.array_equal(np.asarray(c), Xc.col)
    assert np.array_equal(np.asarray(v), Xc.data.astype(np.float32))
    assert not np.asarray(data[A.nnz:]).any()
    assert np.array_equal(np.asarray(indptr), X.indptr)
    assert A.coo()[0] is r and len(placed) == 3
    assert np.array_equal(np.asarray(A.todense()), X.toarray().astype(np.float32))


def test_a_products_operand_holds_the_triplets_alone(monkeypatch):
    """``coo`` with no lanes resident places the exact lanes and keeps them
    *as* the triplets' columns and values: 12 B a nonzero, no padded second
    layout beside them (what an ``spmm`` user holds on the device)."""
    X = zipf_operand(37, N, 9)
    A = SparseMatrix.from_scipy(X)
    placed = _count_placements(monkeypatch)
    r, c, v = A.coo()
    assert placed == [(A.nnz,), (A.nnz,), (38,)]
    assert "csr" not in A._device[np.dtype(np.float32)]
    assert r.shape == c.shape == v.shape == (A.nnz,)
    Xc = X.tocoo()
    assert np.array_equal(np.asarray(r), Xc.row)
    assert np.array_equal(np.asarray(c), Xc.col)
    assert np.array_equal(np.asarray(v), Xc.data)
    assert A.coo()[2] is v and len(placed) == 3


def test_reference_streams_are_the_programs():
    """The reference rebuilds h and v from the stream definition alone."""
    n, s = 4096 + 77, 1000      # past one chunk; s not a power of two
    ctx = Context(123456789)
    ctx.allocate()
    T = sk.CWT(n, s, ctx)       # allocation counter 1
    h, v = reference.streams(123456789, 1, n, s)
    assert np.array_equal(np.asarray(h), np.asarray(T.bucket_indices()))
    assert np.array_equal(np.asarray(v), np.asarray(T.values()))
