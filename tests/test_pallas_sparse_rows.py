"""The rowwise sparse hash kernel (``sketch/pallas_sparse.py``
``hash_rows_apply``): each tile of result rows accumulated in VMEM from its
own run of CSR lanes, in place of XLA's sort and element scatter.

Interpret mode, small shapes, against the program it replaces
(``cwt_sparse_serve_apply`` with its scatter-add):

- *lattice data* (small integers: every sum exact in any order) — bit-equal;
- *float data* — ≤ 1e-6 relative (the terms of one cell are added in another
  order), and every cell with a single term equals ± its stored value to
  the bit (the benchmark reads buckets and signs out of served rows by that);
- the shapes a tile can take: an empty row, a row of 1024 nonzeros, rows
  straddling chunk, tile, block and grid-step borders, a last chunk that is
  all lane padding, two nonzeros of one row in one bucket; CWT, MMT, WZT;
- the shapes the walk can take (PR 41: one visit a (tile, chunk) pair):
  whole empty tiles and an empty grid step, rows of one nonzero (sixteen
  tiles in one chunk), a row longer than a ring block, tile runs that end
  exactly on chunk borders and on a ring-block border, no nonzero at all;
  ``rows_visits`` against the visits an instrumented run makes;
- *the engagement rule* (``sparse_serve.sparse_kernel``): the kernel only on
  a TPU, rowwise, float32, ``s_dim`` a multiple of 128, lanes a multiple of
  1024; the ``sketch.dispatch`` span and the ``sketch.sparse_nnz`` counter
  say which program ran.
"""

from __future__ import annotations

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import scipy.sparse as sp

from libskylark_tpu import Context, engine, telemetry
from libskylark_tpu import sketch as sk
from libskylark_tpu.base.sparse import SparseMatrix
from libskylark_tpu.sketch import pallas_sparse, sparse_serve
from libskylark_tpu.sketch.sparse_serve import cwt_sparse_serve_apply
from libskylark_tpu.telemetry import metrics, trace

N_COLS = 3000
LANES_MIN = 16384       # the least lane extent the kernel's plan takes


def _rows(lengths, rng, n_cols=N_COLS, first=()):
    """CSR of rows with the given lengths: distinct sorted columns a row,
    ``first`` forced into row 0."""
    r, c = [], []
    for i, n in enumerate(lengths):
        cols = rng.choice(n_cols, n, replace=False)
        if i == 0:
            cols = np.union1d(cols, np.asarray(first, cols.dtype))
        r.append(np.full(cols.size, i))
        c.append(np.sort(cols))
    r, c = np.concatenate(r), np.concatenate(c)
    return sp.csr_matrix((np.ones(r.size, np.float32), (r, c)),
                         shape=(len(lengths), n_cols), dtype=np.float32)


def _case(name: str, s_dim: int, rng):
    """(operand pattern, lane extent): the shape each case is named for."""
    lanes = LANES_MIN
    if name == "empty_row":
        lengths = rng.integers(1, 60, 32)
        lengths[[0, 7, 8, 31]] = 0      # first, a tile's last and first, last
    elif name == "row_1024":
        lengths = rng.integers(1, 40, 32)
        lengths[5] = 1024
    elif name == "straddles":
        # rows of 100 ± 30: no row or tile starts on a chunk border, a tile
        # (8 or 16 rows) spans six chunks and more, 512 rows make two grid
        # steps, and the second step's run ends in a block read clamped to
        # the lanes' end
        lengths = rng.integers(70, 130, 512)
        lanes = 1024 * -(-int(lengths.sum()) // 1024)
    elif name == "padding_chunk":
        lengths = rng.integers(1, 9, 32)    # < 256 nonzeros in 16384 lanes
    elif name == "empty_tiles":
        # 1024 rows = four grid steps: the second has no lane at all, the
        # others whole empty tiles (16 rows: a tile at R = 8 and at 16)
        # at their start, inside and at their end
        lengths = rng.integers(1, 30, 1024)
        lengths[256:512] = 0
        for lo in (0, 96, 240, 512, 752, 1008):
            lengths[lo:lo + 16] = 0
    elif name == "one_nonzero":
        lengths = np.ones(1024, np.int64)   # a tile is 8 or 16 lanes
    elif name == "long_row":
        # a row of more lanes than a ring block (128 chunks) holds: its
        # tile is walked through two blocks and part of a third
        lengths = rng.integers(1, 40, 32)
        lengths[11] = 128 * 128 + 300
        lanes = 1024 * -(-int(lengths.sum()) // 1024)
        return _rows(lengths, rng, n_cols=17000), lanes
    elif name == "aligned":
        # every tile's run is exactly one chunk: no chunk is shared (and at
        # R = 8 no lane is padding)
        r = 16 if (s_dim // 128) % 2 else 8
        lengths = np.full(1024, 128 // r)
    elif name == "block_border":
        # 16 rows of 1024: two tiles (one at R = 16) that end exactly where
        # the step's first ring block does, lane 16384; the rest follows
        lengths = np.concatenate([np.full(16, 1024), rng.integers(1, 60, 240)])
        lanes = 1024 * -(-int(lengths.sum()) // 1024)
    elif name == "nnz_zero":
        lengths = np.zeros(32, np.int64)
    elif name == "same_bucket":
        h = np.asarray(sk.CWT(N_COLS, s_dim, Context(3)).bucket_indices())
        twins = np.flatnonzero(h == h[0])[:2]
        assert twins.size == 2
        lengths = rng.integers(1, 30, 32)
        return _rows(lengths, rng, first=twins), lanes
    else:
        raise AssertionError(name)
    return _rows(lengths, rng), lanes


def _lanes(X: sp.csr_matrix, lanes: int):
    X = X.tocsr()
    X.sort_indices()
    pad = lanes - X.nnz
    return (jnp.asarray(np.pad(X.data.astype(np.float32), (0, pad))),
            jnp.asarray(np.pad(X.indices.astype(np.int32), (0, pad))),
            jnp.asarray(X.indptr.astype(np.int32)))


@functools.lru_cache(maxsize=None)
def _program(kernel: str):
    return jax.jit(functools.partial(cwt_sparse_serve_apply, kernel=kernel),
                   static_argnames=("s_dim", "rowwise", "shape", "values"))


def _both(T, X, lanes, s_dim):
    args = (jax.random.key_data(T._alloc.key), *_lanes(X, lanes))
    kw = dict(s_dim=s_dim, rowwise=True, shape=X.shape,
              values=T._value_kind())
    return (np.asarray(_program("pallas_rows")(*args, **kw)),
            np.asarray(_program("xla_scatter")(*args, **kw)))


CASES = [("empty_row", 1024, sk.CWT, {}), ("row_1024", 1024, sk.CWT, {}),
         ("straddles", 1024, sk.CWT, {}), ("padding_chunk", 1024, sk.CWT, {}),
         ("same_bucket", 128, sk.CWT, {}), ("straddles", 128, sk.CWT, {}),
         ("row_1024", 384, sk.CWT, {}), ("empty_row", 2048, sk.CWT, {}),
         ("empty_row", 1024, sk.MMT, {}), ("row_1024", 256, sk.WZT, {"p": 1.5}),
         # the walk's shapes, H = 1 and 3 (R = 16), 2, 8 and 16 (R = 8)
         ("empty_tiles", 1024, sk.CWT, {}), ("empty_tiles", 384, sk.CWT, {}),
         ("one_nonzero", 1024, sk.CWT, {}), ("one_nonzero", 128, sk.CWT, {}),
         ("long_row", 1024, sk.CWT, {}), ("long_row", 128, sk.CWT, {}),
         ("aligned", 1024, sk.CWT, {}), ("aligned", 2048, sk.CWT, {}),
         ("aligned", 384, sk.CWT, {}),
         ("block_border", 1024, sk.CWT, {}), ("block_border", 384, sk.CWT, {}),
         ("nnz_zero", 1024, sk.CWT, {}), ("nnz_zero", 128, sk.CWT, {}),
         ("padding_chunk", 128, sk.CWT, {}), ("straddles", 384, sk.MMT, {}),
         ("one_nonzero", 256, sk.WZT, {"p": 1.5})]


@pytest.mark.parametrize("name,s_dim,family,kwargs", CASES,
                         ids=[f"{c[0]}-{c[1]}-{c[2].sketch_type}" for c in CASES])
class TestAgainstTheScatter:
    def test_lattice_data_bit_equal(self, name, s_dim, family, kwargs):
        rng = np.random.default_rng(17)
        X, lanes = _case(name, s_dim, rng)
        X.data[:] = rng.integers(-8, 9, X.nnz)
        T = family(X.shape[1], s_dim, Context(3), **kwargs)
        got, want = _both(T, X, lanes, s_dim)
        if family is sk.CWT:        # ± integers: every order sums alike
            assert np.array_equal(got, want)
        else:                       # a float value stream: products differ
            assert np.max(np.abs(got - want)) <= 1e-6 * np.max(np.abs(want))
        assert (np.count_nonzero(want) > 0) == (X.nnz > 0)

    def test_float_data_and_single_terms(self, name, s_dim, family, kwargs):
        rng = np.random.default_rng(23)
        X, lanes = _case(name, s_dim, rng)
        X.data[:] = rng.standard_normal(X.nnz).astype(np.float32)
        T = family(X.shape[1], s_dim, Context(3), **kwargs)
        got, want = _both(T, X, lanes, s_dim)
        if not X.nnz:
            assert not got.any() and not want.any()
            return
        assert np.max(np.abs(got - want)) <= 1e-6 * np.max(np.abs(want))
        # a cell one stored nonzero lands in holds v·x to the bit
        h = np.asarray(T.bucket_indices())
        v = np.asarray(T.values())
        coo = X.tocoo()
        cell = coo.row.astype(np.int64) * s_dim + h[coo.col]
        cells, first, count = np.unique(cell, return_index=True,
                                        return_counts=True)
        alone = first[count == 1]
        assert alone.size > (100 if name == "long_row" else X.nnz // 4)
        term = (v[coo.col] * coo.data)[alone]
        assert np.array_equal(got.reshape(-1)[cell[alone]], term)
        if name == "same_bucket":
            assert count.max() >= 2     # the twins share a cell


@pytest.fixture()
def tally(monkeypatch):
    """Every visit the kernel's walk makes while the fixture lives, True
    for a real one and False for a masked overrun: ``_next_visit`` wrapped
    with a host callback, the kernel traced anew with it and again
    without."""
    made = []
    advance = pallas_sparse._next_visit

    def counting(it, c, p1, valid):
        jax.debug.callback(lambda v: made.append(bool(v)), valid)
        return advance(it, c, p1, valid)

    monkeypatch.setattr(pallas_sparse, "_next_visit", counting)
    pallas_sparse._rows_call.clear_cache()
    yield made
    pallas_sparse._rows_call.clear_cache()


WALKS = [("straddles", 1024), ("straddles", 384), ("empty_tiles", 1024),
         ("one_nonzero", 128), ("long_row", 1024), ("aligned", 1024),
         ("aligned", 384), ("block_border", 1024), ("nnz_zero", 1024),
         ("padding_chunk", 2048)]


class TestTheWalk:
    @pytest.mark.parametrize("name,s_dim", WALKS,
                             ids=[f"{n}-{s}" for n, s in WALKS])
    def test_rows_visits_is_what_the_kernel_visits(self, tally, name, s_dim):
        rng = np.random.default_rng(29)
        X, lanes = _case(name, s_dim, rng)
        X.data[:] = rng.integers(-8, 9, X.nnz)
        term, bucket, indptr = _lanes(X, lanes)
        bucket = bucket % s_dim
        rows = X.shape[0]
        out = pallas_sparse.hash_rows_apply(
            term, bucket, indptr, n_rows=rows, s_dim=s_dim, interpret=True)
        out.block_until_ready()
        jax.effects_barrier()
        visits, chunks = pallas_sparse.rows_visits(
            np.asarray(indptr), rows, s_dim, lanes)
        assert sum(tally) == visits
        # what overruns is masked, and less than an iteration a ring block
        r = pallas_sparse.rows_plan(rows, s_dim, lanes, jnp.float32)[0]
        steps = rows // min(rows, pallas_sparse._ROWS_A_STEP)
        blocks = steps + chunks // pallas_sparse._ROWS_BLOCK
        assert len(tally) - visits <= blocks * (pallas_sparse._ROWS_UNROLL - 1)
        assert len(tally) % pallas_sparse._ROWS_UNROLL == 0
        p = np.asarray(indptr)[::r]
        live = int(np.count_nonzero(np.diff(p)))
        assert chunks == -(-X.nnz // 128)
        assert chunks <= visits <= chunks + live
        if name == "aligned":
            assert visits == chunks == live
        if name == "nnz_zero":
            assert (visits, chunks, len(tally)) == (0, 0, 0)
        # and the run was the kernel's: the scatter's sums
        want = np.zeros((rows, s_dim), np.float32)
        np.add.at(want, (np.repeat(np.arange(rows), np.diff(np.asarray(indptr))),
                         np.asarray(bucket)[:X.nnz]), np.asarray(term)[:X.nnz])
        assert np.array_equal(np.asarray(out), want)

    @pytest.mark.parametrize("mean,s_dim", [(0.3, 1024), (4, 1024), (74, 1024),
                                            (74, 128), (600, 2048)])
    def test_visits_lie_between_the_chunks_and_a_chunk_more_a_tile(
            self, mean, s_dim):
        """At the sizes no interpreted run reaches, from ``indptr`` alone:
        a tile adds at most the one chunk it shares with its neighbour."""
        rng = np.random.default_rng(31)
        lengths = rng.poisson(mean, 1 << 16)
        indptr = np.concatenate([[0], np.cumsum(lengths)])
        lanes = max(1024 * -(-int(indptr[-1]) // 1024), LANES_MIN)
        visits, chunks = pallas_sparse.rows_visits(indptr, 1 << 16, s_dim, lanes)
        r = 16 if (s_dim // 128) % 2 else 8
        live = int(np.count_nonzero(np.diff(indptr[::r])))
        assert chunks == -(-int(indptr[-1]) // 128)
        assert chunks <= visits <= chunks + live
        if live == indptr[::r].size - 1:
            # no tile is empty: one visit more for every tile border that
            # falls inside a chunk
            assert visits == chunks + np.count_nonzero(indptr[r:-1:r] % 128)

    def test_an_unfit_shape_has_no_walk(self):
        with pytest.raises(ValueError, match="does not fit"):
            pallas_sparse.rows_visits(np.zeros(9, np.int64), 8, 1000, 16384)


QUALIFIED = dict(shape=(512, N_COLS), s_dim=1024, lanes=32768,
                 dtype=jnp.float32, rowwise=True)


class TestEngagementRule:
    def test_off_the_tpu_the_scatter(self):
        assert sparse_serve.sparse_kernel(**QUALIFIED) == "xla_scatter"
        assert pallas_sparse.rows_plan(512, 1024, 32768, jnp.float32) == (8, 8, 32)

    @pytest.mark.parametrize("n_rows,s_dim,lanes,plan", [
        (512, 384, 32768, (16, 3, 16)),     # odd H: sixteen rows a tile
        (16, 128, 16384, (16, 1, 1)),       # one tile, the least lanes
        (384, 2048, 16384, (8, 16, 16)),    # 48 tiles: G halves to a divisor
        (((1 << 16) - 1) * 8, 1024, 1 << 20, (8, 8, 1)),  # the table's last
        (0, 1024, 32768, None),             # no row at all
        (1 << 19, 1024, 1 << 20, None),     # one tile past the table
    ])
    def test_the_plan_at_the_edges_of_its_conditions(self, n_rows, s_dim,
                                                     lanes, plan):
        assert pallas_sparse.rows_plan(
            n_rows, s_dim, lanes, jnp.float32) == plan

    @pytest.mark.parametrize("change,kernel", [
        ({}, "pallas_rows"),
        ({"s_dim": 128}, "pallas_rows"), ({"s_dim": 384}, "pallas_rows"),
        ({"s_dim": 2048}, "pallas_rows"),
        ({"shape": (24, N_COLS)}, "pallas_rows"),       # 3 tiles: G = 1
        ({"rowwise": False}, "xla_scatter"),
        ({"dtype": jnp.bfloat16}, "xla_scatter"),
        ({"dtype": jnp.float64}, "xla_scatter"),
        ({"s_dim": 1000}, "xla_scatter"), ({"s_dim": 64}, "xla_scatter"),
        ({"s_dim": 4096}, "xla_scatter"),
        ({"lanes": 32768 + 512}, "xla_scatter"),
        ({"lanes": 8192}, "xla_scatter"),               # under a block
        ({"shape": (508, N_COLS)}, "xla_scatter"),      # rows not whole tiles
        ({"shape": (24, N_COLS), "s_dim": 384}, "xla_scatter"),  # 16 a tile
        ({"shape": (8 << 16, N_COLS)}, "xla_scatter"),  # table past SMEM
    ], ids=lambda v: ",".join(f"{k}={getattr(x, '__name__', x)}"
                              for k, x in v.items()) if isinstance(v, dict)
       else v)
    def test_on_a_tpu_by_what_the_apply_observes(self, monkeypatch, change,
                                                 kernel):
        monkeypatch.setattr(pallas_sparse, "available", lambda: True)
        assert sparse_serve.sparse_kernel(**{**QUALIFIED, **change}) == kernel

    @pytest.mark.parametrize("kernel", ["xla_scatter", "pallas_rows"])
    def test_span_and_counter_say_which(self, monkeypatch, kernel):
        """The apply hands the program the kernel the rule names, and the
        span and the counter carry it (the rule itself stubbed: off the TPU
        the kernel is interpreted)."""
        rng = np.random.default_rng(5)
        X = _rows(rng.integers(100, 160, 256), rng)
        X.data[:] = rng.standard_normal(X.nnz).astype(np.float32)
        A = SparseMatrix.from_scipy(X)
        assert A.csr_device()[0].shape[0] % 1024 == 0
        monkeypatch.setattr(sparse_serve, "sparse_kernel",
                            lambda *a, **k: kernel)
        T = sk.CWT(N_COLS, 1024, Context(9))
        counter = metrics.registry().counter("sketch.sparse_nnz")
        before = counter.value(family="CWT", kernel=kernel)
        was = telemetry.enabled()
        telemetry.set_enabled(True)
        try:
            trace.clear_finished()
            out = np.asarray(T.apply(A, sk.ROWWISE))
            spans = trace.finished_spans()
        finally:
            telemetry.set_enabled(was)
        dispatch = [s for s in spans if s.name == "sketch.dispatch"]
        assert [s.attrs["kernel"] for s in dispatch] == [kernel]
        assert dispatch[0].attrs["walk"] == "flat"
        assert dispatch[0].attrs["nnz"] == X.nnz
        assert counter.value(family="CWT", kernel=kernel) - before == X.nnz
        want = np.asarray(T.apply(jnp.asarray(X.toarray()), sk.ROWWISE))
        if kernel == "xla_scatter":
            assert np.array_equal(out, want)
        else:
            assert np.max(np.abs(out - want)) <= 1e-6 * np.max(np.abs(want))

    def test_another_block_of_the_lane_class_compiles_nothing(self,
                                                              monkeypatch):
        """Two blocks of one shape and one lane class, other row lengths:
        the walk is data (``indptr``), so the second apply runs the first
        one's executable and the kernel is traced once."""
        rng = np.random.default_rng(7)
        blocks = []
        for lo, hi in ((100, 160), (60, 200)):
            X = _rows(rng.integers(lo, hi, 256), rng)
            X.data[:] = rng.standard_normal(X.nnz).astype(np.float32)
            blocks.append((SparseMatrix.from_scipy(X), X))
        assert (blocks[0][0].csr_device()[0].shape
                == blocks[1][0].csr_device()[0].shape)
        monkeypatch.setattr(sparse_serve, "sparse_kernel",
                            lambda *a, **k: "pallas_rows")
        traced = []
        kernel = pallas_sparse.hash_rows_apply
        monkeypatch.setattr(
            pallas_sparse, "hash_rows_apply",
            lambda *a, **k: traced.append(k["n_rows"]) or kernel(*a, **k))
        engine.reset()
        # an s_dim no other apply of this file takes: jit's own trace cache
        # outlives engine.reset()
        T = sk.CWT(N_COLS, 512, Context(9))
        try:
            outs = [np.asarray(T.apply(A, sk.ROWWISE)) for A, _ in blocks]
            assert engine.stats().compiles == 1 and traced == [256]
        finally:
            engine.reset()
        for out, (_, X) in zip(outs, blocks):
            want = np.asarray(T.apply(jnp.asarray(X.toarray()), sk.ROWWISE))
            assert np.max(np.abs(out - want)) <= 1e-6 * np.max(np.abs(want))

    def test_an_unfit_shape_raises(self):
        with pytest.raises(ValueError, match="does not fit"):
            pallas_sparse.hash_rows_apply(
                jnp.zeros(1024), jnp.zeros(1024, jnp.int32),
                jnp.zeros(9, jnp.int32), n_rows=8, s_dim=1024)
