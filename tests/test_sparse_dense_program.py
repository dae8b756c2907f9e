"""The sparse × dense program: ``JLT|CT.apply(SparseMatrix, ROWWISE)`` is one
``engine.compiled`` program an apply (``sketch.dense_sparse``), its operator
generated inside it, and its product the body ``base.sparse.spmm`` runs
(``sparse.spmm``) — the Pallas walk of ``sketch/pallas_spmm.py`` over lanes
regrouped at placement (interpreted here, off the TPU), else the span loop.

Oracles:

- *plain reference*: ``cellbench/references/sparse_dense_sketch.py`` (imports
  nothing of the program): S from (seed, counter) by the stream definition,
  and ``X.toarray()·Sᵀ`` at the highest matmul precision;
- ``T.apply(X.todense())``: the same S, entry for entry;
- scipy's ``A @ B`` for ``spmm`` with a supplied right factor;
- a single bfloat16 pass of the reference fails the tolerance the program
  holds;
- one ``sketch.dispatch`` span and one handover an apply, the counters'
  labels, ``lane_slots ≥ nnz``, the operand placed once.
"""

from __future__ import annotations

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import scipy.sparse as sp

from cellbench.references import sparse_dense_sketch as reference
from libskylark_tpu import Context, engine
from libskylark_tpu import sketch as sk
from libskylark_tpu.base import sparse as sparse_mod
from libskylark_tpu.base.sparse import SparseMatrix, spmm
from libskylark_tpu.sketch import pallas_spmm, sparse_serve
from libskylark_tpu.telemetry import metrics, trace
from libskylark_tpu.telemetry.names import HANDOVER

N = 1181            # 47236-like: no multiple of 8, 128 or 256
S = 256
ROWS = 77           # no multiple of a row block either
SEED, COUNTER = 11, 0
TOL = 1e-4          # of the largest entry: the cells' rel_max limit
ROUTES = ["xla", "pallas_tiles"]


def operand(rows: int = ROWS, n: int = N, seed: int = 4) -> sp.csr_matrix:
    """Ragged unit rows: row 3 empty, row 5 of 1024 nonzeros, the others
    1..40 features under a Zipf law over scattered ids."""
    rng = np.random.default_rng(seed)
    weights = 1.0 / np.arange(1, n + 1)
    weights /= weights.sum()
    ids = rng.permutation(n)
    r, c = [], []
    for row in range(rows):
        if row == 3:
            continue
        length = min(1024, n) if row == 5 else int(rng.integers(1, 40))
        feats = ids[rng.choice(n, size=length, replace=False, p=weights)]
        r += [row] * length
        c += list(feats)
    v = np.abs(rng.standard_normal(len(r))).astype(np.float32)
    X = sp.csr_matrix((v, (r, c)), shape=(rows, n))
    X.sort_indices()
    norms = np.sqrt(np.asarray(X.multiply(X).sum(axis=1))).ravel()
    X = sp.diags(1.0 / np.where(norms > 0, norms, 1.0)).dot(X).tocsr()
    X.sort_indices()
    return X.astype(np.float32)


@pytest.fixture()
def fresh():
    engine.reset()
    before = metrics._ENABLED
    trace.clear_finished()
    yield
    metrics._ENABLED = before
    trace.clear_finished()
    engine.reset()


@pytest.fixture(params=ROUTES)
def route(request, monkeypatch):
    """The program under each of its products. Off the TPU the rule picks
    the span loop; the kernel (interpreted) is put in its place with small
    blocks, so that the operand spans several row blocks and column tiles
    and its lanes end inside a chunk."""
    if request.param == "pallas_tiles":
        monkeypatch.setattr(pallas_spmm, "_BLOCK_ROWS", 32)
        # under 128 slots a chunk the kernel traces no unrolled span (8 s a
        # compile, interpreted): TestThePlacement drives that one
        monkeypatch.setattr(pallas_spmm, "_CHUNKS", (64,))

        def rule(shape, k, lanes, dtype, rowwise=True):
            plan, why = pallas_spmm.tiles_plan(shape, k, lanes, dtype)
            return (("pallas_tiles", plan) if plan is not None and rowwise
                    else (f"xla: {why}", None))

        monkeypatch.setattr(sparse_serve, "product_kernel", rule)
    return request.param


FAMILIES = [(sk.JLT, {}), (sk.CT, {"C": 2.0})]


@pytest.mark.parametrize("family,kwargs", FAMILIES)
class TestAgainstTheOracles:
    def test_matches_the_densified_apply(self, fresh, route, family, kwargs):
        T = family(N, S, Context(SEED), **kwargs)
        X = operand()
        A = SparseMatrix.from_scipy(X)
        got = np.asarray(T.apply(A, sk.ROWWISE))
        want = np.asarray(T.apply(jnp.asarray(X.toarray()), sk.ROWWISE))
        assert got.shape == want.shape == (ROWS, S)
        assert np.abs(got - want).max() <= TOL * np.abs(want).max()
        assert not got[3].any()             # the empty row stays empty

    def test_operator_is_the_dense_applys(self, fresh, family, kwargs):
        T = family(N, S, Context(SEED), **kwargs)
        rows = sparse_serve.operator_rows(
            T.allocation.key_data, T.scale, dist=T.dist, s_dim=S, n=N + 299,
            dtype=jnp.float32)
        assert rows.shape == (N + 299, S)
        assert np.array_equal(np.asarray(rows[:N]),
                              np.asarray(T.s_panel(0, N)).T)

    @pytest.mark.parametrize("panel_blocks", [0, 2, 64])
    def test_operator_in_the_kernels_view_is_the_same_operator(
            self, fresh, family, kwargs, panel_blocks):
        """What the kernel's program generates — the rows in the kernel's
        view, one panel of all the blocks (0) or several — against
        ``operator_rows`` at a padded n that ends inside a block: the same
        entries to the bit, and whole blocks (the rows past n are the
        stream's next entries)."""
        T = family(N, S, Context(SEED), **kwargs)
        said = dict(dist=T.dist, s_dim=S, n=N + 299, dtype=jnp.float32)
        rows = sparse_serve.operator_rows(T.allocation.key_data, T.scale,
                                          **said)
        view = sparse_serve.operator_rows_panels(
            T.allocation.key_data, T.scale, lanes=pallas_spmm.LANES,
            panel_blocks=panel_blocks, **said)
        assert view.shape == (6 * 256, S // 128, 128)
        assert np.array_equal(np.asarray(view).reshape(-1, S)[:N + 299],
                              np.asarray(rows))


class TestJLTAgainstThePlainReference:
    def reference(self, X, precision="highest"):
        Sref = reference.operator(SEED, COUNTER, S, N)
        return np.asarray(reference.apply_rows(X, Sref, precision))

    def test_matches(self, fresh, route):
        X = operand()
        T = sk.JLT(N, S, Context(SEED))
        got = np.asarray(T.apply(SparseMatrix.from_scipy(X), sk.ROWWISE))
        want = self.reference(X)
        assert np.abs(got - want).max() <= TOL * np.abs(want).max()

    def test_whole_block_reference_agrees_with_its_rows(self):
        X = operand(rows=2 * reference.ROW_BLOCK + 5, n=300)
        Sref = reference.operator(SEED, COUNTER, 128, 300)
        whole = np.asarray(reference.apply_block(X, Sref))
        rows = np.asarray(reference.apply_rows(X, Sref))
        assert whole.shape == rows.shape
        assert np.abs(whole - rows).max() <= 1e-6 * np.abs(rows).max()

    def test_a_single_bfloat16_pass_fails_the_tolerance(self):
        X = operand()
        want = self.reference(X)
        low = self.reference(X, "bf16")
        assert np.abs(low - want).max() > 10 * TOL * np.abs(want).max()

    def test_another_counter_is_another_operator(self, fresh):
        X = operand()
        ctx = Context(SEED)
        first, second = sk.JLT(N, S, ctx), sk.JLT(N, S, ctx)
        A = SparseMatrix.from_scipy(X)
        want = self.reference(X)
        assert np.abs(np.asarray(first.apply(A, sk.ROWWISE)) - want).max() \
            <= TOL * np.abs(want).max()
        assert np.abs(np.asarray(second.apply(A, sk.ROWWISE)) - want).max() \
            > 0.1 * np.abs(want).max()

    def test_served_law_z_scores(self, fresh):
        X = operand(rows=64)
        X = X[np.diff(X.indptr) > 0]
        T = sk.JLT(N, S, Context(SEED))
        Y = np.asarray(T.apply(SparseMatrix.from_scipy(X), sk.ROWWISE))
        mean_z, var_z = reference.law_z_scores(X, Y, S)
        assert mean_z < 6 and var_z < 6
        _, scaled = reference.law_z_scores(X, 1.1 * Y, S)
        assert scaled > 6


@pytest.mark.parametrize("k", [128, 256, 384])
def test_spmm_with_a_supplied_factor_against_scipy(fresh, route, k):
    X = operand()
    B = np.random.default_rng(3).standard_normal((N, k)).astype(np.float32)
    got = np.asarray(spmm(SparseMatrix.from_scipy(X), B))
    want = X.astype(np.float64) @ B.astype(np.float64)
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


@pytest.mark.parametrize("k", [1, 5, 130])
def test_spmm_widths_the_kernel_declines_take_the_span_loop(fresh, route, k):
    X = operand()
    A = SparseMatrix.from_scipy(X)
    B = np.random.default_rng(3).standard_normal((N, k)).astype(np.float32)
    kernel, plan = sparse_serve.product_kernel(A.shape, k, 4096, jnp.float32)
    assert plan is None and kernel.startswith("xla: ")
    got = np.asarray(spmm(A, B[:, 0] if k == 1 else B))
    want = X @ (B[:, 0] if k == 1 else B)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def test_span_loop_over_several_spans(fresh, monkeypatch):
    monkeypatch.setattr(sparse_mod, "_SPAN_LANES", 512)
    X = operand()
    A = SparseMatrix.from_scipy(X)
    assert A.csr_device()[0].shape[0] > 4 * 512
    B = np.random.default_rng(3).standard_normal((N, 8)).astype(np.float32)
    got = np.asarray(spmm(A, B))
    assert np.abs(got - X @ B).max() <= 1e-5 * np.abs(X @ B).max()


def long_rows(rows: int = ROWS, n: int = N) -> sp.csr_matrix:
    """Three rows that fill columns 0..29 (one tile of 32: rank classes of
    three rows, under any group) over rows of one nonzero in that tile and
    a few elsewhere: the long rows' later lanes are a serial tail."""
    rng = np.random.default_rng(9)
    X = sp.lil_matrix((rows, n), dtype=np.float32)
    for row in range(rows):
        cols = np.concatenate([[row % 32], 32 + rng.choice(n - 32, 3, False)])
        X[row, cols] = rng.standard_normal(4)
    for row in (2, 40, 41):
        X[row, :30] = rng.standard_normal(30)
    return X.tocsr().astype(np.float32)


def singletons(rows: int = 64, n: int = N) -> sp.csr_matrix:
    """Every row holds one nonzero in each of three 32-column tiles: every
    (row, tile) run is of length 1 and a segment is one rank class of 32
    rows — no serial tail under a group of up to 32."""
    rng = np.random.default_rng(10)
    r = np.repeat(np.arange(rows), 3)
    c = (np.array([0, 96, 224])[None, :]
         + rng.integers(0, 32, (rows, 3))).ravel()
    v = rng.standard_normal(3 * rows).astype(np.float32)
    return sp.csr_matrix((v, (r, c)), shape=(rows, n))


OPERANDS = {"ragged": operand, "long_rows": long_rows,
            "singletons": singletons}


_multiply_add = jax.jit(lambda y, v, b: y + v * b)


def plain_product(X: sp.csr_matrix, B: np.ndarray) -> np.ndarray:
    """Each row's terms added one by one by rising column, in float32. The
    order is this loop's; a term's multiply-add is the backend's own
    (XLA's CPU backend contracts ``y + v·b`` into one rounding, a v5e has
    no fused multiply-add)."""
    out = np.zeros((X.shape[0], B.shape[1]), np.float32)
    for row in range(X.shape[0]):
        for at in range(X.indptr[row], X.indptr[row + 1]):
            out[row] = _multiply_add(out[row], X.data[at], B[X.indices[at]])
    return out


class TestThePlacement:
    def plan(self, A, k=S):
        lanes = int(A.csr_device()[0].shape[0])
        plan, why = pallas_spmm.tiles_plan(A.shape, k, lanes, jnp.float32)
        assert plan is not None, why
        return plan

    def test_layout_holds_every_lane_once(self, fresh, monkeypatch):
        monkeypatch.setattr(pallas_spmm, "_BLOCK_ROWS", 32)
        X = operand()
        A = SparseMatrix.from_scipy(X)
        plan = self.plan(A)
        assert (plan.row_block, plan.col_tile) == (32, 32)
        assert plan.row_blocks == 3 and plan.col_tiles == 37
        segment, count, packed, vals = map(np.asarray,
                                           A.tiled_device(plan.layout))
        count = count & 0xFFFF
        assert count.sum() == X.nnz
        assert (np.diff(segment) >= 0).all()
        # every row block owns a chunk, the lanes end inside one
        assert set(segment // plan.col_tiles) == {0, 1, 2}
        assert (count % plan.chunk != 0).any()
        live = np.arange(plan.chunk)[None, :] < count[:, None]
        row = (segment // plan.col_tiles)[:, None] * 32 + (packed[:, 0] >> 16)
        col = (segment % plan.col_tiles)[:, None] * 32 + (packed[:, 0] & 0xFFFF)
        back = sp.csr_matrix((vals[:, 0][live], (row[live], col[live])),
                             shape=X.shape)
        assert (back != X).nnz == 0
        assert not vals[:, 0][~live].any()

    def test_a_segments_last_chunk_is_its_fullest(self, fresh, monkeypatch):
        """Chunks of 16 slots, so that segments take several: every chunk
        of a segment is full but its first (the last one's walk hides the
        next tile's copy), and the product through them is the densified
        one."""
        monkeypatch.setattr(pallas_spmm, "_BLOCK_ROWS", 32)
        monkeypatch.setattr(pallas_spmm, "_CHUNKS", (16,))
        X = operand()
        A = SparseMatrix.from_scipy(X)
        plan = self.plan(A, k=128)
        assert plan.chunk == 16
        placed = A.tiled_device(plan.layout)
        segment, count = np.asarray(placed[0]), np.asarray(placed[1]) & 0xFFFF
        assert count.sum() == X.nnz
        several = 0
        for seg in np.unique(segment[count > 0]):
            counts = count[(segment == seg) & (count > 0)]
            assert (counts[1:] == 16).all() and 0 < counts[0] <= 16
            several += len(counts) > 1
        assert several > 3
        B = np.random.default_rng(2).standard_normal((N, 128)).astype(np.float32)
        out = pallas_spmm.tiles_apply(*placed, jnp.asarray(B), shape=A.shape,
                                      plan=plan, interpret=True)
        want = X.astype(np.float64) @ B.astype(np.float64)
        assert np.abs(np.asarray(out) - want).max() <= 1e-5 * np.abs(want).max()

    @staticmethod
    def tiles_plan(X, col_tile=32, chunk=16, group=4, k_tiles=1,
                   row_block=32):
        blocks = -(-X.shape[0] // row_block)
        tiles = -(-X.shape[1] // col_tile)
        return pallas_spmm.TilesPlan(
            row_block, col_tile, chunk, k_tiles, blocks, tiles,
            -(-X.nnz // chunk) + blocks * tiles, group)

    @staticmethod
    def segments(X, plan):
        """Each live segment's slots in walk order, ``(segment, rows,
        columns, grouped)``: ``grouped`` marks the slots of every chunk's
        grouped count, the rest is walked one by one. Rows and columns
        as the words hold them: times ``plan.stride``."""
        A = SparseMatrix.from_scipy(X)
        segment, count, packed, _ = map(np.asarray,
                                        A.tiled_device(plan.layout))
        stored, ahead = count & 0xFFFF, count >> 16
        assert (ahead <= stored).all()
        for seg in np.unique(segment[stored > 0]):
            rows, cols, grouped = [], [], []
            for t in np.flatnonzero((segment == seg) & (stored > 0)):
                words = packed[t, 0, :stored[t]]
                rows += list(words >> 16)
                cols += list(words & 0xFFFF)
                grouped += list(np.arange(stored[t]) < ahead[t])
            yield seg, np.array(rows), np.array(cols), np.array(grouped)

    @pytest.mark.parametrize("group", [4, 8, 16])
    @pytest.mark.parametrize("name", list(OPERANDS))
    def test_grouped_slots_address_distinct_rows(self, fresh, name, group):
        """Every window of ``group`` consecutive grouped slots of a chunk
        holds ``group`` rows, the grouped slots are a prefix of their
        segment, a segment's grouped and serial slots are its stored
        lanes, and ``grouped_lanes`` counts the whole groups."""
        X = OPERANDS[name]()
        plan = self.tiles_plan(X, group=group)
        A = SparseMatrix.from_scipy(X)
        _, count, packed, _ = map(np.asarray, A.tiled_device(plan.layout))
        stored, ahead = count & 0xFFFF, count >> 16
        for t in np.flatnonzero(ahead):
            rows = packed[t, 0, :ahead[t]] >> 16
            for at in range(ahead[t] - group + 1):
                assert len(set(rows[at:at + group])) == group, (t, at)
        assert A.grouped_lanes(plan.layout) == (ahead // group * group).sum()
        coo = X.tocoo()
        in_segment = np.bincount(
            (coo.row // 32) * plan.col_tiles + coo.col // 32,
            minlength=plan.row_blocks * plan.col_tiles)
        seen = 0
        for seg, rows, _, grouped in self.segments(X, plan):
            assert len(rows) == in_segment[seg]     # grouped + serial slots
            # a prefix: no grouped slot after a serial one
            assert not grouped[np.argmin(grouped):].any() or grouped.all()
            seen += len(rows)
        assert seen == X.nnz
        if name == "singletons":
            assert (ahead == stored).all()          # no tail at all
        if name == "long_rows":
            # tile 0 of every block: one rank class of 32 rows ahead, the
            # long rows' later lanes behind it
            tail = stored.sum() - ahead.sum()
            assert tail >= 3 * 29 and ahead.sum() >= 64

    @pytest.mark.parametrize("k_tiles", [1, 8])
    @pytest.mark.parametrize("name", list(OPERANDS))
    def test_a_rows_lanes_lie_by_rising_column(self, fresh, name, k_tiles):
        """In slot order across its segment's chunks; where the views are
        flat (k_tiles 8) the words count in sublanes."""
        X = OPERANDS[name]()
        plan = self.tiles_plan(X, k_tiles=k_tiles)
        held = 0
        for _, rows, cols, _ in self.segments(X, plan):
            assert not (rows % plan.stride).any()
            assert not (cols % plan.stride).any()
            assert rows.max() < 32 * plan.stride > cols.max()
            for row in np.unique(rows):
                assert (np.diff(cols[rows == row]) > 0).all()
            held += len(rows)
        assert held == X.nnz

    @pytest.mark.parametrize("name", list(OPERANDS))
    @pytest.mark.parametrize("col_tile,chunk,group,k_tiles", [
        (32, 16, 4, 1), (40, 64, 16, 1), (1184, 64, 8, 3), (8, 96, 4, 8),
        (1184, 256, 8, 1), (64, 48, 8, 16)])
    def test_the_result_is_the_layouts_to_the_bit(self, fresh, name, col_tile,
                                                   chunk, group, k_tiles):
        """Whatever the tile width, the chunk size, the group and the width
        of B (a row part of a vector register, or one or two whole ones
        behind the flat views), a row's terms are added by rising column:
        every layout gives the bits of the plain loop over each row's
        nonzeros. Chunks of 256 slots under one column tile hold whole
        128-slot spans of grouped lanes."""
        X = OPERANDS[name]()
        A = SparseMatrix.from_scipy(X)
        B = np.random.default_rng(5).standard_normal(
            (N, 128 * k_tiles)).astype(np.float32)
        plan = self.tiles_plan(X, col_tile, chunk, group, k_tiles)
        assert plan.stride == (k_tiles if k_tiles in (8, 16) else 1)
        placed = A.tiled_device(plan.layout)
        if chunk == 256 and name != "singletons":
            assert (np.asarray(placed[1]) >> 16).max() >= pallas_spmm._SPAN
        got = np.asarray(pallas_spmm.tiles_apply(
            *placed, jnp.asarray(B), shape=A.shape, plan=plan,
            interpret=True))
        assert np.array_equal(got, plain_product(X, B))

    @pytest.mark.parametrize("chunk,chunks", [(2048, 3), (4096, 2),
                                              (8192, 1)])
    def test_the_result_is_the_same_under_the_chunks(self, fresh, chunk,
                                                     chunks):
        """One segment of 5845 lanes under the parent's chunk (2048 slots:
        the remainder, then two full ones), the shipped one (4096) and
        the fallback (8192: the whole segment, one chunk): a row's terms
        are added by rising column wherever a chunk cuts the segment, so
        each gives the plain loop's bits."""
        X = operand(rows=256)
        A = SparseMatrix.from_scipy(X)
        B = np.random.default_rng(5).standard_normal(
            (N, 128)).astype(np.float32)
        plan = self.tiles_plan(X, 1184, chunk, 8, 1, row_block=256)
        placed = A.tiled_device(plan.layout)
        stored = np.asarray(placed[1]) & 0xFFFF
        assert list(stored[stored > 0][1:]) == [chunk] * (chunks - 1)
        assert stored.sum() == X.nnz and (stored > 0).sum() == chunks
        got = np.asarray(pallas_spmm.tiles_apply(
            *placed, jnp.asarray(B), shape=A.shape, plan=plan,
            interpret=True))
        assert np.array_equal(got, plain_product(X, B))

    @pytest.mark.parametrize("chunk,covered", [(64, 1), (1024, 1), (4, 0)])
    def test_covered_segments_counts_long_last_chunks(self, fresh, chunk,
                                                      covered):
        """Two live segments, one of 640 lanes and one of 5, under a tile
        whose copy takes 8 lanes of walk to outlast (``TilesPlan.cover``):
        the long one counts where its last chunk is a full one of 64 slots
        or the one chunk that holds it all, the short one never, and under
        full chunks of 4 slots — shorter than the copy, as the parent's
        2048 were at the cell — nothing does."""
        X = sp.lil_matrix((32, 64), dtype=np.float32)
        X[:, :20] = 1.0
        X[:5, 40] = 2.0
        X = X.tocsr()
        plan = self.tiles_plan(X, 32, chunk, 4, 1)
        assert plan.cover == 8 and (plan.row_blocks, plan.col_tiles) == (1, 2)
        A = SparseMatrix.from_scipy(X)
        assert A.covered_segments(plan.layout) == covered
        assert plan.layout[-1] == plan.cover
        count = np.asarray(A.tiled_device(plan.layout)[1]) & 0xFFFF
        assert count.sum() == X.nnz == 645

    @pytest.mark.parametrize("n,k,lanes,chunk", [
        (47236, 128, 19922944, 4096), (47236, 1024, 19922944, 4096),
        (47236, 2048, 19922944, 4096), (47236, 1024, 1 << 27, 8192),
        (4096, 1024, 19922944, 4096)])
    def test_a_full_chunk_outlasts_the_tiles_copy(self, n, k, lanes, chunk):
        """The rule behind ``_CHUNKS``: whatever the width, a full chunk
        holds the lanes whose walk outlasts the copy of a tile of B
        (``cover``: 2048 bytes of the tile a lane; the largest tile a plan
        makes is 8 MiB, 4096 lanes: n = 4096 here) — also where the chunk
        tables of the first entry pass SMEM and the next one serves."""
        plan, why = pallas_spmm.tiles_plan((262144, n), k, lanes,
                                           jnp.float32)
        assert plan is not None, why
        assert plan.chunk == chunk >= plan.cover
        assert plan.chunk % pallas_spmm._SPAN == 0 and plan.chunk < 1 << 16
        assert plan.n_chunks == (-(-lanes // chunk)
                                 + plan.row_blocks * plan.col_tiles)
        assert plan.n_chunks <= pallas_spmm._MAX_CHUNKS
        tile_bytes = plan.col_tile * k * 4
        assert tile_bytes <= 8 << 20
        assert plan.cover == -(-tile_bytes // 2048)     # 819 B/ns · 2.5 ns

    @pytest.mark.parametrize("n,tile,tiles", [
        (47236, 1976, 24), (2048, 2048, 1), (2049, 1032, 2), (1181, 1184, 1)])
    def test_column_tiles_are_of_one_width(self, n, tile, tiles):
        """No whole tiles and a narrow rest: the rest's short segments
        would end every row block (47236 = 23 · 2048 + 132)."""
        plan, why = pallas_spmm.tiles_plan((4096, n), 1024, 1 << 20,
                                           jnp.float32)
        assert plan is not None, why
        assert (plan.col_tile, plan.col_tiles) == (tile, tiles)
        assert n - (tiles - 1) * tile > tile - 8 * tiles

    def test_placed_once_under_a_span(self, fresh, route):
        metrics._ENABLED = True
        X = operand()
        A = SparseMatrix.from_scipy(X)
        T = sk.JLT(N, S, Context(SEED))
        first = np.asarray(T.apply(A, sk.ROWWISE))
        compiles = engine.stats().compiles
        placed = [s for s in trace.finished_spans() if s.name == "sparse.place"]
        second = np.asarray(T.apply(A, sk.ROWWISE))
        assert np.array_equal(first, second)
        assert engine.stats().compiles == compiles == 1
        after = [s for s in trace.finished_spans() if s.name == "sparse.place"]
        assert len(after) == len(placed) == (route == "pallas_tiles")
        for span in placed:
            assert span.attrs["bytes"] > 0 and span.attrs["seconds"] > 0
            assert span.attrs["lane_slots"] >= X.nnz
            assert 0 <= span.attrs["grouped_lanes"] <= X.nnz
            assert 0 <= span.attrs["covered_segments"] <= 3 * 37

    @pytest.mark.parametrize("family,kwargs", FAMILIES)
    def test_a_warm_apply_moves_nothing_to_the_device(self, fresh, route,
                                                      family, kwargs):
        """The key words, the scale and the lanes are on the device from the
        first apply on: a later one is the executable's call alone (a
        transfer an apply is a call into the runtime and a completion more
        ahead of the program's launch)."""
        A = SparseMatrix.from_scipy(operand())
        T = family(N, S, Context(SEED), **kwargs)
        first = np.asarray(T.apply(A, sk.ROWWISE))
        with jax.transfer_guard_host_to_device("disallow"):
            second = T.apply(A, sk.ROWWISE)
        assert np.array_equal(first, np.asarray(second))

    def test_plans_the_kernel_declines(self):
        shape = (262144, 47236)
        assert pallas_spmm.tiles_plan(shape, 1024, 19922944, jnp.float32)[0] \
            == pallas_spmm.TilesPlan(2048, 1976, 4096, 8, 128, 24, 7936, 8)
        for k, dtype, lanes, why in [
                (1000, jnp.float32, 1 << 20, "multiple of 128"),
                (4096, jnp.float32, 1 << 20, "multiple of 128"),
                (1024, jnp.bfloat16, 1 << 20, "dtype bfloat16"),
                (1024, jnp.float64, 1 << 20, "dtype float64"),
                (1024, jnp.float32, 1 << 28, "chunk table")]:
            plan, said = pallas_spmm.tiles_plan(shape, k, lanes, dtype)
            assert plan is None and why in said
        # off the TPU the rule says so, on either side (since PR 61 the
        # transposed side has a plan of its own: test_sparse_transposed_program)
        for rowwise in (True, False):
            assert sparse_serve.product_kernel(
                shape, 1024, 19922944, jnp.float32, rowwise=rowwise) == (
                    f"xla: backend {jax.default_backend()}", None)


def kernel_view_product(placed, B, plan, rows: int) -> np.ndarray:
    """The product as it left the call before the hand-over: the same
    kernel accumulating straight into the pipeline's output block in the
    flat view (rows · k/128, 128), and XLA's ``reshape(-1, k)`` after it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    assert plan.stride > 1

    def view(r):
        return (r * plan.k_tiles, pallas_spmm.LANES)

    slots = pl.BlockSpec((1, 1, plan.chunk), lambda t, seg, cnt: (t, 0, 0),
                         memory_space=pltpu.SMEM)
    flat = pl.pallas_call(
        functools.partial(pallas_spmm._kernel_tiles, plan.col_tiles,
                          plan.group, plan.stride, plan.runs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(plan.n_chunks,),
            in_specs=[slots, slots,
                      pl.BlockSpec(view(plan.col_tile), lambda t, seg, cnt:
                                   (seg[t] % plan.col_tiles, 0))],
            out_specs=pl.BlockSpec(view(plan.row_block), lambda t, seg, cnt:
                                   (seg[t] // plan.col_tiles, 0))),
        out_shape=jax.ShapeDtypeStruct(
            view(plan.row_blocks * plan.row_block), jnp.float32),
        interpret=True)(*placed, B.reshape(view(B.shape[0])))
    return np.asarray(flat).reshape(-1, plan.k_tiles * pallas_spmm.LANES)[:rows]


def hollow(rows: int = 128) -> sp.csr_matrix:
    """``operand`` with the rows of its second and of its last block of 32
    emptied: each of those blocks owns one chunk, an empty one."""
    X = operand(rows=rows).tolil()
    X[32:64] = 0
    X[96:] = 0
    return X.tocsr().astype(np.float32)


class TestTheHandOver:
    """Where a row of the blocks is whole vector registers (k a multiple of
    1024) the walk accumulates in a scratch block and a row block's last
    chunk hands it over as (row_block, k) rows: movement only, so the
    result is, to the bit, what the call gave in the kernel's view."""

    CASES = {
        # name: (operand, side, k_tiles, chunk, group)
        "tiles_1024": (operand, "rows", 8, 48, 8),
        "tiles_2048": (operand, "rows", 16, 64, 8),
        "tiles_whole_blocks": (lambda: operand(rows=64), "rows", 8, 48, 4),
        "tiles_empty_blocks": (hollow, "rows", 8, 48, 8),
        "runs_1024": (lambda: operand(n=211), "transposed", 8, 24, 8),
        "runs_2048": (lambda: operand(n=211), "transposed", 16, 64, 4),
        "runs_empty_blocks": (lambda: hollow().T.tocsr(), "transposed", 8,
                              24, 8),
    }

    @pytest.mark.parametrize("name", list(CASES))
    def test_the_rows_are_the_kernel_views_to_the_bit(self, fresh, name):
        make, side, k_tiles, chunk, group = self.CASES[name]
        X = make()
        A = SparseMatrix.from_scipy(X)
        runs = side == "transposed"
        rows, n = X.shape[::-1] if runs else X.shape
        blocks, tiles = -(-rows // 32), -(-n // 32)
        plan = pallas_spmm.TilesPlan(
            32, 32, chunk, k_tiles, blocks, tiles,
            4 * -(-X.nnz // chunk) + 8 * blocks * tiles, group, runs)
        assert plan.stride == k_tiles
        placed = A.tiled_device(plan.layout, side=side)
        stored = np.asarray(placed[1]) & 0xFFFF
        assert stored.sum() >= X.nnz            # (a run's padding counts)
        owned = np.asarray(placed[0]) // tiles
        if "empty" in name:
            # an emptied block owns one chunk, an empty one (the last
            # block's is repeated to the end of the tables)
            assert not stored[(owned == 1) | (owned == blocks - 1)].any()
            assert (owned == 1).sum() == 1
        else:
            assert rows % 32 or name == "tiles_whole_blocks"
        B = jnp.asarray(np.random.default_rng(5).standard_normal(
            (tiles * 32 + 5, 128 * k_tiles)).astype(np.float32))
        got = pallas_spmm.tiles_apply(*placed, B, shape=(rows, n), plan=plan,
                                      interpret=True)
        assert got.shape == (rows, 128 * k_tiles)
        want = kernel_view_product(placed, B, plan, rows)
        assert np.array_equal(np.asarray(got), want)
        dense = X.toarray().T if runs else X.toarray()
        exact = dense.astype(np.float64) @ np.asarray(B, np.float64)[:n]
        assert np.abs(want - exact).max() <= 1e-5 * np.abs(exact).max()
        if "empty" in name:
            assert not want[32:64].any() and not want[96:].any()

    def test_the_other_widths_keep_the_kernels_view(self, fresh):
        """At a width whose row is part of a register behind a leading
        index (``stride`` 1) the call's result stays in the kernel's view,
        (rows, k/128, 128), and ``tiles_apply`` reshapes it."""
        X = operand()
        A = SparseMatrix.from_scipy(X)
        plan = TestThePlacement.tiles_plan(X, 32, 48, 8, 3)
        assert plan.stride == 1
        placed = A.tiled_device(plan.layout)
        B = jnp.asarray(np.random.default_rng(5).standard_normal(
            (plan.col_tiles * 32, 384)).astype(np.float32))
        out = pallas_spmm._tiles_call(*placed, B, rows=ROWS, plan=plan,
                                      interpret=True)
        assert out.shape == (plan.row_blocks * 32, 3, 128)
        got = pallas_spmm.tiles_apply(*placed, B, shape=A.shape, plan=plan,
                                      interpret=True)
        assert np.array_equal(np.asarray(got),
                              np.asarray(out).reshape(-1, 384)[:ROWS])

    def test_vmem_counts_the_scratch_block(self):
        flat = TestThePlacement.tiles_plan(operand(), 32, 48, 8, 8)
        view = TestThePlacement.tiles_plan(operand(), 32, 48, 8, 4)
        row = 8 * 128 * 4       # a row under 8 sublanes is padded to 8
        assert pallas_spmm.vmem_bytes(flat) == (2 * (32 + 32) + 32) * row
        assert pallas_spmm.vmem_bytes(view) == 2 * (32 + 32) * row

    @pytest.mark.parametrize("family,kwargs", FAMILIES)
    def test_the_sketch_at_a_handed_over_width(self, fresh, route, family,
                                                kwargs):
        """s = 1024 through the whole program: the operator generated in
        the kernel's view, the result the call's own output, 77 rows under
        blocks of 32 — against the densified apply."""
        T = family(N, 1024, Context(SEED), **kwargs)
        X = operand()
        got = np.asarray(T.apply(SparseMatrix.from_scipy(X), sk.ROWWISE))
        want = np.asarray(T.apply(jnp.asarray(X.toarray()), sk.ROWWISE))
        assert got.shape == want.shape == (ROWS, 1024)
        assert np.abs(got - want).max() <= TOL * np.abs(want).max()
        assert not got[3].any()


class TestSpansAndCounters:
    def test_one_span_one_handover_and_the_labels(self, fresh, route):
        metrics._ENABLED = True
        X = operand()
        A = SparseMatrix.from_scipy(X)
        T = sk.JLT(N, S, Context(SEED))
        T.apply(A, sk.ROWWISE)              # placement and compile
        trace.clear_finished()
        before = _counter("sketch.sparse_nnz")
        for _ in range(2):      # a period ends at the next apply's start
            T.apply(A, sk.ROWWISE).block_until_ready()
        spans = trace.finished_spans()
        dispatch, _ = [s for s in spans if s.name == "sketch.dispatch"]
        attrs = dispatch.attrs
        assert attrs["path"] == "sparse" and attrs["family"] == "JLT"
        assert attrs["s"] == S and attrs["nnz"] == X.nnz
        assert attrs["nnz_class"] == A.csr_device()[0].shape[0]
        assert attrs["lane_slots"] >= attrs["nnz"]
        if route == "pallas_tiles":
            assert attrs["kernel"] == "pallas_tiles"
            # s = 256: a row is part of a register, the result is relaid
            assert attrs["result_layout"] == "kernel_view"
            assert attrs["operator_view"] == "kernel"
            assert attrs["segments"] == 3 * 37 and attrs["chunk"] == 64
            plan = pallas_spmm.tiles_plan(A.shape, S, attrs["nnz_class"],
                                          jnp.float32)[0]
            assert attrs["grouped_lanes"] == A.grouped_lanes(plan.layout)
            assert 0 < attrs["grouped_lanes"] <= X.nnz
            # of the 3 × 37 segments those whose last chunk holds cover = 8
            # lanes (81 here; two segments take a second chunk of 64 slots)
            assert attrs["covered_segments"] \
                == A.covered_segments(plan.layout)
            assert 0 < attrs["covered_segments"] < attrs["segments"]
        else:
            assert attrs["kernel"] == f"xla: backend {jax.default_backend()}"
            assert attrs["result_layout"] == attrs["operator_view"] == "rows"
            assert attrs["segments"] == 1 and "grouped_lanes" not in attrs
            assert "covered_segments" not in attrs
        assert len([s for s in spans if s.name == HANDOVER[0]]) == 2
        assert not [s for s in spans if s.name == "sparse.place"]
        periods = trace.apply_periods("sketch.apply")
        assert [p["handovers"] for p in periods] == [1]
        after = _counter("sketch.sparse_nnz")
        key = (("family", "JLT"), ("kernel", attrs["kernel"]))
        assert after.get(key, 0) - before.get(key, 0) == 2 * X.nnz

    @pytest.mark.parametrize("s_dim,layout", [(1024, "rows"), (2048, "rows"),
                                              (384, "kernel_view")])
    def test_the_span_says_how_the_arrays_crossed(self, fresh, route, s_dim,
                                                  layout):
        """``result_layout``: the call's own (rows, k) output where k is a
        multiple of 1024, else the kernel's view relaid by XLA;
        ``operator_view``: Sᵀ generated in the kernel's view whenever the
        kernel runs. The span loop knows rows only."""
        A = SparseMatrix.from_scipy(operand())
        T = sk.JLT(N, s_dim, Context(SEED))
        metrics._ENABLED = True
        T.apply(A, sk.ROWWISE).block_until_ready()
        (dispatch,) = [s for s in trace.finished_spans()
                       if s.name == "sketch.dispatch"]
        kernel = route == "pallas_tiles"
        assert dispatch.attrs["result_layout"] == (layout if kernel
                                                   else "rows")
        assert dispatch.attrs["operator_view"] == ("kernel" if kernel
                                                   else "rows")

    def test_spmm_counts_under_its_own_name(self, fresh, route):
        X = operand()
        A = SparseMatrix.from_scipy(X)
        B = np.ones((N, 128), np.float32)
        before = _counter("sparse.spmm_nnz")
        sketched = _counter("sketch.sparse_nnz")
        spmm(A, B)
        after = _counter("sparse.spmm_nnz")
        (key,) = [k for k in after if after[k] != before.get(k, 0)]
        assert after[key] - before.get(key, 0) == X.nnz
        assert dict(key)["kernel"] == (
            "pallas_tiles" if route == "pallas_tiles"
            else f"xla: backend {jax.default_backend()}")
        assert _counter("sketch.sparse_nnz") == sketched


def _counter(name: str) -> dict:
    entry = metrics.snapshot()["metrics"].get(name)
    if entry is None:
        return {}
    return {tuple(sorted(v["labels"].items())): int(v["value"])
            for v in entry["values"]}


def test_pinned_operator_is_spmms_right_factor(fresh, route):
    X = operand()
    A = SparseMatrix.from_scipy(X)
    T = sk.JLT(N, S, Context(SEED))
    virtual = np.asarray(T.apply(A, sk.ROWWISE))
    T.materialize()
    pinned = np.asarray(T.apply(A, sk.ROWWISE))
    assert np.abs(pinned - virtual).max() <= 1e-6 * np.abs(virtual).max()


def test_operator_past_auto_block_bytes_keeps_the_panel_loop(fresh):
    from libskylark_tpu.sketch import params as sketch_params

    X = operand()
    A = SparseMatrix.from_scipy(X)
    T = sk.JLT(N, S, Context(SEED))
    want = np.asarray(T.apply(A, sk.ROWWISE))
    old = sketch_params.get_auto_block_bytes()
    sketch_params.set_auto_block_bytes(N * S * 4 - 1)
    try:
        got = np.asarray(T.apply(A, sk.ROWWISE))
    finally:
        sketch_params.set_auto_block_bytes(old)
    assert engine.stats().compiles == 1     # the loop is eager: no program
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
