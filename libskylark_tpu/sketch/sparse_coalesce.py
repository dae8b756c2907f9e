"""Coalescing of relabelled sparse lanes on the device: the second half of a
hash sketch whose result stays sparse (``HashTransform.apply_sparse``; ref:
sketch/hash_transform_local_sparse.hpp:12-152, CSC → CSC with duplicates
summed).

A hash sketch relabels each stored nonzero 1:1 — rowwise (r, c, x) →
(r, h(c), v(c)·x) — so the result's *structure* is data: which (row, bucket)
cells are stored, in what order, how many. :func:`coalesce` takes the
relabelled lanes and returns canonical CSR lanes in the
``SparseMatrix.csr_device()`` format — a row's columns ascending and
distinct, the terms of one cell summed in float32, ``indptr`` exact, the
lanes past the stored count 0.0 at column 0 — with every shape static
although the stored count is not: the result's lane extent is the input's
(``nnz_out ≤ nnz_in``), so the blocks of one corpus share one executable.

Three stages, all under ``jax.named_scope(SCOPE)`` so that a device trace
names them (``coalesce_share.apply`` reads the scope off the compiled
module's metadata):

* **sort** — :func:`sort_form` says which. ``"window"``: the lanes arrive
  grouped by result row (CSR) and no row holds more than ``cap`` lanes, so a
  row lies whole inside one of the overlapping windows of 2·cap lanes that
  start every cap lanes; each window is sorted along the minor axis by ONE
  32-bit key (the row's rank inside the window · 2^bits + the column) —
  two batched sorts of the lane extent (the even and the odd windows), each
  a bitonic network over 2·cap lanes in VMEM, where a global sort of the
  same lanes is log²(lanes)/2 stages through HBM — and a lane takes its
  place from the window that holds its row whole. ``"global"``: anything
  else (a columnwise apply regroups every lane; a row past ``_WINDOW_CAP``):
  one two-key ``lax.sort`` of all lanes by (row, column).
* **sum** — the terms of one cell are adjacent now; an inclusive segmented
  scan by doubling leaves a cell's sum in its last lane (as many steps as
  the longest run's bit length: one or two where collisions are rare, and
  a + b is the float32 sum whatever the order).
* **compact** — the lanes that close a cell are kept; a kept lane moves
  left by the number of dropped lanes before it, one power of two a step
  (as many steps as the bit length of the largest displacement, rounded up
  to three; kept lanes never collide, their displacements are monotone). The new row pointers
  are the kept-lane count read at the old row starts. No scatter and no
  gather over the lanes: an element scatter is 19 ns a lane on a v5e.

Workspace: under ``_WORKSPACE_WORDS`` 4-byte words a lane beside the
operand and the result (keys, terms and their sorted copies, the scan's and
the shift loop's carries), whatever the shapes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

SCOPE = "sparse_coalesce"

# the widest row the windowed sort takes: a window is twice the cap, and a
# minor-axis sort of 8192 lanes still sits in VMEM
_WINDOW_CAP = 4096
_MIN_CAP = 128
# 4-byte words a lane the program holds at its peak beside operand and
# result (memory_analysis of the cell's program: see tests/test_v5e_compile)
_WORKSPACE_WORDS = 10


def window_cap(row_cap) -> int | None:
    """The chunk of the windowed sort for rows of at most ``row_cap`` lanes:
    the power of two ≥ max(row_cap, 128), or ``None`` where the bound is
    unknown or past ``_WINDOW_CAP``."""
    if row_cap is None or row_cap > _WINDOW_CAP:
        return None
    return max(_MIN_CAP, 1 << max(int(row_cap) - 1, 0).bit_length())


def sort_form(n_minor: int, grouped: bool, row_cap) -> tuple:
    """Which sort coalesces lanes whose result has ``n_minor`` columns:
    ``("window", cap, why)`` where the lanes are ``grouped`` by result row,
    no row holds more than ``row_cap`` ≤ ``_WINDOW_CAP`` lanes and a window's
    row rank and the column fit one 32-bit key, else ``("global", None,
    why)``. Decided from what the apply can observe (the dimension, the
    operand's row bound); the ``sketch.dispatch`` span carries it."""
    if not grouped:
        return "global", None, "lanes regrouped by the hashed axis"
    cap = window_cap(row_cap)
    if cap is None:
        return "global", None, (
            "no row bound" if row_cap is None
            else f"a row of {row_cap} lanes passes {_WINDOW_CAP}")
    if (2 * cap).bit_length() + _column_bits(n_minor) > 32:
        return "global", None, f"{n_minor} columns leave no room for the rank"
    return "window", cap, f"rows of at most {cap} lanes"


def _column_bits(n_minor: int) -> int:
    """Bits of a window's sort key that hold the column."""
    return max(int(n_minor) - 1, 1).bit_length()


def _shift(x, d, fill=0):
    """``x`` moved towards higher lanes by the traced ``d`` ≥ 0 (lane j
    holds what lane j − d held), ``fill`` below. (A ``lax.switch`` over
    static powers of two, each a slice and a pad, read 33 % slower on a
    v5e: PERF.md PR 64.)"""
    n = x.shape[0]
    pad = jnp.full((n,), fill, x.dtype)
    return lax.dynamic_slice(jnp.concatenate([pad, x]), (n - d,), (n,))


def _unshift(x, d, fill=0):
    """``x`` moved towards lower lanes by the traced ``d`` ≥ 0."""
    n = x.shape[0]
    pad = jnp.full((n,), fill, x.dtype)
    return lax.dynamic_slice(jnp.concatenate([x, pad]), (d,), (n,))


def _segment_sums(term, same_prev):
    """Inclusive sums of ``term`` over runs of lanes (``same_prev[j]``: lane
    j continues lane j − 1's run): a run's last lane holds its sum. Doubling
    steps, as many as the longest run's bit length."""
    def more(carry):
        return jnp.any(carry[2])

    def step(carry):
        k, x, reach = carry
        d = jnp.left_shift(jnp.int32(1), k)
        x = x + jnp.where(reach, _shift(x, d), jnp.zeros((), x.dtype))
        return k + 1, x, reach & _shift(reach, d, False)

    return lax.while_loop(more, step, (jnp.int32(0), term, same_prev))[1]


def _compact(keep, arrays):
    """The lanes of ``arrays`` where ``keep`` holds, moved to the front in
    order; ``(count, kept_before, arrays)`` — lanes past ``count`` are
    unspecified. A kept lane moves left by the dropped lanes before it, one
    bit of that displacement a step."""
    n = keep.shape[0]
    kept_through = jnp.cumsum(keep.astype(jnp.int32), dtype=jnp.int32)
    kept_before = kept_through - keep.astype(jnp.int32)
    count = kept_through[-1]
    # a kept lane's way to go, plus one: 0 says the lane holds nothing
    todo = jnp.where(keep, jnp.arange(n, dtype=jnp.int32) - kept_before + 1, 0)
    largest = jnp.max(todo) - 1

    def more(carry):
        # the steps come three bits at a time: an apply's time is then no
        # step function of the merged count at every power of two (blocks
        # of 16.3 k against 16.7 k merged lanes read 209.95 against 215.7 ms
        # on a v5e, PERF.md PR 64), at up to two steps that move nothing
        return jnp.left_shift(jnp.int32(1), carry[0] // 3 * 3) <= largest

    def step(carry):
        b, todo, xs = carry
        d = jnp.left_shift(jnp.int32(1), b)
        moves = (todo > 0) & (((todo - 1) & d) != 0)
        arriving = _unshift(jnp.where(moves, todo, 0), d)
        arrives = arriving > 0
        todo = jnp.where(arrives, arriving, jnp.where(moves, 0, todo))
        xs = tuple(jnp.where(arrives, _unshift(x, d), x) for x in xs)
        return b + 1, todo, xs

    xs = lax.while_loop(more, step, (jnp.int32(0), todo, tuple(arrays)))[2]
    return count, kept_before, xs


def _row_ranks(starts, lanes: int):
    """The running count of row starts over ``lanes`` lanes (≥ 1,
    nondecreasing; rows that start at one lane count once): ``starts`` are
    the row pointers, the last of them the start of the padding, a row of
    its own. One scatter of the pointers, one running sum."""
    flags = jnp.zeros((lanes,), jnp.int32).at[starts].max(
        1, indices_are_sorted=True, mode="drop").at[0].set(1)
    return jnp.cumsum(flags, dtype=jnp.int32)


def _rows_sorted(starts, minor, term, *, cap: int, n_minor: int):
    """``(rank, minor, term)`` of lanes grouped by row (row pointers
    ``starts``, a row at most ``cap`` lanes), each row's lanes sorted by
    ``minor`` in the row's own lane range — the windowed sort, the lane
    extent padded to whole windows for it and cut back."""
    lanes = term.shape[0]
    pad = -lanes % (2 * cap)
    rank = _row_ranks(starts, lanes + pad)
    minor, term = _window_sorted(
        rank, jnp.pad(minor, (0, pad)), jnp.pad(term, (0, pad)), cap=cap,
        bits=_column_bits(n_minor))
    return rank[:lanes], minor[:lanes], term[:lanes]


def _window_sorted(rank, minor, term, *, cap: int, bits: int):
    """``(minor, term)`` with each row's lanes sorted by ``minor``, every
    lane in its row's own lane range: ``rank`` is the running count of row
    starts (≥ 1, nondecreasing), a row holds at most ``cap`` lanes and the
    lane extent is a multiple of 2·cap."""
    lanes = rank.shape[0]
    chunks = lanes // cap
    width = 2 * cap
    # a chunk's base: the rank at the last lane of the chunk before it — a
    # lane whose rank equals its chunk's base continues a row from there
    base = jnp.concatenate([jnp.zeros((1,), rank.dtype),
                            rank[cap - 1::cap][:chunks - 1]])
    mask = jnp.uint32((1 << bits) - 1)

    def sort_windows(rank_w, minor_w, term_w, base_w):
        # window w holds chunks (q, q + 1): rank inside it 0 .. 2·cap
        local = (rank_w.reshape(-1, width) - base_w[:, None]).astype(
            jnp.uint32)
        key = (local << bits) | minor_w.reshape(-1, width).astype(jnp.uint32)
        # equal keys are one cell's terms: their order is nobody's
        key, val = lax.sort((key, term_w.reshape(-1, width)), dimension=1,
                            num_keys=1, is_stable=False)
        return (key & mask).astype(minor.dtype), val

    even = sort_windows(rank, minor, term, base[0::2])
    # the odd windows start one chunk in; a chunk of closing lanes behind
    tail_rank = jnp.full((cap,), rank[-1] + 1, rank.dtype)
    odd = sort_windows(
        jnp.concatenate([rank[cap:], tail_rank]),
        jnp.concatenate([minor[cap:], jnp.zeros((cap,), minor.dtype)]),
        jnp.concatenate([term[cap:], jnp.zeros((cap,), term.dtype)]),
        base[1::2])
    carried = (rank.reshape(chunks, cap)
               == base[:, None]).reshape(chunks // 2, 2, cap)

    def place(e, o):
        # chunk 2w: its own window is even w (first half), the window
        # before it odd w − 1 (second half); chunk 2w + 1: odd w, even w
        own = jnp.stack([e[:, :cap], o[:, :cap]], axis=1)
        before = jnp.stack([jnp.roll(o[:, cap:], 1, axis=0), e[:, cap:]],
                           axis=1)
        return jnp.where(carried, before, own).reshape(lanes)

    return place(even[0], odd[0]), place(even[1], odd[1])


def coalesce(major, minor, term, count, *, n_major: int, n_minor: int,
             form: str, cap=None, starts=None):
    """Canonical CSR lanes ``(data, indices, indptr, merged)`` of the
    (``n_major`` × ``n_minor``) matrix whose entries are the first ``count``
    lanes' ``term`` at (``major``, ``minor``), duplicates summed; ``merged``
    = ``count`` − stored entries, a device scalar like ``indptr[-1]``.

    ``form`` (:func:`sort_form`): ``"window"`` takes ``major`` = None and
    ``starts`` = the (``n_major`` + 1,) row pointers of the lanes (grouped
    by row, a row at most ``cap`` lanes); ``"global"`` takes ``major`` (any
    order) and no ``starts``. The lane extent is kept."""
    lanes = term.shape[0]
    lane = jnp.arange(lanes, dtype=jnp.int32)
    with jax.named_scope(SCOPE):
        if form == "window":
            group, minor, term = _rows_sorted(starts, minor, term, cap=cap,
                                              n_minor=n_minor)
            valid = lane < count
        elif form == "global":
            major = jnp.where(lane < count, major, n_major)  # padding last
            group, minor, term = lax.sort((major, minor, term), num_keys=2,
                                          is_stable=False)
            valid = group < n_major
            # a row's first lane: the count of lanes in the rows before it
            starts = jnp.searchsorted(
                group, jnp.arange(n_major + 1, dtype=group.dtype))
        else:
            raise ValueError(f"no sort form {form!r}")
        same = ((group[1:] == group[:-1]) & (minor[1:] == minor[:-1])
                & valid[1:])
        no = jnp.zeros((1,), bool)
        term = _segment_sums(term, jnp.concatenate([no, same]))
        keep = valid & ~jnp.concatenate([same, no])
        stored, kept_before, (data, indices) = _compact(keep, (term, minor))
        indptr = jnp.concatenate([kept_before, stored[None]])[starts]
        live = lane < stored
        data = jnp.where(live, data, jnp.zeros((), data.dtype))
        indices = jnp.where(live, indices, jnp.zeros((), indices.dtype))
        return data, indices, indptr.astype(jnp.int32), count - stored
