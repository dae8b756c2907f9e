"""Operations and bytes one rowwise hash sketch of a sparse row block needs
when its result stays sparse: Z[r, h(c)] += v(c)·X[r, c], one add a stored
nonzero; each lane (value and column id, 8 B) read once and written once,
both row pointers once. h and v are generated, never read; the sort that
finds the collisions moves nothing the algorithm *needs* — so the floor is
HBM's, a millisecond, and a sort's share of it reads low: that is the truth
about a sort, not a fault of the count."""


def stored_nonzeros(config: dict) -> int:
    return round(config["rows_per_panel"] * config["nnz_per_row_mean"])


def work(config: dict) -> dict:
    rows, nnz = config["rows_per_panel"], stored_nonzeros(config)
    return {"flops": nnz, "bytes": 2 * nnz * 8 + 2 * (rows + 1) * 4}
