"""Operations and bytes one rowwise hash sketch of a sparse row block needs:
Z[r, h(c)] += v(c)·X[r, c], one add a stored nonzero; each nonzero's value
and column id read once (8 B), the row pointers read once, Z (rows × s)
written once. h and v are generated, never read."""


def stored_nonzeros(config: dict) -> int:
    return round(config["rows_per_panel"] * config["nnz_per_row_mean"])


def work(config: dict) -> dict:
    rows, s, nnz = config["rows_per_panel"], config["s"], stored_nonzeros(config)
    return {"flops": nnz, "bytes": nnz * 8 + (rows + 1) * 4 + rows * s * 4}
