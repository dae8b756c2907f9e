"""Operations and bytes one rowwise dense sketch of a sparse row block
needs, whatever implements it: Y[r, :] += X[r, c]·S[:, c] for every stored
nonzero, a multiply and an add for each of its s results; each nonzero's
value and column id read once (8 B), the row pointers read once, Sᵀ (n × s)
read once, Y (rows × s) written once. (S is generated, but no walk over
stored nonzeros can do without its rows in memory, so they count.)"""


def stored_nonzeros(config: dict) -> int:
    return round(config["rows_per_panel"] * config["nnz_per_row_mean"])


def work(config: dict) -> dict:
    rows, n, s = config["rows_per_panel"], config["n"], config["s"]
    nnz = stored_nonzeros(config)
    return {"flops": 2 * nnz * s,
            "bytes": nnz * 8 + (rows + 1) * 4 + n * s * 4 + rows * s * 4}
