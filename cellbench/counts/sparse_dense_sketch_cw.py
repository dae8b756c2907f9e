"""Operations and bytes one columnwise dense sketch of a sparse row block
needs, whatever implements it: Y[:, c] += X[r, c]·S[:, r] for every stored
nonzero, a multiply and an add for each of its s results; each nonzero's
value and row id read once (8 B), the column pointers read once, Sᵀ
(rows × s: the operator as the program holds it, a row an example) read
once, Y (s × n) written once. (S is generated, but no walk over stored
nonzeros can do without its columns in memory, so they count.)"""

from cellbench.counts.sparse_dense_sketch import stored_nonzeros


def work(config: dict) -> dict:
    rows, n, s = config["rows_per_panel"], config["n"], config["s"]
    nnz = stored_nonzeros(config)
    return {"flops": 2 * nnz * s,
            "bytes": nnz * 8 + (n + 1) * 4 + rows * s * 4 + n * s * 4}
