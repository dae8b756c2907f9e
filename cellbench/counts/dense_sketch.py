"""Operations and bytes one rowwise dense sketch apply needs: Y = A · Sᵀ with
A (m × n) read once, Y (m × s) written once, S generated (never read)."""


def work(config: dict) -> dict:
    m, n, s = config["rows_per_panel"], config["n"], config["s"]
    itemsize = 4  # float32 operand and result
    return {"flops": 2 * m * n * s, "bytes": (m * n + m * s) * itemsize}
