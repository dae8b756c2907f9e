"""Operations and bytes one rowwise dense feature-map apply needs:
Z = f(X · Wᵀ) with X (m × n) read once, Z (m × s) written once, W, scales
and shifts generated (never read). One multiply-add a product, as
``dense_sketch.py`` reckons a one-pass contraction; the m·s evaluations of f
(cos, exp) are given apart, for the record: the peaks table has no rate for
them, so the roofline does not count them."""


def work(config: dict) -> dict:
    m, n, s = config["rows_per_panel"], config["n"], config["s"]
    itemsize = 4  # float32 operand and result
    return {"flops": 2 * m * n * s, "bytes": (m * n + m * s) * itemsize,
            "transcendentals": m * s}
