"""Operations and bytes one rank-k randomized SVD needs, counted as its
passes over A: the range sketch A·Sᵀ, two products per power iteration
(Aᵀ·Q, A·(Aᵀ·Q)) and the projection Aᵀ·Q. Each pass reads the (m × n)
operand once and multiplies it by a k'-wide panel; the k'-sized QR, eigh
and SVD work is left out (it is under 1 % of the time and of the count)."""


def sketch_width(config: dict) -> int:
    return int(config["oversampling_ratio"] * config["rank"]) + int(
        config.get("oversampling_additive", 0))


def work(config: dict) -> dict:
    m, n = config["m"], config["n"]
    passes = 2 + 2 * config["num_iterations"]
    itemsize = 4  # float32 operand
    return {"flops": passes * 2 * m * n * sketch_width(config),
            "bytes": passes * m * n * itemsize}
