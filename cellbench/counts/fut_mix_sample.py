"""Operations and bytes one columnwise mix-and-sample apply needs: S·A with
A (m × n) read once, the s × n sample written once, the signs and the
sampled coordinates generated (never read). The operations are the
algorithm's: the butterfly's m·log₂m adds and subtracts a column, whatever
the kernel spends on the MXU to get them (a dense ±1 factor of 128 costs
2·128 a value where the butterfly costs 7), so the roofline reads the same
work whatever implements it."""

import math


def work(config: dict) -> dict:
    m, n, s = config["m"], config["n"], config["s"]
    itemsize = 4  # float32 operand and result
    return {"flops": m * int(math.log2(m)) * n, "bytes": (m * n + s * n) * itemsize}
