"""Operations and bytes one columnwise DCT mix-and-sample apply needs: S·A
with A (m × n) read once, the s × n sample written once, the signs and the
sampled coordinates generated (never read). The operations are the
algorithm's: a fast DCT's (5/2)·m·log₂m a column (the real-data FFT behind
it; m need be no power of two), whatever the program spends on the MXU to
get them (a dense DFT factor of 125 costs 2·125 real operations a value and
part, in six bfloat16 passes, where the butterflies cost a handful), so the
roofline reads the same work on an XLA route and on a kernel."""

import math


def work(config: dict) -> dict:
    m, n, s = config["m"], config["n"], config["s"]
    itemsize = 4  # float32 operand and result
    return {"flops": int(2.5 * m * math.log2(m) * n),
            "bytes": (m * n + s * n) * itemsize}
