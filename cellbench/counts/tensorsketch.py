"""Operations and bytes one rowwise TensorSketch apply needs:
z = IFFT(∏_{k<q} FFT(√γ·C_k x + √c·s'_k·e_{h'_k})) with X (m × n) read once,
Z (m × s) written once, the buckets and signs generated (never read). The
operations are the algorithm's, an example: q·n multiply-adds (the q
CountSketches), q + 1 real FFTs of length s at (5/2)·s·log₂s, and q − 1
products of half spectra (s/2 + 1 complex bins, 6 operations a bin) —
whatever the program spends on the MXU to get them (a dense DFT factor of
128 costs 2·128 a value where the butterfly costs 5 a stage), so that the
roofline reads the same work on any route."""

import math


def work(config: dict) -> dict:
    m, n, s, q = config["rows_per_panel"], config["n"], config["s"], config["q"]
    itemsize = 4  # float32 operand and result
    sketches = 2 * q * n
    transforms = (q + 1) * 5 * s * math.log2(s) / 2
    products = 6 * (q - 1) * (s // 2 + 1)
    return {"flops": int(m * (sketches + transforms + products)),
            "bytes": (m * n + m * s) * itemsize}
