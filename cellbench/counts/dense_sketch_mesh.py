"""Operations and bytes ONE CHIP of the grid needs for one rowwise dense
sketch apply of an [MC,MR] operand: Y = A · Sᵀ with the chip's shard of A
((m/r) × (n/c)) read once and its shard of Y ((m/r) × (s/c)) written once, S
generated (never read). The exchange of partial products between the chips
of a grid row is no work of the algorithm's on a chip's memory or MXU: it
stands in the denominator of ``sketch_roofline.apply`` (the harness's device
time is the mean over the device planes, the peaks are one chip's), not in
this count."""


def work(config: dict) -> dict:
    r, c = config["grid"]
    m, n, s = config["m"] // r, config["n"] // c, config["s"]
    itemsize = 4  # float32 operand and result
    return {"flops": 2 * m * n * s, "bytes": (m * n + m * (s // c)) * itemsize}
