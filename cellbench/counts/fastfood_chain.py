"""Operations and bytes one rowwise Fastfood feature-map apply needs:
Z = √(2/s)·cos(Sm ⊙ H(G ⊙ Π(H(B ⊙ x̃))) + b) with X (m × n) read once, Z
(m × s) written once, the diagonals, the permutations and the shifts
generated (never read). The operations are the algorithm's: two butterflies
of NB·log₂NB adds and subtracts a block and an example, and the three
diagonals' and the shift's multiply or add a value — whatever the kernel
spends on the MXU to get them (a dense ±1 factor of 128 costs 2·128 a value
where the butterfly costs 7), so the roofline reads the same work whatever
implements it. The m·s cosines are given apart, for the record: the peaks
table has no rate for them, so the roofline does not count them."""


def work(config: dict) -> dict:
    m, n, s = config["rows_per_panel"], config["n"], config["s"]
    block = 1 << max(0, (n - 1).bit_length())       # NB: n padded to 2^k
    blocks = -(-s // block)
    itemsize = 4  # float32 operand and result
    butterflies = 2 * blocks * block * (block.bit_length() - 1)
    diagonals = 4 * blocks * block                   # B, G, Sm ⊙ and + b
    return {"flops": m * (butterflies + diagonals),
            "bytes": (m * n + m * s) * itemsize,
            "transcendentals": m * s}
