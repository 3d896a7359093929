"""Operations and bytes of one ``compose_mm_fwd_pallas`` call.

delta [M, N] = (g - 1) ⊙ base + g ⊙ s ⊙ (h @ Bᵀ), from base [M, N],
h [M, rp], B [N, rp] and gm1 [1, N]. The algorithm needs the up-projection
(2 M N rp) and four element-wise operations per output, and reads each
operand and writes the output once. At the training shapes (M = 4096,
rp = 384) it does 384 / 2 = 192 operations per bf16 byte of base read and
delta written, under the v5e's 197e12 / 819e9 = 240: bandwidth bounds it.
"""
BOUND = "bandwidth"


def cost(operands, results) -> dict:
    """``operands``/``results``: [(itemsize, shape)] as the call has them."""
    (_, (m, n)), (_, (_, rp)) = operands[0], operands[1]
    nbytes = sum(size * _count(shape) for size, shape in operands + results)
    return {"flops": 2.0 * m * n * rp + 4.0 * m * n, "bytes": float(nbytes)}


def _count(shape) -> int:
    out = 1
    for s in shape:
        out *= s
    return out
