"""Operations and bytes of one ``compose_mm_bwd_pallas`` call.

From dY [M, N], B [N, rp], gm1 and gs [1, N]: d_base [M, N] = (g - 1) ⊙ dY
and d_h [M, rp] = ((g s) ⊙ dY) @ B. The algorithm needs the contraction
over N (2 M N rp) and two element-wise operations per element of dY, and
reads each operand and writes each result once. At the training shapes it
does about 192 operations per byte: bandwidth bounds it on a v5e.
"""
BOUND = "bandwidth"


def cost(operands, results) -> dict:
    (_, (m, n)), (_, (_, rp)) = operands[0], operands[1]
    nbytes = sum(size * _count(shape) for size, shape in operands + results)
    return {"flops": 2.0 * m * n * rp + 3.0 * m * n, "bytes": float(nbytes)}


def _count(shape) -> int:
    out = 1
    for s in shape:
        out *= s
    return out
