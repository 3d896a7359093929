"""Operations and bytes of one ``selective_scan_pallas`` call.

From dt and dtx [B, S, di], Bᵀ and Cᵀ [B, n, S], Aᵀ [n, di] and h0
[B, n, di]: y [B, S, di], h_final [B, n, di] and, when the call saves them
for the backward, the states at each chunk's start [B, S/chunk, n, di].
The recurrence needs 7 operations per (token, d_inner, state) element
(``hybrid.SCAN_FWD_OPS``) and reads each operand and writes each result
once. At the cell's shapes that is 7 · 16 = 112 operations per 12 bytes of
dt, dtx and y: about 9 per byte, where the v5e's 197e12 / 819e9 = 240:
bandwidth bounds it.
"""
BOUND = "bandwidth"
OPS = 7


def cost(operands, results) -> dict:
    """``operands``/``results``: [(itemsize, shape)] as the call has them."""
    (_, (b, s, di)), (_, (_, n, _)) = operands[0], operands[2]
    nbytes = sum(size * _count(shape) for size, shape in operands + results)
    return {"flops": float(OPS * b * s * di * n), "bytes": float(nbytes)}


def _count(shape) -> int:
    out = 1
    for s in shape:
        out *= s
    return out
