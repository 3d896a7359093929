"""Operations and bytes of one ``selective_scan_bwd_pallas`` call.

From dt, dtx [B, S, di], Bᵀ, Cᵀ [B, n, S], Aᵀ [n, di], the saved
chunk-start states [B, S/chunk, n, di], dy [B, S, di] and dh_final
[B, n, di]: d(dt), d(dtx) [B, S, di], the per-tile partial dBᵀ and dCᵀ
[B, tiles, n, S], dAᵀ [B, n, di] and dh0 [B, n, di]. The cotangents need
15 operations per (token, d_inner, state) element
(``hybrid.SCAN_BWD_OPS``; the kernel's recomputation of the chunk's
states is not counted), and each operand is read and each result written
once: about 12 operations per byte at the cell's shapes, so bandwidth
bounds it on a v5e.
"""
BOUND = "bandwidth"
OPS = 15


def cost(operands, results) -> dict:
    (_, (b, s, di)), (_, (_, n, _)) = operands[0], operands[2]
    nbytes = sum(size * _count(shape) for size, shape in operands + results)
    return {"flops": float(OPS * b * s * di * n), "bytes": float(nbytes)}


def _count(shape) -> int:
    out = 1
    for s in shape:
        out *= s
    return out
