"""Operations and bytes of one ``assemble_norm_pallas`` call.

w_norm = sqrt(max(base_sq + 2s·cross + s²·ba_sq, 0)) over fp32 [d_out]
vectors: five operations per element, three vectors read and one written.
Bandwidth bounds it.
"""
BOUND = "bandwidth"


def cost(*, d_out: int) -> dict:
    return {"flops": 5.0 * d_out, "bytes": float(4 * 4 * d_out)}
