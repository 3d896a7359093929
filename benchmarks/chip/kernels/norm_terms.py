"""Operations and bytes of one ``norm_terms_pallas`` call.

For W [d_out, d_in], A [r, d_in], B [d_out, r]: base_sq = Σ_k W² and
cross = Σ_l B ⊙ (W @ Aᵀ), both fp32 [d_out]. The algorithm needs the
product W @ Aᵀ (2 d_out d_in r) and the squares and sums of W (2 d_out
d_in), and reads W, A and B once. At d_in 3584 and r = 384 that is about
384 operations per byte of W: compute bounds it on a v5e (240).
"""
BOUND = "compute"


def cost(*, d_out: int, d_in: int, rank: int, itemsize: int) -> dict:
    flops = 2.0 * d_out * d_in * rank + 2.0 * d_out * d_in \
        + 2.0 * d_out * rank
    nbytes = itemsize * (d_out * d_in + rank * d_in + d_out * rank) \
        + 2 * 4 * d_out
    return {"flops": flops, "bytes": float(nbytes)}
