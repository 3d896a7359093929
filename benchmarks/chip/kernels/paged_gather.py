"""Operations and bytes of one ``paged_gather`` call.

The logical view [B, mb, bs, Hkv·hd] of a block pool gathered through the
block table pages [B, mb], one pool block per grid step, row by row. The
kernel reads the table, writes every view block, and fetches a pool block
at each grid step whose block differs from the previous step's: every
allocated block, and block 0 once for each run of unallocated slots (they
all clamp to it). Which slots are allocated is not in the shapes, so the
caller gives the blocks each row holds. No arithmetic: bandwidth bounds it.
"""
BOUND = "bandwidth"


def fetched_blocks(counts, mb: int) -> int:
    """Pool blocks fetched for a table whose row i holds ``counts[i]``
    allocated blocks followed by unallocated slots."""
    n, prev_full = 0, True            # nothing is loaded before step one
    for c in counts:
        n += c
        if c < mb and (c > 0 or prev_full):
            n += 1                    # a run of block 0 starts here
        prev_full = c == mb
    return n


def cost(operands, results, counts) -> dict:
    """``operands``/``results``: [(itemsize, shape)] as the call has them:
    (pages [B, mb], pool [n_blocks, bs, Hkv·hd]) and (view,); ``counts``:
    the allocated blocks of each of the B rows."""
    (size, view), = results
    written = size
    for n in view:
        written *= n
    (p_size, (rows, mb)), (b_size, pool) = operands[:2]
    block = b_size * pool[1] * pool[2]
    return {"flops": 0.0,
            "bytes": float(written + p_size * rows * mb
                           + fetched_blocks(counts, mb) * block)}
