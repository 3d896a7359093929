"""paged_gather_roofline: the least time of every traced ``paged_gather``
call (its bytes over the HBM bandwidth: the table read, the pool blocks
fetched and the view written, ``kernels/paged_gather.py``) over the time
those calls took. Moves itl_p99_ms.

The blocks each row holds come from the driver's record of every engine
tick (``ctx["ticks"]``: the slots' block counts before and after it), one
record per ``engine.step`` host span, in order. A call of the batched
decode sees each row at the larger of the two counts; a call of the batch-1
chunk step sees the row its chunk grew, taken in order of growth when
several chunk steps run in one tick. A block allocated or freed inside the
tick can shift a row's count by one."""
import bisect

KERNEL = "paged_gather"
COST = "paged_gather"


def read(ctx):
    tv, ticks = ctx["trace"], ctx.get("ticks")
    ops = sorted(tv.kernel_ops(KERNEL), key=lambda e: e.start)
    spans = sorted(s.start for s in tv.spans if s.name == "engine.step")
    if not ops or not ticks or len(spans) != len(ticks):
        return None
    cost = ctx["kernel_cost"](COST).cost
    runs: dict[str, list[int]] = {}
    for m in tv.modules:
        runs.setdefault(m.name, []).append(m.start)
    for starts in runs.values():
        starts.sort()
    least = took = 0.0
    for e in ops:
        k = bisect.bisect_right(spans, e.start) - 1
        if k < 0:
            return None
        pre, post = ticks[k]
        rows = e.operands[0][1][0]
        if rows == len(pre):
            counts = [max(a, b) for a, b in zip(pre, post)]
        else:
            starts = runs.get(e.module, [e.start])
            mine = bisect.bisect_right(starts, e.start) - 1
            first = bisect.bisect_left(starts, spans[k])
            grown = sorted(range(len(pre)),
                           key=lambda i: (pre[i] - post[i], -post[i]))
            counts = [post[grown[min(max(mine - first, 0),
                                     len(grown) - 1)]]]
        least += (cost(list(e.operands), list(e.results), counts)["bytes"]
                  / ctx["peaks"]["hbm_bytes_per_s"])
        took += e.dur * 1e-9
    return 100.0 * least / took
