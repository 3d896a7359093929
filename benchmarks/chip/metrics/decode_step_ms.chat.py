"""decode_step_ms.chat: mean device time of one run of the engine's decode
executable, from the module runs in the trace. Moves itl_p99_ms."""
MODULE = "decode_step"


def read(ctx):
    runs = ctx["trace"].module_runs(MODULE)
    if not runs:
        return None
    return 1e-6 * sum(e.dur for e in runs) / len(runs)
