"""mfu.decode.chat: model FLOPs of one decode step over the engine's slots
(``model.decode_flops_per_step``: each row under its own tenant's adapter)
over the mean device time of the decode executable times the chips' bf16
peak. Moves itl_p99_ms."""
MODULE = "decode_step"


def read(ctx):
    import model
    runs = ctx["trace"].module_runs(MODULE)
    if not runs:
        return None
    mean_s = 1e-9 * sum(e.dur for e in runs) / len(runs)
    flops = model.decode_flops_per_step(ctx["dims"],
                                        rows=ctx["traffic"]["slots"])
    return 100.0 * flops / (mean_s * ctx["peaks"]["bf16_flops"]
                            * ctx["chips"])
