"""selective_scan_fwd_roofline: the least time the chip could take for
every traced call of ``selective_scan_pallas`` (larger of its operations
over the bf16 peak and its bytes over the HBM bandwidth, from the call's
operand and result shapes in the trace), over the time those calls took
in the trace. Moves train_tokens_per_s."""
KERNEL = "selective_scan_pallas"
COST = "selective_scan_fwd"


def read(ctx):
    import tracing
    return tracing.kernel_roofline(ctx, KERNEL, COST)
