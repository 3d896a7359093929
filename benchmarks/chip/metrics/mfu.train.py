"""mfu.train: model FLOPs of the fine-tuning steps (recomputation excluded,
``model.train_flops_per_step``) times the run's tokens per second, over the
chips' bf16 peak. Moves train_tokens_per_s."""


def read(ctx):
    d = ctx["cell"].traffic
    per_token = ctx["flops_per_step"] / (d["batch"] * d["seq"])
    peak = ctx["peaks"]["bf16_flops"] * ctx["chips"]
    return 100.0 * per_token * ctx["train_tokens_per_s"] / peak
