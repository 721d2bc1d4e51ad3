"""Model FLOPs of both sides' training steps (``flops.train_flops``) per
second of the traced window, over the chips' bf16 peak, in %.  Float32 at
``highest`` precision takes about six bf16 passes, so about 17% is its
ceiling."""


def read(ctx):
    if ctx["window_s"] <= 0 or not ctx["peaks"]:
        return None
    rate = ctx["flops_per_step"] * ctx["steps"] / ctx["window_s"]
    chips = max(1, len([d for d, b in ctx["trace"].busy.items() if b > 0]))
    return 100.0 * rate / (chips * ctx["peaks"]["bf16_flops_per_s"])
