"""mfu_pct: Tokens/s/chip of the window times the FLOPs a token needs (forward + backward from shapes, causal attention, nothing recomputed) over the chip's bf16 peak."""


def read(ctx):
    if ctx["peaks"] is None or "train_tokens_per_s_per_chip" not in ctx["e2e"]:
        return None
    flops = ctx["costs"].train_flops_per_token(ctx["dims"], int(ctx["mix"]["seq"]))
    return 100.0 * ctx["e2e"]["train_tokens_per_s_per_chip"] * flops / ctx["peaks"]["bf16_flops"]
