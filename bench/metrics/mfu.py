"""mfu: model FLOPs per token x tokens/s over chips x the bf16 peak, in %.

The f32 matmuls run at the TPU's default precision, one bf16 pass, so the
bf16 peak is the yardstick.  Moves tokens_per_s; layer: whole train step."""


def read(ctx):
    return 100.0 * ctx.flops_per_token * ctx.tokens_per_s / (
        ctx.chips * ctx.peaks.bf16_flops)
