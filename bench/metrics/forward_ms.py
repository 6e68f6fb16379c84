"""forward_ms: device self time per step of the ops under the program's
``lag.grad`` scope that are not under ``transpose(``: the workers'
forward passes (bench/stages.py), averaged over the chips.
Moves tokens_per_s; layer: model forward and backward."""
import stages


def read(ctx):
    return stages.stage_ms(ctx, "forward")
