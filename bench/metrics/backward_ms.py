"""backward_ms: device self time per step of the ops under ``lag.grad``
and ``transpose(``: the workers' backward passes, with the forward that
remat recomputes in them (bench/stages.py), averaged over the chips.
Moves tokens_per_s; layer: model forward and backward."""
import stages


def read(ctx):
    return stages.stage_ms(ctx, "backward")
