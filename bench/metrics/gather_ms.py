"""gather_ms: device self time per step of the ops under ``lag.gather``:
the mask and payload all-gathers of repro/devrun, their lax.cond and the
worker-order sum (bench/stages.py), averaged over the chips.
Moves tokens_per_s; layer: gated gather."""
import stages


def read(ctx):
    return stages.stage_ms(ctx, "gather")
