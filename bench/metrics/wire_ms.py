"""wire_ms: device self time per step of the ops under ``lag.wire``:
the pack and unpack of the collective's wire arrays (bench/stages.py),
averaged over the chips.
Moves tokens_per_s; layer: gated gather."""
import stages


def read(ctx):
    return stages.stage_ms(ctx, "wire")
