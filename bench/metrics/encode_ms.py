"""encode_ms: device self time per step of the ops under ``lag.encode``:
the innovation, the trigger norms, the LAQ absmax and quantize, and the
upload decision (bench/stages.py), averaged over the chips.
Moves tokens_per_s; layer: comm plane."""
import stages


def read(ctx):
    return stages.stage_ms(ctx, "encode")
