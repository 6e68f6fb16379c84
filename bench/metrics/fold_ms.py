"""fold_ms: device self time per step of the ops under ``lag.fold``:
the masked folds of the mirrors (bench/stages.py), averaged over the
chips.
Moves tokens_per_s; layer: comm plane."""
import stages


def read(ctx):
    return stages.stage_ms(ctx, "fold")
