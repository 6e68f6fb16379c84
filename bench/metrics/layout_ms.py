"""layout_ms: device self time per step of the ops under ``lag.layout``:
the flat buffer's flattens and scatters (``FlatLayout``, also where they
serve another stage; bench/stages.py), averaged over the chips.
Moves tokens_per_s; layer: comm plane."""
import stages


def read(ctx):
    return stages.stage_ms(ctx, "layout")
