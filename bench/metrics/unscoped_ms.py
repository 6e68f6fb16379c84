"""unscoped_ms: device self time per step of the ops that carry no
``lag.*`` scope: what XLA adds outside the program's stages, such as
copies it inserts (bench/stages.py), averaged over the chips.
Moves tokens_per_s; layer: whole train step."""
import stages


def read(ctx):
    return stages.stage_ms(ctx, "unscoped")
