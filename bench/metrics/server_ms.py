"""server_ms: device self time per step of the ops under ``lag.server``:
the in-process sum of the uploads (repro/devrun's is ``lag.gather``), the
aggregate recursion, the server step, the history, the counters and the
step's metrics (bench/stages.py), averaged over the chips.
Moves tokens_per_s; layer: server update."""
import stages


def read(ctx):
    return stages.stage_ms(ctx, "server")
