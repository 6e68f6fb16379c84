"""dispatch_ms: host time per step inside the call to the jitted step,
from the harness's own ``dispatch`` span, summed over the window's steps.
Moves tokens_per_s; layer: host step loop."""


def read(ctx):
    if not ctx.window.steps:
        return None
    return 1e3 * ctx.window.spans["dispatch"] / ctx.window.steps
