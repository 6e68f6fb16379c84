"""idle_share: 1 - (union of device operation intervals) / traced window,
in %, averaged over the chips.  Moves tokens_per_s; layer: device."""


def read(ctx):
    busy = ctx.trace.busy_s()
    if busy <= 0.0:
        return None
    return 100.0 * (1.0 - busy / ctx.trace.window_s())
