"""collective_ms: device time per step of the all-gather operations (the
gated gather of repro/devrun/runner.py), summed per chip and averaged over
the chips.  Moves tokens_per_s; layer: gated gather.

An operation is told by its opcode, not by its text: a TPU op's trace name
is its whole HLO instruction, operands included, so an op that reads the
gather's output names ``%all-gather.6`` too."""

OPCODES = ("all-gather", "all-gather-start", "all-gather-done")


def read(ctx):
    found = ctx.trace.matching(lambda e: e.opcode in OPCODES)
    if not any(found.values()):
        return None
    per_chip = sum(sum(e.dur for e in evs) for evs in found.values()) \
        / len(found)
    return 1e-6 * per_chip / ctx.window.steps
