"""plane_kernel_ms: device time per step of the comm plane's Pallas
kernels (repro/fastpath/kernels.py), summed per chip and averaged over
the chips.  Moves tokens_per_s; layer: comm plane.

The trace names a Mosaic kernel after the jitted function around it
(``%train_step.3 = ... custom-call(...), custom_call_target=
"tpu_custom_call"``), so a plane kernel is told by its signature over the
plane's ``(W, rows, 128)`` float32 buffers:

  _masked_kernel     returns a (W, rows, 128) buffer (the folded mirror)
  _laq_kernel        returns a tuple (payload, residual, partials)
  _sq_kernel         one buffer in, per-sub-block partials out
  _delta_sq_kernel   two buffers in, partials out
  _absmax_kernel     three buffers in, partials out
"""
import re

KERNELS = ("_delta_sq_kernel", "_sq_kernel", "_absmax_kernel",
           "_laq_kernel", "_masked_kernel")
PARTIALS = {1: "_sq_kernel", 2: "_delta_sq_kernel", 3: "_absmax_kernel"}


def kernel_of(event, rows):
    """The plane kernel an operation is, or None."""
    text = event.name
    if 'custom_call_target="tpu_custom_call"' not in text:
        return None
    buf = re.compile(r"[\[,]%d,128\]" % rows)
    head, _, rest = text.partition(" custom-call(")
    operands = rest.split("), custom_call_target=", 1)[0]
    if not buf.search(operands):
        return None
    if "= (" in head:
        return "_laq_kernel"
    if buf.search(head):
        return "_masked_kernel"
    return PARTIALS.get(operands.count("%"))


def read(ctx):
    found = ctx.trace.matching(
        lambda e: kernel_of(e, ctx.plane_rows) is not None)
    if not any(found.values()):
        return None
    per_chip = sum(sum(e.dur for e in evs) for evs in found.values()) \
        / len(found)
    return 1e-6 * per_chip / ctx.window.steps
