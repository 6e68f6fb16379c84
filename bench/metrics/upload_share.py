"""upload_share: uploads over workers x steps in the window, from the
steps' upload masks, in %.  Moves tokens_per_s; layer: comm policy."""


def read(ctx):
    masks = ctx.window.masks
    total = sum(len(m) for m in masks)
    if not total:
        return None
    return 100.0 * sum(sum(m) for m in masks) / total
