"""Operations and bytes from shapes: the yardstick for ``mfu`` and the
comm plane's roofline share.

Model FLOPs per training token are 6 x the matmul parameters (the head
counted, an embedding lookup not) plus causal attention's score and value
terms; SSD scan FLOPs and recomputation are not counted.  Each reference
module (``bench/reference/<name>.py``) states its own matmul parameter
count and attention term from the configuration's sizes.

The plane's bytes follow the flat layout of ``repro.fastpath.layout``,
worked out here again from the leaf sizes: each leaf pads to whole
1024-element sub-blocks (8 rows of 128 lanes), and the buffer to whole
256-row grid blocks.  A kernel reads and writes its ``(W, rows, 128)``
float32 slabs once, plus one float32 partial per (worker, sub-block) for
the reductions and per-sub-block quantizer steps for the LAQ encode.
"""
from __future__ import annotations

from typing import Iterable

LANES = 128
SUB_ROWS = 8
SUB = SUB_ROWS * LANES
BLOCK_ROWS = 256
SUBS_PER_BLOCK = BLOCK_ROWS // SUB_ROWS
F32 = 4

#: kernel name -> (slabs read, slabs written, per-sub-block arrays read,
#: per-sub-block arrays written), every slab (W, rows, 128) float32
KERNEL_IO = {
    "_sq_kernel": (1, 0, 0, 1),
    "_delta_sq_kernel": (2, 0, 0, 1),
    "_absmax_kernel": (3, 0, 0, 1),
    "_laq_kernel": (3, 2, 1, 1),
    "_masked_kernel": (2, 1, 0, 0),
}


def plane_rows(leaf_sizes: Iterable[int]) -> int:
    """Rows of one worker's flat buffer for leaves of these sizes."""
    subs = sum(-(-int(s) // SUB) for s in leaf_sizes)
    return -(-subs // SUBS_PER_BLOCK) * BLOCK_ROWS


def kernel_bytes(name: str, workers: int, rows: int) -> int:
    """HBM bytes one launch of plane kernel ``name`` must move over a
    ``(workers, rows, 128)`` float32 buffer.  The masked fold also reads
    one float32 mask entry per worker."""
    slabs_in, slabs_out, subs_in, subs_out = KERNEL_IO[name]
    slab = workers * rows * LANES * F32
    per_sub = workers * (rows // SUB_ROWS) * F32
    extra = workers * F32 if name == "_masked_kernel" else 0
    return ((slabs_in + slabs_out) * slab + (subs_in + subs_out) * per_sub
            + extra)


def flops_per_token(ref, model: dict, seq: int) -> float:
    """Training FLOPs per token: 6 x matmul parameters plus the reference
    module's attention term."""
    return 6.0 * ref.matmul_params(model) + ref.attention_flops(model, seq)
