"""Static flat-buffer layout: ONE padded ``(rows, 128)`` view of a pytree.

The comm plane's per-round quantities (trigger sqnorms, LAQ absmax +
encode, masked lazy updates) are all elementwise-or-reduce sweeps over
the gradient pytree.  Launching one Pallas kernel per leaf per worker
(the ``repro.kernels.lag_trigger.ops`` loops) costs L·M launches per
round; this module makes the batched alternative possible by fixing, at
trace time, a single flat layout every leaf scatters into.

Two granularities keep both padding waste and launch overhead small:

  * **sub-blocks** (``SUB_ROWS`` × ``LANES`` = 1024 elements, the f32
    tile): each leaf is flattened, cast to float32 and padded up to
    whole sub-blocks, so a sub-block never straddles two leaves —
    per-leaf quantities (the LAQ quantizer scale, the deterministic
    per-(worker, leaf-offset) partial sums) survive batching, and a
    63-element bias leaf wastes ≤ 1023 padded elements, not ≤ 32767;
  * **grid blocks** (``BLOCK_ROWS`` = 256 rows = ``SUBS_PER_BLOCK`` = 32
    sub-blocks): the kernel grid steps over these; the buffer tail is
    padded to a whole grid block, with ``sub_leaf`` mapping every
    sub-block to its leaf (tail sub-blocks map to leaf 0 — they are
    all-zero, which is absorbing for every plane op: x² sums, |v| maxes,
    quantize-to-zero, masked folds).

Leaves are concatenated in pytree order into one ``(rows, LANES)``
buffer (``(W, rows, LANES)`` for stacked per-worker trees); ``sub_leaf``
is the static leaf-offset table the batched kernels and the fixed-order
segment reductions consume.  Zero-size leaves occupy zero sub-blocks
and round-trip as empty arrays.

Every flatten and scatter runs under the round's ``lag.layout`` stage
(``repro.engine.rounds.STAGES``), also where a caller's stage is open.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any

LANES = 128
SUB_ROWS = 8                    # (8, 128) f32 tile — the leaf-padding unit
SUB = SUB_ROWS * LANES          # 1024 elements per sub-block
BLOCK_ROWS = 256                # rows per kernel grid step
SUBS_PER_BLOCK = BLOCK_ROWS // SUB_ROWS
BLOCK = BLOCK_ROWS * LANES      # elements per grid block

#: dtypes the flat plane serves; everything is computed in float32 and
#: scattered back at the leaf's own dtype (the jnp oracle's convention)
SUPPORTED_DTYPES = (jnp.float32, jnp.bfloat16, jnp.float16)


def _layout_stage(method):
    """Run ``method`` under the ``lag.layout`` scope; the name is one of
    ``repro.engine.rounds.STAGES``, spelled here because the engine
    imports this module."""
    @functools.wraps(method)
    def scoped(*args, **kwargs):
        with jax.named_scope("lag.layout"):
            return method(*args, **kwargs)
    return scoped


def tree_signature(tree: Pytree) -> Tuple:
    """Static (treedef, shapes, dtypes) key for layout caching."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (treedef,
            tuple(l.shape for l in leaves),
            tuple(jnp.dtype(l.dtype).name for l in leaves))


@dataclasses.dataclass(frozen=True)
class FlatLayout:
    """The static offset table for one pytree structure (unstacked)."""
    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]
    sizes: Tuple[int, ...]
    leaf_subs: Tuple[int, ...]         # sub-blocks per leaf (0 when empty)
    leaf_sub_offsets: Tuple[int, ...]
    nsubs: int                         # data sub-blocks (pre tail pad)
    nblocks: int                       # kernel grid blocks (tail padded)
    sub_leaf: np.ndarray               # (nblocks·SUBS_PER_BLOCK,) int32

    @property
    def rows(self) -> int:
        return self.nblocks * BLOCK_ROWS

    @property
    def num_leaves(self) -> int:
        return len(self.shapes)

    @classmethod
    def for_tree(cls, tree: Pytree) -> "FlatLayout":
        """Build the layout from an (unstacked) template tree."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        shapes = tuple(tuple(l.shape) for l in leaves)
        dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
        sizes = tuple(int(np.prod(s, dtype=np.int64)) for s in shapes)
        subs = tuple(-(-s // SUB) for s in sizes)       # ceil; 0 stays 0
        offsets, acc = [], 0
        for b in subs:
            offsets.append(acc)
            acc += b
        nblocks = -(-acc // SUBS_PER_BLOCK)
        sub_leaf = np.zeros((nblocks * SUBS_PER_BLOCK,), np.int32)
        sub_leaf[:acc] = np.repeat(np.arange(len(leaves), dtype=np.int32),
                                   np.asarray(subs, np.int64))
        # tail sub-blocks keep the leaf-0 default: all-zero data, so they
        # contribute nothing to any reduction or fold
        return cls(treedef=treedef, shapes=shapes, dtypes=dtypes,
                   sizes=sizes, leaf_subs=subs,
                   leaf_sub_offsets=tuple(offsets), nsubs=acc,
                   nblocks=nblocks, sub_leaf=sub_leaf)

    # -- flatten ------------------------------------------------------------

    def _flat_leaves(self, tree: Pytree, lead: Tuple[int, ...]):
        """Per-leaf ``lead + (leaf_rows, LANES)`` float32 segments, pytree
        order, plus the zero tail up to a whole grid block.  Segments are
        row-shaped so the concatenation is the buffer itself: a flat
        ``(…, size)`` concat reshaped to rows would be a second full-size
        copy on TPU, where the two shapes tile differently."""
        leaves = jax.tree_util.tree_leaves(tree)
        if len(leaves) != self.num_leaves:
            raise ValueError(f"tree has {len(leaves)} leaves, layout expects "
                             f"{self.num_leaves}")
        segs = []
        for l, size, subs in zip(leaves, self.sizes, self.leaf_subs):
            if subs == 0:
                continue
            flat = l.reshape(lead + (size,)).astype(jnp.float32)
            pad = subs * SUB - size
            if pad:
                flat = jnp.pad(flat, [(0, 0)] * len(lead) + [(0, pad)])
            segs.append(flat.reshape(lead + (subs * SUB_ROWS, LANES)))
        tail = self.nblocks * SUBS_PER_BLOCK - self.nsubs
        if tail:
            segs.append(jnp.zeros(lead + (tail * SUB_ROWS, LANES),
                                  jnp.float32))
        return segs

    @_layout_stage
    def flatten(self, tree: Pytree) -> jnp.ndarray:
        """Template-shaped tree → ``(rows, LANES)`` float32 buffer."""
        if self.nblocks == 0:
            return jnp.zeros((0, LANES), jnp.float32)
        return jnp.concatenate(self._flat_leaves(tree, ()), axis=0)

    @_layout_stage
    def flatten_stacked(self, tree: Pytree) -> jnp.ndarray:
        """Stacked ``(W, …leaf)`` tree → ``(W, rows, LANES)`` float32."""
        leaves = jax.tree_util.tree_leaves(tree)
        W = leaves[0].shape[0]
        if self.nblocks == 0:
            return jnp.zeros((W, 0, LANES), jnp.float32)
        return jnp.concatenate(self._flat_leaves(tree, (W,)), axis=1)

    # -- scatter back -------------------------------------------------------

    def _out_dtypes(self, like: Any):
        """Per-leaf scatter dtypes: the layout's own when ``like`` is None,
        a fixed dtype when ``like`` is one, else ``like``-tree leaf dtypes
        (e.g. a bf16 ``grad_hat`` mirror updated through the f32 plane)."""
        if like is None:
            return self.dtypes
        if isinstance(like, (str, jnp.dtype, type)) or hasattr(like, "name"):
            return (jnp.dtype(like),) * self.num_leaves
        return tuple(jnp.dtype(l.dtype)
                     for l in jax.tree_util.tree_leaves(like))

    def _leaf_from_rows(self, buf: jnp.ndarray, i: int,
                        lead: Tuple[int, ...], dtype) -> jnp.ndarray:
        """Leaf ``i`` out of a ``lead + (rows, LANES)`` buffer: slice its
        rows first, so only the leaf's own segment is reshaped."""
        shape = self.shapes[i]
        size, subs = self.sizes[i], self.leaf_subs[i]
        if subs == 0:
            return jnp.zeros(lead + shape, dtype)
        r0, nr = self.leaf_sub_offsets[i] * SUB_ROWS, subs * SUB_ROWS
        ax = len(lead)
        seg = jax.lax.slice_in_dim(buf, r0, r0 + nr, axis=ax)
        seg = jax.lax.slice_in_dim(seg.reshape(lead + (nr * LANES,)),
                                   0, size, axis=ax)
        return seg.reshape(lead + shape).astype(dtype)

    @_layout_stage
    def unflatten(self, buf: jnp.ndarray, like: Any = None) -> Pytree:
        """``(rows, LANES)`` buffer → template tree (leaf dtypes restored)."""
        dts = self._out_dtypes(like)
        leaves = [self._leaf_from_rows(buf, i, (), dts[i])
                  for i in range(self.num_leaves)]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    @_layout_stage
    def unflatten_stacked(self, buf: jnp.ndarray, like: Any = None) -> Pytree:
        """``(W, rows, LANES)`` buffer → stacked template tree."""
        W = buf.shape[0]
        dts = self._out_dtypes(like)
        leaves = [self._leaf_from_rows(buf, i, (W,), dts[i])
                  for i in range(self.num_leaves)]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    # -- compact per-client views (the fleet population substrate) -----------
    #
    # ``flatten_stacked`` pads every buffer to whole KERNEL GRID blocks
    # (BLOCK = 32768 elements) because the Pallas grid steps over them —
    # the right trade for k cohort-sized launches, ruinous for a
    # population mirror held for EVERY client (a 4-element convex leaf
    # would cost 128 KiB per client).  ``pack_stacked``/``unpack_stacked``
    # are the storage twins: same leaf order, same f32 convention, same
    # ``like=`` scatter-dtype contract, but each leaf pads only to the
    # LANES vector width and there is no grid tail — one ``(W,
    # packed_cols)`` array, gather/scatter-friendly along the client dim.

    @property
    def leaf_lanes(self) -> Tuple[int, ...]:
        """LANES-vectors per leaf in the packed view (0 for empty leaves)."""
        return tuple(-(-s // LANES) for s in self.sizes)

    @property
    def leaf_lane_offsets(self) -> Tuple[int, ...]:
        offs, acc = [], 0
        for n in self.leaf_lanes:
            offs.append(acc)
            acc += n
        return tuple(offs)

    @property
    def packed_cols(self) -> int:
        """Columns of the compact ``(W, packed_cols)`` per-client view."""
        return sum(self.leaf_lanes) * LANES

    @_layout_stage
    def pack_stacked(self, tree: Pytree) -> jnp.ndarray:
        """Stacked ``(W, …leaf)`` tree → compact ``(W, packed_cols)``
        float32 — per-leaf LANES padding only, no kernel-grid tail."""
        leaves = jax.tree_util.tree_leaves(tree)
        if len(leaves) != self.num_leaves:
            raise ValueError(f"tree has {len(leaves)} leaves, layout expects "
                             f"{self.num_leaves}")
        W = leaves[0].shape[0]
        segs = []
        for l, size, lanes in zip(leaves, self.sizes, self.leaf_lanes):
            if lanes == 0:
                continue
            flat = l.reshape((W, size)).astype(jnp.float32)
            pad = lanes * LANES - size
            if pad:
                flat = jnp.pad(flat, [(0, 0), (0, pad)])
            segs.append(flat)
        if not segs:
            return jnp.zeros((W, 0), jnp.float32)
        return jnp.concatenate(segs, axis=1)

    @_layout_stage
    def unpack_stacked(self, buf: jnp.ndarray, like: Any = None) -> Pytree:
        """Compact ``(W, packed_cols)`` buffer → stacked template tree."""
        W = buf.shape[0]
        dts = self._out_dtypes(like)
        offs = self.leaf_lane_offsets
        leaves = []
        for i in range(self.num_leaves):
            shape, size = self.shapes[i], self.sizes[i]
            if self.leaf_lanes[i] == 0:
                leaves.append(jnp.zeros((W,) + shape, dts[i]))
                continue
            off = offs[i] * LANES
            seg = jax.lax.slice_in_dim(buf, off, off + size, axis=1)
            leaves.append(seg.reshape((W,) + shape).astype(dts[i]))
        return jax.tree_util.tree_unflatten(self.treedef, leaves)
