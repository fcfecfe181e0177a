"""Static bucketization plan for the gradient-sync engine.

The seed ``sync_pytree`` Python-looped over buckets — every bucket traced its
own copy of the strategy pipeline (O(#buckets) HLO growth) after
materializing a second full-size gradient copy via concatenate-then-slice.
``BucketPlan`` replaces that with trace-time-static layout bookkeeping:

* built once from the pytree treedef + leaf shapes (hashable, so it can ride
  in jit static args or be cached by the trainer),
* ``pack`` lays the flat gradient stream into ONE batch (a single
  full-size buffer; the last bucket zero-padded): ``(B, R, L)`` when the
  plan has a lane width ``L`` dividing ``bucket_elems`` (bucket k is then a
  contiguous ``(R, L)`` slab of whole (8, 128) tiles), else the flat
  ``(B, bucket_elems)`` view,
* the engine then runs the strategy body once under ``lax.scan`` over the
  leading bucket axis (or vectorized via ``vmap``, or stage-skewed across
  buckets via ``mode="pipelined"`` — see ``allreduce.sync_packed``) — one
  traced pipeline regardless of B,
* ``unpack`` restores leaf shapes/dtypes from the synced batch.

Zero-padding the tail bucket is sound for every strategy: the pipelines are
elementwise across peers (pad positions sync to 0 and are sliced away), and
it is what makes the batched layout possible at all.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """Hashable leaf->bucket layout, computed once from treedef/shapes."""
    treedef: Any
    shapes: tuple[tuple[int, ...], ...]
    dtypes: tuple[str, ...]
    bucket_elems: int
    num_buckets: int
    lane: int = 0                     # L of the (B, R, L) arena; 0: (B, E)

    @classmethod
    def for_tree(cls, tree, bucket_elems: int,
                 lane: int = 0) -> "BucketPlan":
        """Plan from a pytree of arrays (or ShapeDtypeStructs).

        ``lane`` asks for the bucket-major ``(B, R, L)`` arena (the sync
        engine's :func:`~repro.core.pipeline.arena_lane`); it is kept only
        when it divides the bucket length, else the plan falls back to the
        flat ``(B, bucket_elems)`` view (e.g. a single bucket of odd
        length)."""
        leaves, treedef = jax.tree.flatten(tree)
        shapes = tuple(tuple(leaf.shape) for leaf in leaves)
        dtypes = tuple(jnp.dtype(leaf.dtype).name for leaf in leaves)
        total = sum(math.prod(s) for s in shapes)
        num_buckets = max(1, -(-total // bucket_elems))
        if num_buckets == 1:
            bucket_elems = total        # single bucket: no tail padding
        if lane <= 0 or bucket_elems % lane:
            lane = 0
        return cls(treedef=treedef, shapes=shapes, dtypes=dtypes,
                   bucket_elems=bucket_elems, num_buckets=num_buckets,
                   lane=lane)

    @property
    def sizes(self) -> tuple[int, ...]:
        return tuple(math.prod(s) for s in self.shapes)

    @property
    def total(self) -> int:
        return sum(self.sizes)

    @property
    def padded(self) -> int:
        return self.num_buckets * self.bucket_elems

    @property
    def arena_shape(self) -> tuple[int, ...]:
        """``(B, R, L)`` with a lane width, else ``(B, bucket_elems)``."""
        if self.lane:
            return (self.num_buckets, self.bucket_elems // self.lane,
                    self.lane)
        return (self.num_buckets, self.bucket_elems)

    @property
    def offsets(self) -> tuple[int, ...]:
        """Flat-stream start offset of each leaf (pytree order) — the arena
        coordinates consumers like ``packed_global_norm`` reduce over."""
        offs = []
        off = 0
        for size in self.sizes:
            offs.append(off)
            off += size
        return tuple(offs)

    def pack(self, tree, dtype=jnp.float32) -> jnp.ndarray:
        """Flatten leaves (pytree order) into one :attr:`arena_shape` batch
        — the engine's only full-size buffer.  ``dtype`` defaults to fp32 (the
        sync engine's wire dtype); the trainer's packed gradient arena packs
        micro-batch grads in ``accum_dtype`` and accumulates in packed space
        (the per-leaf cast-then-concatenate is elementwise identical to the
        seed per-leaf accumulator)."""
        leaves = jax.tree.leaves(tree)
        parts = [leaf.reshape(-1).astype(dtype) for leaf in leaves]
        pad = self.padded - self.total
        if pad:
            parts.append(jnp.zeros((pad,), dtype))
        flat = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        return flat.reshape(self.arena_shape)

    def leaf_view(self, batch: jnp.ndarray, off: int,
                  size: int) -> jnp.ndarray:
        """Flat ``[off, off + size)`` of the stream, read from the arena.

        The dense arena is never flattened whole: the range is cut from
        the rows it spans in the ``(B * R, L)`` row view (a free reshape of
        ``(B, R, L)``), so each leaf's relayout is its own size and can
        fuse into its consumer."""
        if not self.lane:
            return batch.reshape(-1)[off:off + size]
        lane = self.lane
        r0, r1 = off // lane, -(-(off + size) // lane)
        rows = batch.reshape(-1, lane)[r0:r1]
        start = off - r0 * lane
        return rows.reshape(-1)[start:start + size]

    def sum_squares(self, batch: jnp.ndarray, a: int,
                    b: int) -> jnp.ndarray:
        """fp32 sum of squares of the stream's ``[a, b)``, read in place:
        the dense arena's rows it spans, edges cut by a flat-index mask
        (no relayout of the arena)."""
        if not self.lane:
            seg = batch.reshape(-1)[a:b].astype(jnp.float32)
            return jnp.sum(jnp.square(seg))
        lane = self.lane
        r0, r1 = a // lane, -(-b // lane)
        rows = batch.reshape(-1, lane)[r0:r1].astype(jnp.float32)
        idx = ((r0 + jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0))
               * lane + jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1))
        inside = (idx >= a) & (idx < b)
        return jnp.sum(jnp.where(inside, jnp.square(rows), 0.0))

    def unpack(self, batch: jnp.ndarray):
        """Inverse of ``pack``: :attr:`arena_shape` -> original pytree."""
        leaves = [self.leaf_view(batch, off, size).reshape(shape)
                  .astype(dtype)
                  for shape, dtype, size, off in zip(
                      self.shapes, self.dtypes, self.sizes, self.offsets)]
        return jax.tree.unflatten(self.treedef, leaves)

    def bucket_keys(self, key: jax.Array) -> jax.Array:
        """Stacked per-bucket PRNG keys (see :func:`bucket_keys`)."""
        return bucket_keys(key, self.num_buckets)


def bucket_keys(key: jax.Array, num_buckets: int) -> jax.Array:
    """Stacked per-bucket PRNG keys: fold_in(key, bucket_index), the same
    derivation as the seed's Python loop — the single source of truth the
    bitwise parity of every sync engine schedule rests on."""
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(num_buckets, dtype=jnp.uint32))
