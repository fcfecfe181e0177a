"""Baseline collectives the paper compares against (§5.1.2), as real
``shard_map`` collectives: Gloo Ring, recursive halving-doubling ("NCCL
Tree" stand-in), BCube, and plain psum (XLA's native choice).

The ring implementation also supports per-hop drop masks so the loss-
propagation pathology of Ring (accumulated partial sums lost in one hop,
§5.3 MSE microbenchmark) is reproduced in the actual dataflow.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def psum_mean(x: jnp.ndarray, axis) -> jnp.ndarray:
    return jax.lax.pmean(x, axis)


def ring_allreduce(x: jnp.ndarray, axis: str, *,
                   hop_masks: jnp.ndarray | None = None,
                   active: tuple[int, ...] | None = None,
                   weights: tuple[int, ...] | None = None) -> jnp.ndarray:
    """Bandwidth-optimal ring allreduce (Patarasuk-Yuan): N-1 reduce-scatter
    hops + N-1 all-gather hops over a fixed ring i -> i+1.

    x: flat (L,), L % N == 0. hop_masks: (2N-2, S) 0/1 — what survived each
    hop *into this node* (1 everywhere = lossless). A dropped hop loses the
    accumulated partial sum, which is exactly Ring's pathology.

    With a degraded-participation set ``active`` the ring is the *virtual
    ring of active peers* **in the order given** — callers route around
    failed links by passing a ``tar.ring_order``-ed tuple, since only
    consecutive (distance-1) hops are ever used: A chunks, 2(A-1) hops,
    mean over A contributions; ejected peers self-loop (their partial sums
    never enter the ring) and their garbage result must be replaced via
    ``tar.graft_inactive`` by the caller.  ``hop_masks`` then indexes the
    2(A-1) virtual hops.

    ``weights`` (positive shard units per virtual position, len A) makes
    chunk ownership straggler-proportional: x must be pre-padded to a
    multiple of ``sum(weights)`` and is cut by ``tar.shard_plan`` into
    contiguous slices that ride the ring zero-padded to the widest slice.
    """
    n = jax.lax.axis_size(axis)
    if active is None and weights is not None:
        active = tuple(range(n))
    if active is None:
        ring_n, k = n, jax.lax.axis_index(axis)
        perm = [(j, (j + 1) % n) for j in range(n)]
    else:
        from .tar import _ring_perms, peer_lookup
        ring_n = len(active)
        vpos, _ = peer_lookup(active, n)
        k = jnp.take(vpos, jax.lax.axis_index(axis))
        perm = _ring_perms(active, n)(1)
    if weights is not None:
        from .tar import shard_plan, weighted_flat, weighted_rows
        if len(weights) != ring_n:
            raise ValueError(f"weights {weights} do not match ring size {ring_n}")
        plan = shard_plan(x.shape[0], weights)
        if plan.padded != x.shape[0]:
            raise ValueError(f"bucket length {x.shape[0]} not a multiple of "
                             f"sum(weights)={sum(weights)}")
        chunks = weighted_rows(x, plan)
        s = plan.s_max
    else:
        plan = None
        s = x.shape[0] // ring_n
        chunks = x.reshape(ring_n, s)

    acc = chunks  # acc[c] = running partial sum of chunk c held at this node
    # reduce-scatter: after N-1 hops, node k owns the full sum of chunk (k+1)%n
    for h in range(ring_n - 1):
        send = jnp.take(acc, (k - h) % ring_n, axis=0)
        recv = jax.lax.ppermute(send, axis, perm)
        m = hop_masks[h] if hop_masks is not None else 1.0
        acc = acc.at[(k - h - 1) % ring_n].add(recv * m)
    own_idx = (k + 1) % ring_n
    own = jnp.take(acc, own_idx, axis=0) / ring_n

    # all-gather ring
    out = jnp.zeros_like(chunks).at[own_idx].set(own)
    cur = own
    for h in range(ring_n - 1):
        recv = jax.lax.ppermute(cur, axis, perm)
        m = hop_masks[ring_n - 1 + h] if hop_masks is not None else 1.0
        cur = recv * m
        out = out.at[(k - h) % ring_n].set(cur)
    if plan is not None:
        return weighted_flat(out, plan)
    return out.reshape(ring_n * s)


def tree_allreduce(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Recursive halving-doubling (the classic log-round tree allreduce;
    stands in for NCCL Tree): log2 N reduce-scatter + log2 N all-gather.
    After halving, node i owns segment i; doubling reassembles in order."""
    n = jax.lax.axis_size(axis)
    if n & (n - 1):
        return jax.lax.pmean(x, axis)
    i = jax.lax.axis_index(axis)
    buf = x
    d = n // 2
    while d >= 1:
        perm = [(j, j ^ d) for j in range(n)]
        half = buf.shape[0] // 2
        lo, hi = buf[:half], buf[half:]
        in_upper = (i & d) != 0
        mine = jnp.where(in_upper, hi, lo)      # half I keep reducing
        theirs = jnp.where(in_upper, lo, hi)    # half the partner owns
        recv = jax.lax.ppermute(theirs, axis, perm)
        buf = mine + recv
        d //= 2
    own = buf / n                               # (L/N,) segment i
    d = 1
    while d < n:
        perm = [(j, j ^ d) for j in range(n)]
        recv = jax.lax.ppermute(own, axis, perm)
        in_upper = (i & d) != 0
        own = jnp.where(in_upper,
                        jnp.concatenate([recv, own]),
                        jnp.concatenate([own, recv]))
        d *= 2
    return own


def bcube_allreduce(x: jnp.ndarray, axis: str, *, base: int = 4) -> jnp.ndarray:
    """Gloo-style BCube: k = log_base(N) stages. In each reduce stage, the
    ``base`` peers of a group (nodes differing only in one base-``base``
    digit) split their buffer into ``base`` parts and exchange so each
    member reduces the part matching its digit; the all-gather phase
    mirrors the stages in reverse. base=2 == recursive halving-doubling.
    """
    n = jax.lax.axis_size(axis)
    k, m = 0, n
    while m > 1:
        if m % base:
            return jax.lax.pmean(x, axis)       # N not a power of base
        m //= base
        k += 1
    i = jax.lax.axis_index(axis)
    buf = x
    strides = [base ** t for t in range(k)]

    def group_perm(stride: int, o: int) -> list[tuple[int, int]]:
        # every node j sends to the group member whose digit is digit(j)+o
        out = []
        for j in range(n):
            dj = (j // stride) % base
            out.append((j, j + ((((dj + o) % base) - dj) * stride)))
        return out

    for stride in strides:                       # reduce-scatter stages
        digit = (i // stride) % base
        parts = buf.reshape(base, -1)
        acc = jnp.take(parts, digit, axis=0)     # my digit's part, own contrib
        for o in range(1, base):
            send = jnp.take(parts, (digit + o) % base, axis=0)
            recv = jax.lax.ppermute(send, axis, group_perm(stride, o))
            acc = acc + recv                     # sender's part for my digit
        buf = acc
    own = buf / n

    for stride in reversed(strides):             # all-gather stages (mirror)
        digit = (i // stride) % base
        rows = [own]
        for o in range(1, base):
            rows.append(jax.lax.ppermute(own, axis, group_perm(stride, o)))
        stacked = jnp.stack(rows)                # row o = chunk of digit-(o) peer
        offs = (digit - jnp.arange(base)) % base # row o belongs at digit-o
        ordered = jnp.zeros_like(stacked).at[offs].set(stacked)
        own = ordered.reshape(-1)
    return own
