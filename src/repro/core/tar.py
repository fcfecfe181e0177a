"""Transpose AllReduce (TAR) — the paper's collective, on TPU axes (§3.1).

All functions run *inside* a ``jax.shard_map`` body; ``axis`` names a mesh
axis. A "bucket" is a flat per-device array that is identical (replicated in
value) across the axis before the call — i.e. each worker's local gradients.

Stage mapping (DESIGN §2):
  stage 1 (shard exchange, P2P)   -> jax.lax.all_to_all (tiled)
  reduce (colocated PS)           -> drop-compensated masked mean
  stage 2 (broadcast aggregated)  -> jax.lax.all_gather (tiled)

The round-based variant reproduces the paper's 2*ceil((N-1)/I) round schedule
with ``collective_permute`` so the lowered HLO carries the exact round
structure (used by the round/incast experiments); the all_to_all form is the
production path (XLA/ICI schedules it better — see EXPERIMENTS §Perf).

Hierarchical 2D TAR (§3.1.2) maps groups onto the ``pod`` axis: intra-pod TAR
reduce-scatter, inter-pod same-rank aggregation, intra-pod broadcast —
2(N/G-1) + (G-1) logical rounds.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.kernels.masked_sum import masked_mean as _masked_mean_kernel
from repro.kernels.masked_sum import masked_mean_ref


def pad_for_tar(x: jnp.ndarray, n: int, block: int = 1) -> tuple[jnp.ndarray, int]:
    """Pad flat x so len % (n * block) == 0 (block-aligned shards)."""
    length = x.shape[0]
    quantum = n * block
    pad = (-length) % quantum
    if pad:
        x = jnp.pad(x, (0, pad))
    return x, length


def tar_view(bucket: jnp.ndarray, n: int, block: int = 1, *,
             flat: bool = False) -> jnp.ndarray:
    """A bucket padded for an ``n``-shard TAR exchange, in the layout the
    sync engine moves it in.

    A tile-dense ``(rows, L)`` bucket (the ``(B, R, L)`` arena's slab) stays
    ``(rows', L)`` — rows zero-padded — when the padded shard length that
    :func:`pad_for_tar` gives (a multiple of ``n * block``) is whole rows
    and each row holds whole codec blocks (``block`` 1 or ``L``): shard j is
    then rows ``[j * rows'/n, (j+1) * rows'/n)``, the same elements as the
    flat cut, and never crosses the sublane axis. Otherwise — or with
    ``flat`` (weighted shards' unit rows) — the flat padded vector, as for
    a flat bucket."""
    if bucket.ndim == 2 and not flat:
        rows, lane = bucket.shape
        quantum = n * block
        padded = -(-(rows * lane) // quantum) * quantum
        if block in (1, lane) and (padded // n) % lane == 0:
            pad = padded // lane - rows
            return jnp.pad(bucket, ((0, pad), (0, 0))) if pad else bucket
    x, _ = pad_for_tar(bucket.reshape(-1), n, block)
    return x


def unpad_to(out: jnp.ndarray, shape) -> jnp.ndarray:
    """Cut a decoded, padded bucket (dense rows or flat) back to the
    bucket's own ``shape`` (an int for a flat bucket's length)."""
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    if len(shape) == 2 and out.ndim == 2 and out.shape[1] == shape[1]:
        return out[:shape[0]]
    return out.reshape(-1)[:math.prod(shape)].reshape(shape)


def shard_rows(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """Cut a padded bucket into its ``n`` TAR shards along axis 0:
    ``(n, S)`` flat, ``(n, S / L, L)`` tile-dense."""
    return x.reshape((n, x.shape[0] // n) + x.shape[1:])


def masked_mean(received: jnp.ndarray,
                mask: jnp.ndarray | None) -> jnp.ndarray:
    """Drop-compensated mean over the peer axis. received: (N, S), or the
    tile-dense (N, S / L, L) shard view.

    The public reduction every codec shares: no mask -> plain mean; with an
    arrival mask -> the masked compensated mean (kernels/masked_sum).
    """
    if mask is None:
        return jnp.mean(received, axis=0)
    return _masked_mean_kernel(received, mask)


def tar_reduce_scatter(x: jnp.ndarray, axis: str, *,
                       mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """TAR stage 1 + reduce: returns this node's aggregated shard (S,).

    x: flat (L,), L % N == 0, or tile-dense rows (N divides them). mask:
    (N, S) — which peers' packets arrived at *this* receiver (row self is
    always 1; see drops.make_mask).
    """
    n = jax.lax.axis_size(axis)
    shards = shard_rows(x, n)
    received = jax.lax.all_to_all(shards, axis, split_axis=0, concat_axis=0,
                                  tiled=True)          # (N, S): row p = peer p's shard for me
    return masked_mean(received, mask)


def tar_allreduce(x: jnp.ndarray, axis: str, *,
                  mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """Full TAR: all_to_all -> compensated reduce -> all_gather. (L,)->(L,)."""
    own = tar_reduce_scatter(x, axis, mask=mask)
    return jax.lax.all_gather(own, axis, axis=0, tiled=True)


def relay_via(src: int, dst: int, participants: Sequence[int],
              dead_links) -> int:
    """First participant that can relay src->dst around a dead edge.

    Both relay hops (src->m and m->dst) must themselves be live; raises
    when the dead-link set isolates the pair (the caller must eject one
    endpoint instead of rerouting).
    """
    dead = set(dead_links)
    for m in participants:
        if m in (src, dst):
            continue
        if (src, m) not in dead and (m, dst) not in dead:
            return m
    raise ValueError(f"no live relay for dead link {(src, dst)} "
                     f"among participants {tuple(participants)}")


def _grouped_rounds(axis: str, n: int, incast: int, send_for_round,
                    perm_for_round=None, dead_links=(), participants=None):
    """Run rounds 1..N-1 with <= incast permutes in flight per group.

    In round r (r = 1..N-1) node j sends to node (j+r) mod N and receives
    from (j-r) mod N — a round-robin schedule where a node-pair never
    repeats. ``incast`` is the paper's I: rounds are issued in groups of I
    permutes in flight concurrently, and group g+1's sends are gated on
    group g's arrivals (an ``optimization_barrier`` chain), so the lowered
    HLO carries the real ceil((N-1)/I) round schedule instead of one flat
    burst.  ``perm_for_round`` overrides the per-round permutation (the
    degraded-participation schedules route over a virtual ring of active
    peers; ``n`` is then the *virtual* ring size).

    ``dead_links`` is a set of directed (src, dst) edges that must not be
    used: any round whose permutation would traverse a dead edge has that
    pair removed from the main ppermute and replaced by a two-hop relay
    through a live intermediate (two extra single-pair ppermutes). The
    receiver's row is bit-identical either way — a ppermute destination
    not named receives zeros, so ``direct + relayed`` routes exactly the
    payload.
    """
    dead = {(int(s), int(d)) for (s, d) in dead_links}
    rows = []
    pending = []
    token = None
    for r in range(1, n):
        # node j sends to node (j + r) % n in round r
        if perm_for_round is None:
            perm = [(j, (j + r) % n) for j in range(n)]
        else:
            perm = perm_for_round(r)
        dead_pairs = [p for p in perm
                      if p[0] != p[1] and (p[0], p[1]) in dead]
        live = [p for p in perm if p not in dead_pairs] if dead_pairs else perm
        send = send_for_round(r)
        if token is not None:           # gate on the previous group's recvs
            send, token = jax.lax.optimization_barrier((send, token))
        recv = jax.lax.ppermute(send, axis, live)      # from (i - r) % n
        for (src, dst) in dead_pairs:
            m = relay_via(src, dst, participants
                          if participants is not None else range(n), dead)
            mid = jax.lax.ppermute(send, axis, [(src, m)])
            recv = recv + jax.lax.ppermute(mid, axis, [(m, dst)])
        pending.append(recv)
        if len(pending) == incast or r == n - 1:
            pending = list(jax.lax.optimization_barrier(tuple(pending)))
            rows.extend(pending)
            token = pending[-1]
            pending = []
    return rows


# ----------------------------------------------- degraded participation
def peer_lookup(active: tuple[int, ...], n: int):
    """Static lookup arrays for a degraded-participation set.

    Returns ``(vpos, is_active)``: ``vpos[p]`` is peer p's position on the
    virtual ring of active peers (0 for ejected peers — only ever read
    behind an ``is_active`` guard) and ``is_active[p]`` is 1.0/0.0.
    """
    vpos = [0] * n
    ind = [0.0] * n
    for k, p in enumerate(active):
        vpos[p] = k
        ind[p] = 1.0
    return jnp.asarray(vpos, jnp.int32), jnp.asarray(ind, jnp.float32)


def _ring_perms(active: tuple[int, ...], n: int):
    """perm_for_round over the active virtual ring: active peer at position
    j sends to position (j+r) % A; ejected peers self-loop (their sends
    never enter the schedule)."""
    a = len(active)
    ejected = [p for p in range(n) if p not in set(active)]

    def perm_for_round(r: int):
        return ([(active[j], active[(j + r) % a]) for j in range(a)]
                + [(e, e) for e in ejected])
    return perm_for_round


# ------------------------------------------- weighted (non-uniform) shards
class ShardPlan(NamedTuple):
    """Contiguous block-aligned ownership of a padded bucket.

    ``sizes[k]``/``offsets[k]`` describe the slice owned by virtual-ring
    position k; ``padded`` is the bucket length the plan covers and
    ``s_max`` the widest slice (the static row width every round moves —
    narrower slices ride zero-padded so the scanned strategy body stays
    static per policy).
    """
    sizes: tuple[int, ...]
    offsets: tuple[int, ...]
    padded: int
    s_max: int


def shard_plan(length: int, weights: Sequence[int], block: int = 1) -> ShardPlan:
    """Cut a bucket into straggler-proportional contiguous shards.

    ``weights`` are positive integer shard units, one per virtual-ring
    position (a slow-but-alive peer gets fewer units, fast peers absorb
    the remainder).  ``length`` is padded up to a multiple of
    ``sum(weights) * block`` — exactly what ``pad_for_tar(x, sum(weights),
    block)`` produces — so every slice is ``w_k * unit`` elements with
    ``unit`` a multiple of ``block``: every element is owned by exactly
    one position and codec blocks never straddle an ownership boundary.
    """
    ws = tuple(int(w) for w in weights)
    if not ws or any(w < 1 for w in ws):
        raise ValueError(f"shard weights must be positive integers, got {weights}")
    total = sum(ws)
    quantum = total * block
    padded = length + ((-length) % quantum)
    unit = padded // total
    sizes = tuple(w * unit for w in ws)
    offsets = []
    off = 0
    for s in sizes:
        offsets.append(off)
        off += s
    return ShardPlan(sizes, tuple(offsets), padded, max(sizes))


def weighted_rows(x: jnp.ndarray, plan: ShardPlan) -> jnp.ndarray:
    """(padded,) flat bucket -> (A, s_max) row matrix; row k is the slice
    owned by virtual position k, zero-padded to the static row width."""
    rows = []
    for size, off in zip(plan.sizes, plan.offsets):
        row = x[off:off + size]
        if size < plan.s_max:
            row = jnp.pad(row, (0, plan.s_max - size))
        rows.append(row)
    return jnp.stack(rows)


def weighted_flat(rows: jnp.ndarray, plan: ShardPlan) -> jnp.ndarray:
    """(A, s_max) row matrix -> (padded,) flat bucket: the inverse of
    :func:`weighted_rows` (zero-pad tails are dropped)."""
    return jnp.concatenate([rows[k, :size]
                            for k, size in enumerate(plan.sizes)])


def ring_order(active: tuple[int, ...], dead_links) -> tuple[int, ...]:
    """Link-avoiding virtual-ring order.

    Returns a permutation of ``active`` in which no consecutive hop
    (including the wrap-around) traverses a dead directed edge — a failed
    (i -> j) edge reroutes the virtual ring around the edge instead of
    ejecting j.  When no dead edge touches consecutive active pairs the
    order is ``tuple(active)`` unchanged (the bitwise-parity fast path).
    Raises ValueError when the dead set leaves no Hamiltonian cycle (the
    caller must fall back to ejection).
    """
    act = tuple(active)
    a = len(act)
    if a <= 1:
        return act
    members = set(act)
    dead = {(int(s), int(d)) for (s, d) in dead_links
            if int(s) in members and int(d) in members}
    if not dead:
        return act
    hops = {(act[j], act[(j + 1) % a]) for j in range(a)}
    if not (hops & dead):
        return act
    # depth-first search for a Hamiltonian cycle avoiding the dead edges
    start = act[0]
    order = [start]
    rest = set(act) - {start}

    def extend() -> bool:
        if not rest:
            return (order[-1], start) not in dead
        cur = order[-1]
        for p in sorted(rest):
            if (cur, p) in dead:
                continue
            order.append(p)
            rest.discard(p)
            if extend():
                return True
            order.pop()
            rest.add(p)
        return False

    if not extend():
        raise ValueError(f"no dead-link-avoiding ring order for "
                         f"active={act} dead={sorted(dead)}")
    return tuple(order)


def graft_inactive(full: jnp.ndarray, axis: str,
                   active: tuple[int, ...]) -> jnp.ndarray:
    """Deliver the assembled result to ejected peers.

    A degraded schedule assembles the full reduced bucket only on active
    peers; ejected peers must still *receive* it (they keep training — that
    is what makes probationary readmission a policy flip instead of a
    checkpoint restore).  ``ceil(E/A)`` extra graft rounds pair each ejected
    peer with an active sender (a ppermute destination not named receives
    zeros, so summing the rounds routes each peer exactly its copy), and a
    final select keeps active peers' locally-assembled bytes.
    """
    n = jax.lax.axis_size(axis)
    ejected = [p for p in range(n) if p not in set(active)]
    if not ejected:
        return full
    a = len(active)
    _, is_active = peer_lookup(active, n)
    got = jnp.zeros_like(full)
    for t in range(0, len(ejected), a):
        pairs = [(active[j], e) for j, e in enumerate(ejected[t:t + a])]
        got = got + jax.lax.ppermute(full, axis, pairs)
    keep = jnp.take(is_active, jax.lax.axis_index(axis))
    return jnp.where(keep > 0.5, full, got)


def _sender_order(i: jnp.ndarray, n: int) -> jnp.ndarray:
    # row r of a by-distance stack came from (i - r) % n
    return (i - jnp.arange(n)) % n


def tar_exchange_rounds(shards: jnp.ndarray, axis: str, *, incast: int = 1,
                        active: tuple[int, ...] | None = None,
                        dead_links=()) -> jnp.ndarray:
    """Stage-1 shard exchange on the explicit round schedule (Fig 5b).

    shards: (N, S), row j = this node's contribution to peer j's shard (or
    the tile-dense (N, S/L, L)). Returns the (N, S) received matrix in *sender* order (row p = peer p's
    shard for me) — the same layout the tiled all_to_all form produces.

    With a degraded-participation set ``active`` (a proper subset of the
    axis), the schedule is generated over the *virtual ring of active
    peers*: shards has A = len(active) rows (virtual position k's shard),
    rounds run r = 1..A-1, ejected peers self-loop (they neither contribute
    nor are waited on), and the returned (A, S) matrix is in virtual-sender
    order.  Ejected peers execute the same program on garbage rows; their
    result is replaced by :func:`graft_inactive` after stage 2.

    Non-uniform (weighted) shards are expressed entirely in the row
    matrix: build ``shards`` with :func:`weighted_rows` over a
    :func:`shard_plan` (rows zero-padded to the static width) and pass
    ``active`` explicitly — the schedule itself is weight-agnostic.
    ``dead_links`` reroutes any round traversing a failed directed edge
    through a two-hop relay (see :func:`_grouped_rounds`).
    """
    n = jax.lax.axis_size(axis)
    incast = max(1, int(incast))
    if active is None:
        i = jax.lax.axis_index(axis)
        own_rows = [jnp.take(shards, i, axis=0)]       # my own contribution
        own_rows += _grouped_rounds(axis, n, incast,
                                    lambda r: jnp.take(shards, (i + r) % n,
                                                       axis=0),
                                    dead_links=dead_links)
        # rows arrive ordered by sender distance r; reorder to sender index
        received_by_dist = jnp.stack(own_rows)         # row r = from (i-r)%n
        senders = _sender_order(i, n)
        return jnp.zeros_like(received_by_dist).at[senders] \
                  .set(received_by_dist)
    a = len(active)
    vpos, _ = peer_lookup(active, n)
    k = jnp.take(vpos, jax.lax.axis_index(axis))       # my virtual position
    own_rows = [jnp.take(shards, k, axis=0)]
    if a > 1:
        own_rows += _grouped_rounds(
            axis, a, incast,
            lambda r: jnp.take(shards, (k + r) % a, axis=0),
            perm_for_round=_ring_perms(active, n),
            dead_links=dead_links, participants=active)
    received_by_dist = jnp.stack(own_rows)             # row r = virt (k-r)%A
    senders = (k - jnp.arange(a)) % a
    return jnp.zeros_like(received_by_dist).at[senders].set(received_by_dist)


def tar_broadcast_rounds(own: jnp.ndarray, axis: str, *, incast: int = 1,
                         active: tuple[int, ...] | None = None,
                         dead_links=(),
                         plan: ShardPlan | None = None) -> jnp.ndarray:
    """Stage-2 broadcast of the aggregated shard, mirrored round schedule.

    own: (S,) this node's aggregated shard (or its tile-dense (S/L, L)
    rows). Returns the reassembled flat (N*S,) bucket (or (N*S/L, L)) —
    the same layout the tiled all_gather form produces.
    With ``active`` set, the mirror of the degraded exchange: A-1 rounds on
    the virtual ring assembling the flat (A*S,) bucket on active peers
    (virtual-position order); route it to ejected peers afterwards with
    :func:`graft_inactive`.  With a weighted ``plan``, ``own`` is the
    zero-padded (s_max,) row and the reassembly concatenates each
    position's valid slice (:func:`weighted_flat`) instead of reshaping.
    """
    n = jax.lax.axis_size(axis)
    incast = max(1, int(incast))
    if active is None:
        i = jax.lax.axis_index(axis)
        out_rows = [own]
        out_rows += _grouped_rounds(axis, n, incast, lambda r: own,
                                    dead_links=dead_links)
        got_by_dist = jnp.stack(out_rows)              # row r = shard of (i-r)%n
        senders = _sender_order(i, n)
        out = jnp.zeros_like(got_by_dist).at[senders].set(got_by_dist)
        if plan is not None:
            return weighted_flat(out, plan)
        return out.reshape((n * own.shape[0],) + own.shape[1:])
    a = len(active)
    vpos, _ = peer_lookup(active, n)
    k = jnp.take(vpos, jax.lax.axis_index(axis))
    out_rows = [own]
    if a > 1:
        out_rows += _grouped_rounds(axis, a, incast, lambda r: own,
                                    perm_for_round=_ring_perms(active, n),
                                    dead_links=dead_links,
                                    participants=active)
    got_by_dist = jnp.stack(out_rows)                  # row r = virt (k-r)%A
    senders = (k - jnp.arange(a)) % a
    out = jnp.zeros_like(got_by_dist).at[senders].set(got_by_dist)
    if plan is not None:
        return weighted_flat(out, plan)
    return out.reshape((a * own.shape[0],) + own.shape[1:])


def tar_allreduce_rounds(x: jnp.ndarray, axis: str, *, incast: int = 1,
                         mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """Round-structured TAR via collective_permute (paper Fig 5b):
    exchange -> compensated mean -> mirrored broadcast, 2*ceil((N-1)/I)
    rounds total.  The composable pipeline reaches the two stages directly
    (:func:`tar_exchange_rounds` / :func:`tar_broadcast_rounds`) so codecs
    can interpose; this wrapper is the plain value-domain form.
    """
    n = jax.lax.axis_size(axis)
    s = x.shape[0] // n
    received = tar_exchange_rounds(x.reshape(n, s), axis, incast=incast)
    if mask is None:
        own = jnp.mean(received, axis=0)
    else:
        own = masked_mean_ref(received, mask)
    return tar_broadcast_rounds(own, axis, incast=incast)


def tar_allreduce_2d(x: jnp.ndarray, inner_axis: str, outer_axis: str, *,
                     mask: jnp.ndarray | None = None,
                     outer_mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """Hierarchical 2D TAR (§3.1.2 / App. A): groups = pods.

    1. intra-group: TAR reduce-scatter over ``inner_axis``  (N/G - 1 rounds)
    2. inter-group: same-rank aggregation over ``outer_axis``  (G - 1 rounds)
    3. intra-group broadcast over ``inner_axis``            (N/G - 1 rounds)
    """
    own = tar_reduce_scatter(x, inner_axis, mask=mask)
    g = jax.lax.axis_size(outer_axis)
    if g > 1:
        s = own.shape[0]
        if s % g == 0:
            # TAR across pods too: shard my shard over the outer axis.
            own = tar_allreduce(own, outer_axis, mask=outer_mask)
        else:
            own = jax.lax.pmean(own, outer_axis)
    return jax.lax.all_gather(own, inner_axis, axis=0, tiled=True)
