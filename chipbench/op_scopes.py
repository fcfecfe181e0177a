"""The train step's layers in a device trace, by the program's own scope
names (``jax.named_scope`` with the names of ``repro.obs.scopes``).

Each operation of a device's ``XLA Ops`` line has event metadata, and in it
the ``tf_op`` stat: the JAX ``op_name`` path of the instruction it ran, e.g.
``jit(body)/fwd_bwd/transpose(jvp(fwd_bwd))/while/body/...``. The
profiler's Python reader does not expose metadata stats, so they are
decoded here from the ``.xplane.pb`` wire format.

An operation belongs to the innermost known scope on its path; a path
component names a scope bare or wrapped, as in ``transpose(jvp(fwd_bwd))``.
An operation with a path but no known scope (another program, such as the
benchmark's input maker; constants JAX builds outside autodiff) is
``unscoped``. Operations the compiler made have no ``tf_op`` at all
(layout copies, multi-output fusions, the writes of a loop's outputs,
``copy-done``), and a loop's own bookkeeping is busy time between its
operations: each takes the scope of the operation that ran just before it
on the device, which on one device is the computation it was made for,
except at the boundary of two layers. The layers: ``fwd_bwd``; ``sync``,
which holds the stages ``encode``, ``exchange`` and ``decode``;
``optimizer``.

Times are the clipped leaf operations that ``trace_reduce`` keeps for the
traced steps and the busy time between them, summed per scope and divided
by the traced steps: with ``unscoped`` they add up to the device's busy
time.
"""
from __future__ import annotations

import re
import sys

from chipbench import trace_reduce

SCOPES = ("fwd_bwd", "sync", "encode", "exchange", "decode", "optimizer")
LAYERS = {"fwd_bwd": ("fwd_bwd",),
          "sync": ("sync", "encode", "exchange", "decode"),
          "optimizer": ("optimizer",)}
UNSCOPED = "unscoped"

_COMPONENT = {s: re.compile(rf"(^|\(){s}(\)|$)") for s in SCOPES}


def scope_of(path: str | None) -> str:
    """The innermost known scope on an ``op_name`` path, else
    ``unscoped``."""
    for comp in reversed((path or "").split("/")):
        for s, pat in _COMPONENT.items():
            if pat.search(comp):
                return s
    return UNSCOPED


# ------------------------------------------------------ xplane wire format
# XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 (map), stat_metadata
# = 5 (map); map entry: key = 1, value = 2; XEventMetadata: name = 2, stats
# = 5; XStatMetadata: name = 2; XStat: metadata_id = 1, str_value = 5,
# ref_value = 7 (the id of a stat metadata whose name is the string).

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of a message: ints for varints, memoryviews
    for length-delimited fields; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")
        yield key >> 3, value


def _map_values(entries):
    for entry in entries:
        for f, v in _fields(entry):
            if f == 2:
                yield v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def read_tf_ops(path: str) -> dict[str, set]:
    """Event text -> the set of ``tf_op`` paths (None where an event has
    none) of the operations on ``/device:TPU:0``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    want = "/device:TPU:0"
    for field, plane in _fields(space):
        if field != 1:
            continue
        parts: dict[int, list] = {}
        for f, v in _fields(plane):
            parts.setdefault(f, []).append(v)
        if not parts.get(2) or _text(parts[2][0]) != want:
            continue
        stat_names = {}
        for meta in _map_values(parts.get(5, ())):
            d = dict(_fields(meta))
            stat_names[d.get(1, 0)] = _text(d.get(2, b""))
        tf_op_ids = {k for k, name in stat_names.items() if name == "tf_op"}
        out: dict[str, set] = {}
        for meta in _map_values(parts.get(4, ())):
            name, tf_op = "", None
            for f, v in _fields(meta):
                if f == 2:
                    name = _text(v)
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) in tf_op_ids:
                        tf_op = (_text(stat[5]) if 5 in stat
                                 else stat_names.get(stat.get(7)))
            out.setdefault(name, set()).add(tf_op)
        return out
    raise ValueError(f"{path}: no plane {want}")


# ------------------------------------------------------------- reduction
def split(red, tf_ops: dict[str, set]) -> dict[str, float]:
    """Device ns of the traced steps per scope and ``unscoped``. Also, as
    parts of those: ``compiler_made`` (operations without a ``tf_op``) and
    ``between_ops`` (busy time no leaf operation covers), both counted with
    the operation before them, and ``ambiguous`` (texts whose paths name
    different scopes; counted as unscoped)."""
    scope = {}
    for text, paths in tf_ops.items():
        if paths != {None}:
            scopes = {scope_of(p) for p in paths}
            scope[text] = scopes.pop() if len(scopes) == 1 else None
    out = dict.fromkeys(SCOPES + (UNSCOPED, "compiler_made", "between_ops",
                                  "ambiguous"), 0.0)
    leaves = sorted(red.devices[0], key=lambda e: e.start)
    owner, last = [], UNSCOPED
    for e in leaves:
        if e.name in scope:
            last = scope[e.name]
            if last is None:
                out["ambiguous"] += e.end - e.start
                last = UNSCOPED
        else:
            out["compiler_made"] += e.end - e.start
        out[last] += e.end - e.start
        owner.append(last)
    covered = trace_reduce.merge((e.start, e.end) for e in leaves)
    j = -1
    for lo, hi in trace_reduce.subtract(red.busy[0], covered):
        while j + 1 < len(leaves) and leaves[j + 1].end <= lo:
            j += 1
        out[owner[j] if j >= 0 else UNSCOPED] += hi - lo
        out["between_ops"] += hi - lo
    return out


_CACHE: dict = {}


def per_step_ms(run) -> dict[str, float] | None:
    """``split`` of the run's traced steps in ms per step, read once per
    trace and logged on standard error; None without a trace."""
    red = run.trace
    path = run.window.trace_file
    if red is None or path is None:
        return None
    if path not in _CACHE:
        tf_ops = read_tf_ops(path)
        mixed = [t for t, paths in tf_ops.items() if len(paths) > 1]
        if mixed:
            print(f"chipbench: op_scopes: {len(mixed)} operation texts with "
                  f"more than one op_name path, e.g. {mixed[0][:120]!r}",
                  file=sys.stderr)
        ns = split(red, tf_ops)
        ms = {k: v * 1e-6 / red.n_steps for k, v in ns.items()}
        busy = red.busy_ns(0) * 1e-6 / red.n_steps
        _log(ms, busy)
        _CACHE[path] = ms
    return _CACHE[path]


def _log(ms: dict, busy: float) -> None:
    sync = sum(ms[s] for s in LAYERS["sync"])
    print(f"chipbench: op_scopes ms/step of device 0's busy {busy!r}: "
          f"fwd_bwd {ms['fwd_bwd']!r}, sync {sync!r} (encode "
          f"{ms['encode']!r}, exchange {ms['exchange']!r}, decode "
          f"{ms['decode']!r}, outside the stages {ms['sync']!r}), "
          f"optimizer {ms['optimizer']!r}, unscoped {ms[UNSCOPED]!r} (of it "
          f"ambiguous {ms['ambiguous']!r}); counted with the operation "
          f"before them: compiler-made operations {ms['compiler_made']!r}, "
          f"time between a loop's operations {ms['between_ops']!r}",
          file=sys.stderr)


def layer_ms(run, layer: str) -> float | None:
    """Device ms per traced step of one layer (``LAYERS``), or None when no
    operation carries its scopes."""
    ms = per_step_ms(run)
    if ms is None:
        return None
    total = sum(ms[s] for s in LAYERS[layer])
    return total if total > 0 else None
