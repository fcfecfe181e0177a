"""Reduces a profiler trace (``.xplane.pb``) of the traced steps.

From the device planes (``/device:TPU:<i>``, line ``XLA Ops``) it keeps each
operation's name and interval; from the host plane the benchmark's own
annotations: ``train`` (one per step, ``StepTraceAnnotation``) and inside
it ``batch``, ``dispatch`` and ``host_wait``. The traced window runs from
the first step's start to the last step's end, on the host clock that the
profiler puts the device events on too. Everything is clipped to it.

What the metric readers take from here:

* busy time: the union of a device's operation intervals;
* time of operations matched by name (kernels, collectives), and the part
  of the collectives' intervals in which no other operation runs on that
  device (exposed);
* idle gaps, each put down to the host annotation that overlaps it most.
"""
from __future__ import annotations

import dataclasses
import re

HOST_SPANS = ("train", "batch", "dispatch", "host_wait")
COLLECTIVE = re.compile(r"all-to-all|all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|ragged-all-to-all")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float          # ns
    end: float


def merge(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals as sorted disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """Parts of disjoint sorted intervals ``a`` not covered by ``b``."""
    b = merge(b)
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _clip(events, lo, hi) -> list[Event]:
    return [Event(ev.name, max(ev.start, lo), min(ev.end, hi))
            for ev in events if ev.end > lo and ev.start < hi]


@dataclasses.dataclass
class Reduced:
    window: tuple[float, float]
    n_steps: int
    devices: list          # per device: leaf operations (Events), clipped
    busy: list             # per device: merged intervals of all operations
    host: list             # list[Event] of the benchmark's annotations

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_ns(self, dev: int) -> float:
        return length(self.busy[dev])

    def busy_s(self) -> float:
        """Busy seconds averaged over the traced devices."""
        return sum(map(self.busy_ns, range(len(self.devices)))) * 1e-9 \
            / len(self.devices)

    def fullest(self) -> int:
        return max(range(len(self.devices)), key=self.busy_ns)

    def matched(self, dev: int, pattern) -> list[Event]:
        pat = re.compile(pattern) if isinstance(pattern, str) else pattern
        return [e for e in self.devices[dev] if pat.search(e.name)]

    def op_ns(self, dev: int, pattern) -> float:
        return length(merge((e.start, e.end)
                            for e in self.matched(dev, pattern)))

    def exposed_ns(self, dev: int, pattern) -> float:
        """Time in ``pattern``'s intervals with no other operation
        running on the device."""
        pat = re.compile(pattern) if isinstance(pattern, str) else pattern
        mine = merge((e.start, e.end) for e in self.devices[dev]
                     if pat.search(e.name))
        others = [(e.start, e.end) for e in self.devices[dev]
                  if not pat.search(e.name)]
        return length(subtract(mine, others))

    def idle_gaps(self, dev: int) -> list[tuple[str, float]]:
        """Idle intervals of the device, longest first, each named by the
        host annotation (other than ``train``) that overlaps it most."""
        gaps = subtract([self.window], self.busy[dev])
        spans = [h for h in self.host if h.name != "train"]
        out = []
        for s, e in gaps:
            best, label = 0.0, "none"
            for h in spans:
                ov = min(e, h.end) - max(s, h.start)
                if ov > best:
                    best, label = ov, h.name
            out.append((label, (e - s) * 1e-9))
        return sorted(out, key=lambda g: -g[1])


def op_name(text: str) -> str:
    """An operation's name without its HLO text (``%fusion.12 = ...``)."""
    return text.split(" = ", 1)[0]


def leaves(events) -> list[Event]:
    """Operations that contain no other: the ``XLA Ops`` line nests a
    loop's body operations inside the loop's own event."""
    evs = sorted(events, key=lambda e: (e.start, -e.end))
    out = []
    for i, e in enumerate(evs):
        j = i + 1
        while j < len(evs) and evs[j].start < e.end \
                and evs[j].end > e.end:
            j += 1
        if not (j < len(evs) and evs[j].start < e.end):
            out.append(e)
    return out


def from_events(devices, host) -> Reduced:
    """Reduce already-read events: ``devices`` a list (one per device) of
    Events of operations, ``host`` the Events of the host annotations.

    Starting and stopping the profiler disturbs the steps at the edges of
    the traced part (a device not traced yet; a host wait that outlasts
    the device by seconds while the trace is drained). Edge steps that
    are busy for under half the median step's busy time, or last more
    than 1.5 times the median step, are dropped."""
    busy_all = [merge((e.start, e.end) for e in d) for d in devices]
    steps = sorted((h for h in host if h.name == "train"),
                   key=lambda h: h.start)
    if not steps:
        raise ValueError("no 'train' step annotations in the trace")

    def step_busy(h):
        return length(subtract([(h.start, h.end)],
                               subtract([(h.start, h.end)], busy_all[0])))

    typical_busy = sorted(map(step_busy, steps))[len(steps) // 2]
    typical_len = sorted(h.end - h.start for h in steps)[len(steps) // 2]

    def odd(h):
        return (step_busy(h) < 0.5 * typical_busy
                or h.end - h.start > 1.5 * typical_len)

    while steps and odd(steps[0]):
        steps.pop(0)
    while steps and odd(steps[-1]):
        steps.pop()
    if not steps or typical_busy <= 0:
        raise ValueError("no traced step with device operations")
    lo, hi = steps[0].start, max(h.end for h in steps)
    return Reduced(window=(lo, hi), n_steps=len(steps),
                   devices=[_clip(leaves(d), lo, hi) for d in devices],
                   busy=[[(max(s, lo), min(e, hi)) for s, e in b
                          if e > lo and s < hi] for b in busy_all],
                   host=_clip(host, lo, hi))


def read_xplane(path: str, n_devices: int):
    """(devices, host) events of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    by_index, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend(Event(e.name, e.start_ns, e.end_ns)
                               for e in line.events)
            by_index[int(m.group(1))] = evs
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.end_ns)
                            for e in line.events if e.name in HOST_SPANS)
    if len(by_index) < n_devices:
        raise ValueError(f"trace has {len(by_index)} TPU planes, expected "
                         f"{n_devices}")
    return [by_index[i] for i in sorted(by_index)[:n_devices]], host


def load(path: str, n_devices: int) -> Reduced:
    return from_events(*read_xplane(path, n_devices))


def breakdown(red: Reduced, top: int = 10) -> dict:
    """Top device operations by time and the longest idle gaps, on the
    fullest device, in seconds."""
    dev = red.fullest()
    per_op: dict[str, float] = {}
    for e in red.devices[dev]:
        name = op_name(e.name)
        per_op[name] = per_op.get(name, 0.0) + (e.end - e.start) * 1e-9
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in red.idle_gaps(dev)[:top]]}
