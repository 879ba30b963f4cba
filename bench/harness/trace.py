"""Reduction of a ``jax.profiler`` trace to device busy time, program
times and idle gaps attributed to host spans.

The trace is read with ``jax.profiler.ProfileData`` (the ``.xplane.pb``
the profiler writes).  Devices are the planes ``/device:TPU:<n>``; on each,
the line ``XLA Ops`` holds one event per operation run and ``XLA Modules``
one per program run.  Host spans are ``TraceAnnotation`` events on the
host plane named with one of ``HOST_PREFIXES``: the bench's own
(``bench.*``) and the program's (``engine.*``); ``bench.window`` marks the
traced window.  Times are in nanoseconds.

The device's clock runs early against the host's (by ~1.2 ms on a v5e):
a program shows as started before the host launched it.  Where the host's
launches (``PJRT_LoadedExecutable_Execute``) and the device's program runs
pair up one to one, the device's events are moved later by the least shift
that puts no run before its launch; otherwise they are left as recorded
and ``Device.shift_ns`` is None.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

__all__ = ["Event", "Trace", "load", "find_xplane", "union_ns", "busy_s",
           "module_seconds", "scope_seconds", "idle_gaps", "gaps_by_host",
           "top_ops"]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PREFIXES = ("bench.", "engine.")
WINDOW_SPAN = "bench.window"


@dataclass(frozen=True)
class Event:
    name: str
    start: float  # ns
    end: float    # ns


LAUNCH = "PJRT_LoadedExecutable_Execute"


@dataclass
class Device:
    ops: list[Event] = field(default_factory=list)
    modules: list[Event] = field(default_factory=list)
    shift_ns: float | None = None

    def shifted(self, d: float) -> "Device":
        def mv(events):
            return [Event(e.name, e.start + d, e.end + d) for e in events]

        return Device(mv(self.ops), mv(self.modules), d)


@dataclass
class Trace:
    devices: list[Device]
    host: list[Event]
    window: tuple[float, float]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _events(line) -> list[Event]:
    return [Event(_short(e.name), float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def _short(name: str) -> str:
    """An operation's name without its HLO text: ``%fusion.3 = bf16[..] ..``
    is ``fusion.3``; other names are kept."""
    return name.split(" = ", 1)[0].lstrip("%") if " = " in name else name


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host, launches = [], [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = Device()
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops = _events(line)
                elif line.name == "XLA Modules":
                    dev.modules = _events(line)
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = _events(line)
                host += [e for e in evs if e.name.startswith(HOST_PREFIXES)]
                launches += [e.start for e in evs if e.name == LAUNCH]
    host.sort(key=lambda e: e.start)
    if len(devices) == 1:
        starts = sorted(e.start for e in devices[0].modules)
        if starts and len(starts) == len(launches):
            shift = max(0.0, max(h - d for h, d in zip(sorted(launches), starts)))
            devices = [devices[0].shifted(shift)]
    win = [e for e in host if e.name == WINDOW_SPAN]
    if win:
        window = (win[0].start, win[-1].end)
    else:  # no marked window: the span of everything recorded
        every = host + [e for d in devices for e in d.ops + d.modules]
        window = (min((e.start for e in every), default=0.0),
                  max((e.end for e in every), default=0.0))
    return Trace(devices, [e for e in host if e.name != WINDOW_SPAN], window)


def _merged(events: list[Event], t0: float, t1: float) -> list[tuple[float, float]]:
    """Union of the events' intervals, clipped to [t0, t1], in order."""
    iv = sorted((max(e.start, t0), min(e.end, t1)) for e in events
                if e.end > t0 and e.start < t1)
    out: list[list[float]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out if b > a]


def union_ns(events: list[Event], t0: float, t1: float) -> float:
    return sum(b - a for a, b in _merged(events, t0, t1))


def busy_s(trace: Trace) -> float:
    """Seconds in the window in which an operation ran, averaged over the
    devices."""
    if not trace.devices:
        return 0.0
    t0, t1 = trace.window
    tot = sum(union_ns(d.ops or d.modules, t0, t1) for d in trace.devices)
    return tot / len(trace.devices) * 1e-9


def module_seconds(trace: Trace, part: str) -> list[float]:
    """Device seconds of each run of the programs whose name holds
    ``part``, over every device, within the window."""
    t0, t1 = trace.window
    return [(e.end - e.start) * 1e-9 for d in trace.devices for e in d.modules
            if part in e.name and e.start >= t0 and e.end <= t1]


def idle_gaps(trace: Trace, device: int = 0) -> list[tuple[float, float]]:
    """The intervals of the window in which no operation ran."""
    t0, t1 = trace.window
    d = trace.devices[device]
    gaps, t = [], t0
    for a, b in _merged(d.ops or d.modules, t0, t1):
        if a > t:
            gaps.append((t, a))
        t = b
    if t < t1:
        gaps.append((t, t1))
    return gaps


def gaps_by_host(trace: Trace, device: int = 0) -> list[tuple[str, float]]:
    """Idle seconds by what the host was doing: each gap's time is split
    over the host spans it overlaps (innermost span first; ``host.other``
    where no span covers it), summed per span name, largest first."""
    spans = sorted(trace.host, key=lambda e: e.end - e.start)
    out: dict[str, float] = {}
    for a, b in idle_gaps(trace, device) if trace.devices else []:
        left = [(a, b)]
        for s in spans:
            nxt = []
            for x, y in left:
                lo, hi = max(x, s.start), min(y, s.end)
                if hi > lo:
                    out[s.name] = out.get(s.name, 0.0) + (hi - lo) * 1e-9
                    if lo > x:
                        nxt.append((x, lo))
                    if y > hi:
                        nxt.append((hi, y))
                else:
                    nxt.append((x, y))
            left = nxt
            if not left:
                break
        rest = sum(y - x for x, y in left)
        if rest > 0:
            out["host.other"] = out.get("host.other", 0.0) + rest * 1e-9
    return sorted(out.items(), key=lambda kv: -kv[1])


def _attributed(trace: Trace, device: int):
    """Each operation of the window that is not a container, with the
    program run that encloses it (None where none does).  An operation
    whose span holds the next one (a ``while`` around its body's
    operations) is a container: its time is its body's."""
    import bisect

    t0, t1 = trace.window
    d = trace.devices[device]
    mods = sorted(d.modules, key=lambda e: e.start)
    starts = [m.start for m in mods]
    ops = sorted(d.ops, key=lambda e: (e.start, -e.end))
    for i, e in enumerate(ops):
        if e.end <= t0 or e.start >= t1:
            continue
        if i + 1 < len(ops) and ops[i + 1].start < e.end and ops[i + 1].end <= e.end:
            continue
        j = bisect.bisect_right(starts, e.start) - 1
        yield (mods[j] if j >= 0 and mods[j].end >= e.start else None), e


def top_ops(trace: Trace, n: int = 10, device: int = 0) -> list[tuple[str, float]]:
    """Device seconds per operation within the window, named
    ``<program>/<op>`` by the program run that encloses it, largest first;
    containers are left out (``_attributed``)."""
    if not trace.devices:
        return []
    t0, t1 = trace.window
    tot: dict[str, float] = {}
    for run, e in _attributed(trace, device):
        prog = run.name if run is not None else "?"
        key = f"{prog.split('(')[0]}/{e.name}"
        tot[key] = tot.get(key, 0.0) + (min(e.end, t1) - max(e.start, t0)) * 1e-9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def scope_seconds(trace: Trace, part: str, names: set[str],
                  device: int = 0) -> list[float]:
    """Device seconds of each run of the programs whose name holds
    ``part``, wholly within the window, spent in the operations named in
    ``names``; containers are left out (``_attributed``)."""
    if not trace.devices:
        return []
    t0, t1 = trace.window
    runs = {id(m): 0.0 for m in trace.devices[device].modules
            if part in m.name and m.start >= t0 and m.end <= t1}
    for run, e in _attributed(trace, device):
        if run is not None and id(run) in runs and e.name in names:
            runs[id(run)] += (e.end - e.start) * 1e-9
    return list(runs.values())
