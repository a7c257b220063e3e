"""Reduce a `jax.profiler` trace (`.xplane.pb`) to what the per-layer
readers need, on the profiler's own clock.

* device busy: the union of the intervals in which an executable ran on
  a device (the ``XLA Modules`` line of each ``/device:`` plane),
  clipped to the window, averaged over the devices;
* executables: each module run, tagged ``scan`` or ``exact`` by the
  program span it ran under (``sim[...]|device-sim`` or
  ``sim[...]|exact-verify``), else ``other``;
* host spans: every annotation named ``<name>|<phase>`` — the program's
  spans, made annotations by the benchmark's tracer, and the
  benchmark's own ``bench|<call>`` spans;
* the window: the ``bench|window`` annotation.

Times are seconds from here on.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field

SIM_RE = re.compile(r"^sim\[(\d+)x(\d+)x(\d+)\]$")


def union(intervals) -> list:
    """Merged, sorted [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def covered(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


@dataclass
class Span:
    name: str
    phase: str
    start: float
    end: float


@dataclass
class Module:
    name: str
    start: float
    end: float
    kind: str = "other"


@dataclass
class TraceData:
    window: tuple
    devices: dict = field(default_factory=dict)   # plane -> [Module]
    spans: list = field(default_factory=list)     # [Span]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy(self) -> list:
        """Busy seconds in the window, per device."""
        lo, hi = self.window
        return [covered(clip([[m.start, m.end] for m in mods], lo, hi))
                for mods in self.devices.values()]

    def busy_s(self) -> float:
        b = self.busy()
        return sum(b) / len(b) if b else 0.0

    def modules(self, kind=None) -> list:
        lo, hi = self.window
        return [m for mods in self.devices.values() for m in mods
                if lo <= m.start < hi and (kind is None or m.kind == kind)]

    def phase_spans(self, phase: str) -> list:
        return [s for s in self.spans if s.phase == phase]

    def phase_share(self, phase: str) -> float:
        """Share of the window covered by spans of one phase."""
        lo, hi = self.window
        return covered(clip([[s.start, s.end] for s in
                             self.phase_spans(phase)], lo, hi)) / self.window_s

    def sim_steps(self, phase: str) -> int:
        """Sequential steps the program's simulation calls ran: the op
        bucket of every ``sim[NxRxC]`` span of one phase."""
        lo, hi = self.window
        n = 0
        for s in self.phase_spans(phase):
            m = SIM_RE.match(s.name)
            if m and lo <= s.start < hi:
                n += int(m.group(1))
        return n

    def idle_gaps(self, top: int = 10) -> list:
        """Device-idle seconds in the window (first device), split by
        what the host was doing: the innermost span active on the host,
        ``no span`` where none was."""
        lo, hi = self.window
        mods = next(iter(self.devices.values()), [])
        busy = union(clip([[m.start, m.end] for m in mods], lo, hi))
        spans = [sp for sp in self.spans
                 if not (sp.phase == "bench" and sp.name == "window")
                 and sp.end > lo and sp.start < hi]
        cuts = sorted({lo, hi, *(t for b in busy for t in b),
                       *(min(max(t, lo), hi) for sp in spans
                         for t in (sp.start, sp.end))})
        named: dict = {}
        k, j = 0, 0
        by_start = sorted(spans, key=lambda sp: sp.start)
        active: list = []
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            while k < len(busy) and busy[k][1] <= mid:
                k += 1
            if k < len(busy) and busy[k][0] <= mid:
                continue                  # the device was busy
            while j < len(by_start) and by_start[j].start <= mid:
                active.append(by_start[j])
                j += 1
            active = [sp for sp in active if sp.end > mid]
            name = "no span"
            if active:
                sp = min(active, key=lambda x: x.end - x.start)
                name = f"bench:{sp.name}" if sp.phase == "bench" \
                    else f"{sp.phase}:{SIM_RE.sub('sim', sp.name)}"
            named[name] = named.get(name, 0.0) + (b - a)
        return sorted(([k_, v] for k_, v in named.items()),
                      key=lambda kv: -kv[1])[:top]

    def device_ops(self, top: int = 10) -> list:
        """Device seconds per executable in the window, named by its
        kind and module name."""
        tot: dict = {}
        for m in self.modules():
            key = f"{m.kind}:{m.name.split('(')[0]}"
            tot[key] = tot.get(key, 0.0) + (m.end - m.start)
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:top]


def _span_of(name: str):
    """(name, phase) of an annotation named ``name|phase``, else None.
    The benchmark's own spans are ``bench|<call>``: phase ``bench``."""
    if "|" not in name:
        return None
    a, b = name.rsplit("|", 1)
    if a == "bench":
        return b, "bench"
    return a, b


def reduce_trace(path) -> TraceData:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    devices[plane.name] = [
                        Module(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    sp = _span_of(e.name)
                    if sp is not None:
                        spans.append(Span(sp[0], sp[1], e.start_ns * 1e-9,
                                          e.end_ns * 1e-9))
    windows = [s for s in spans if s.phase == "bench" and s.name == "window"]
    if not windows:
        raise ValueError(f"{path}: no bench|window annotation")
    w = windows[0]
    sims = sorted((s for s in spans if SIM_RE.match(s.name)
                   and s.phase in ("device-sim", "exact-verify")),
                  key=lambda s: s.start)
    _tag_modules(devices, sims)
    return TraceData(window=(w.start, w.end), devices=devices, spans=spans)


def _tag_modules(devices: dict, sims: list) -> None:
    """Tag each executable ``scan`` or ``exact``: every run goes to the
    simulation span it overlaps most (device and host clocks agree only
    to some microseconds), and an executable takes the kind most of its
    runs got, so its runs outside any span are tagged too."""
    starts = [s.start for s in sims]
    votes: dict = {}
    for mods in devices.values():
        for m in mods:
            k = bisect.bisect_right(starts, m.end) - 1
            best, kind = 0.0, None
            # spans of one thread do not overlap: their ends are sorted too
            while k >= 0 and sims[k].end > m.start - 1.0:
                ov = min(m.end, sims[k].end) - max(m.start, sims[k].start)
                if ov > best:
                    best = ov
                    kind = "scan" if sims[k].phase == "device-sim" \
                        else "exact"
                k -= 1
            if kind is not None:
                v = votes.setdefault(m.name, {})
                v[kind] = v.get(kind, 0) + 1
    for mods in devices.values():
        for m in mods:
            if m.name in votes:
                m.kind = max(votes[m.name].items(), key=lambda kv: kv[1])[0]
