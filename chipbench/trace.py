"""Reduction of one profiler trace (an XSpace) to intervals the per-layer
metric readers share.

The traced window is the host span ``chipbench.window`` that the harness
opens around the traced part of the run. Device work is the events of the
``XLA Ops`` line of a TPU plane; programs are the events of its ``XLA
Modules`` line, named ``jit_<function>`` after the jitted function
(``jit_decode_step``, ``jit__admit_wave_fn`` ...). A cell on ``chips``
chips reduces the first ``chips`` TPU planes by name, one per chip;
``ops`` and ``programs`` are the first chip's, which is all a one-chip
cell has. Host spans are the harness's own ``chipbench.*`` annotations.
"""
from __future__ import annotations

import bisect
import dataclasses
import gzip
import re
from pathlib import Path

WINDOW = "chipbench.window"


@dataclasses.dataclass
class Event:
    start: float              # ns, on the trace's clock
    end: float
    name: str
    stats: dict


@dataclasses.dataclass
class Chip:
    ops: list                 # device op events inside the window
    programs: list            # device program events inside the window


@dataclasses.dataclass
class Reduced:
    window: tuple             # (start, end) ns of the traced window
    ops: list                 # the first chip's op events inside the window
    programs: list            # the first chip's program events
    spans: list               # host chipbench.* spans inside the window
    chips: list = None        # Chip of every chip of the cell, first first

    def __post_init__(self):
        self.ops = sorted(self.ops, key=lambda e: e.start)
        self._starts = [e.start for e in self.ops]
        if self.chips is None:
            self.chips = [Chip(self.ops, self.programs)]

    def ops_between(self, lo: float, hi: float) -> list:
        """Device op events that start in [lo, hi)."""
        return self.ops[bisect.bisect_left(self._starts, lo):
                        bisect.bisect_left(self._starts, hi)]

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def busy_ns(self) -> float:
        return union_length([(e.start, e.end) for e in self.ops])

    def busy_ns_per_chip(self) -> list:
        return [union_length([(e.start, e.end) for e in c.ops])
                for c in self.chips]

    def spans_named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]

    def programs_matching(self, pattern: str) -> list:
        rx = re.compile(pattern)
        return [p for p in self.programs if rx.search(p.name)]


def union_length(intervals) -> float:
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def idle_gaps(intervals, lo: float, hi: float) -> list:
    """(start, end) of the stretches of [lo, hi] that no interval covers."""
    gaps, at = [], lo
    for a, b in sorted(intervals):
        if a > at:
            gaps.append((at, min(a, hi)))
        at = max(at, b)
        if at >= hi:
            break
    if at < hi:
        gaps.append((at, hi))
    return [(a, b) for a, b in gaps if b > a]


def _events(line, lo=None, hi=None) -> list:
    out = []
    for e in line.events:
        s, d = float(e.start_ns), float(e.duration_ns)
        if lo is not None and (s + d <= lo or s >= hi):
            continue
        if lo is not None:
            a, b = max(s, lo), min(s + d, hi)
        else:
            a, b = s, s + d
        out.append(Event(a, b, e.name, dict(e.stats)))
    return out


def _device_planes(profile, chips: int) -> list:
    planes = [p for p in profile.planes if p.name.startswith("/device:TPU:")]
    if len(planes) < chips:
        raise ValueError(f"the trace holds {len(planes)} TPU planes, the "
                         f"cell asks for {chips}")
    return sorted(planes, key=lambda p: p.name)[:chips]


def _chip(plane, lo, hi) -> Chip:
    lines = {ln.name: ln for ln in plane.lines}
    if "XLA Ops" not in lines or "XLA Modules" not in lines:
        raise ValueError(f"TPU plane lines {sorted(lines)}")
    return Chip(_events(lines["XLA Ops"], lo, hi),
                _events(lines["XLA Modules"], lo, hi))


def reduce(profile, chips: int = 1) -> Reduced:
    """``profile``: a ``jax.profiler.ProfileData``; ``chips``: the TPU
    planes of the cell."""
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans += [e for e in _events(line)
                      if e.name.startswith("chipbench.")]
    windows = [s for s in spans if s.name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {WINDOW} spans in the trace")
    lo, hi = windows[0].start, windows[0].end
    per_chip = [_chip(p, lo, hi) for p in _device_planes(profile, chips)]
    return Reduced(
        window=(lo, hi), ops=per_chip[0].ops, programs=per_chip[0].programs,
        spans=sorted([s for s in spans if s.name != WINDOW
                      and s.end > lo and s.start < hi],
                     key=lambda s: s.start),
        chips=per_chip)


def load(path, chips: int = 1) -> Reduced:
    """Reduce an ``.xplane.pb`` file (gzipped or not)."""
    from jax.profiler import ProfileData
    data = Path(path).read_bytes()
    if data[:2] == b"\x1f\x8b":
        data = gzip.decompress(data)
    return reduce(ProfileData.from_serialized_xspace(data), chips)


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The device programs that took most time (summed over the chips),
    and the longest idle gaps labelled with the host span they fell in
    (and, on several chips, the chip)."""
    per = {}
    for chip in red.chips:
        for p in chip.programs:
            per[p.name] = per.get(p.name, 0.0) + (p.end - p.start)
    device_ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    for k, chip in enumerate(red.chips):
        suffix = f" TPU:{k}" if len(red.chips) > 1 else ""
        gaps += [(g, suffix) for g in
                 idle_gaps([(e.start, e.end) for e in chip.ops],
                           *red.window)]
    gaps = sorted(gaps, key=lambda g: g[0][0] - g[0][1])[:top]
    return {"device_ops": [[n, t * 1e-9] for n, t in device_ops],
            "idle_gaps": [[host_label(red, g) + suffix, (g[1] - g[0]) * 1e-9]
                          for g, suffix in gaps]}


def host_label(red: Reduced, gap) -> str:
    """Name of the innermost host span that covers the middle of ``gap``."""
    mid = 0.5 * (gap[0] + gap[1])
    inside = [s for s in red.spans if s.start <= mid <= s.end]
    if not inside:
        return "host:outside-spans"
    return min(inside, key=lambda s: s.end - s.start).name


def per_step(red: Reduced, steps: list, pattern: str = None) -> list:
    """``(step, ns)`` for each harness step traced in full: the device
    time of the programs matching ``pattern`` that started inside its host
    span or, with no pattern, the time in which any operation ran inside
    it. Each step ends with a device->host sync of its wave, so its work
    runs inside its span."""
    by_n = {s.n: s for s in steps}
    progs = red.programs_matching(pattern) if pattern else None
    out = []
    for span in red.spans_named("chipbench.step"):
        step = by_n.get(span.stats.get("n"))
        if step is None or span.start < red.window[0] \
                or span.end > red.window[1]:
            continue
        if progs is None:
            ns = union_length([(e.start, min(e.end, span.end)) for e in
                               red.ops_between(span.start, span.end)])
        else:
            ns = sum(p.end - p.start for p in progs
                     if span.start <= p.start < span.end)
        out.append((step, ns))
    return out
