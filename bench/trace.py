"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the
per-layer metrics read: the traced window, the device's busy time in
it, the device time of each program, and the host's activity during the
device's idle gaps, all on the profiler's one clock.

The window runs from the start of the first traversal span (a host
``TraceAnnotation`` the harness puts around each traversal) to the end
of the last.  The host's activity is read from the line of the thread
that ran the traversals.  A TPU's plane (``/device:TPU:<n>``) holds one line of
program executions (``XLA Modules``) and one of the operations inside
them (``XLA Ops``); busy time is the union of the operations'
intervals.  Times are averaged over the chips of the run.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

import numpy as np

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_MODULES_LINE = "XLA Modules"
_OPS_LINE = "XLA Ops"
_HOST_PLANE = "/host:CPU"
# how far back among earlier-starting host events to look for the
# innermost one around an idle gap
_HOST_LOOKBACK = 64
_TOP = 10


def program_name(event_name: str) -> str:
    """The jitted function's name of a program execution event:
    ``jit__bin_pass_impl(12)`` -> ``_bin_pass_impl``."""
    name = event_name.split("(", 1)[0].strip()
    return name[4:] if name.startswith("jit_") else name


def union_length(intervals: np.ndarray) -> float:
    """Total length covered by ``[start, end)`` rows."""
    if len(intervals) == 0:
        return 0.0
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    total, cur_s, cur_e = 0.0, iv[0, 0], iv[0, 1]
    for s, e in iv[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    return float(total + cur_e - cur_s)


def gaps(intervals: np.ndarray, lo: float, hi: float) -> list:
    """The ``(start, end)`` stretches of ``[lo, hi)`` no interval
    covers."""
    out, at = [], lo
    for s, e in intervals[np.argsort(intervals[:, 0], kind="stable")]:
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def _clip(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(intervals) == 0:
        return intervals
    iv = np.clip(intervals, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    # per program name: device seconds, averaged over the chips
    programs: dict
    # per host activity: seconds of device idle gaps it spans, chip 0
    idle_by_host: dict

    def program_seconds(self, names):
        """Device seconds of the programs named; None if none ran."""
        hit = [self.programs[n] for n in names if n in self.programs]
        return sum(hit) if hit else None

    def breakdown(self) -> dict:
        """The programs that took most device time, and the host
        activities under the most device idle time."""
        ops = sorted(self.programs.items(), key=lambda kv: -kv[1])[:_TOP]
        idle = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle[:_TOP]]}


def reduce_dir(trace_dir: str, span: str, chips: int) -> Reduced:
    """:func:`reduce_file` of the newest trace under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(max(paths, key=os.path.getmtime), span, chips)


def reduce_file(path: str, span: str, chips: int) -> Reduced:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = plane
        elif plane.name == _HOST_PLANE:
            for line in plane.lines:
                events = _events(line)
                # the thread that ran the traversals
                if any(n == span for n, _, _ in events):
                    host.extend(events)
    spans = [(s, e) for n, s, e in host if n == span]
    if not spans:
        raise ValueError(f"no {span!r} span in {path}")
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    if len(devices) < chips:
        raise ValueError(f"{path}: {len(devices)} TPU planes, the run "
                         f"used {chips} chips")

    busy, programs, idle_by_host = 0.0, collections.Counter(), {}
    for k, dev_id in enumerate(sorted(devices)[:chips]):
        lines = {line.name: _events(line) for line in devices[dev_id].lines}
        mods = lines.get(_MODULES_LINE, [])
        ops = lines.get(_OPS_LINE) or mods
        iv = _clip(np.array([(s, e) for _, s, e in ops], float).reshape(-1, 2),
                   lo, hi)
        busy += union_length(iv)
        for name, s, e in mods:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                programs[program_name(name)] += d
        if k == 0:
            idle_by_host = _attribute(gaps(iv, lo, hi), host)
    scale = 1e-9 / chips
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy * scale,
                   programs={n: t * scale for n, t in programs.items()},
                   idle_by_host={n: t * 1e-9
                                 for n, t in idle_by_host.items()})


def _attribute(idle: list, host: list) -> dict:
    """Seconds of idle gaps by the innermost host event around each
    gap's midpoint (the traversal span itself where nothing else is)."""
    host = sorted(host, key=lambda ev: ev[1])
    starts = [s for _, s, _ in host]
    out = collections.Counter()
    for g0, g1 in idle:
        mid = 0.5 * (g0 + g1)
        i = bisect.bisect_right(starts, mid)
        best, best_len = "other", float("inf")
        for name, s, e in host[max(0, i - _HOST_LOOKBACK):i][::-1]:
            if e >= mid and e - s < best_len:
                best, best_len = name, e - s
        out[best] += g1 - g0
    return out
