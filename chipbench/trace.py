"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

Per chip: the busy union of its device operations inside the benchmark's
window annotation, the device time and count of each program it ran, and
its idle gaps, each labelled by the innermost benchmark annotation that was
open on the host at the gap's middle.  Over all chips: device time per
operation name.
"""
from __future__ import annotations

import dataclasses
import pathlib
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def union_seconds(intervals) -> float:
    """Seconds covered by the union of (start, end) intervals."""
    total, reach = 0.0, float("-inf")
    for lo, hi in sorted(intervals):
        if hi <= reach:
            continue
        total += hi - max(lo, reach)
        reach = hi
    return total


def idle_gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi] that no interval covers."""
    gaps, reach = [], lo
    for s, e in sorted(intervals):
        if s > reach:
            gaps.append((reach, min(s, hi)))
        reach = max(reach, e)
        if reach >= hi:
            break
    if reach < hi:
        gaps.append((reach, hi))
    return [(s, e) for s, e in gaps if e > s]


def program_name(event_name: str) -> str:
    """A module event's program name without its run suffix:
    ``jit_matmul(123)`` -> ``jit_matmul``."""
    return re.sub(r"\(\d+\)$", "", event_name)


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy: dict[int, float]                      # chip id -> busy seconds
    programs: dict[int, dict[str, list[float]]]  # chip id -> name -> seconds
    ops: dict[str, float]                       # op name -> device seconds
    gaps: list[tuple[str, float]]               # (label, seconds)

    @property
    def busy_s(self) -> float | None:
        """Busy seconds averaged over the chips; None for a trace that holds
        no chip."""
        return sum(self.busy.values()) / len(self.busy) if self.busy else None

    def breakdown(self) -> dict:
        ops = sorted(self.ops.items(), key=lambda t: -t[1])[:TOP]
        gaps = sorted(self.gaps, key=lambda t: -t[1])[:TOP]
        return {"device_ops": [list(t) for t in ops],
                "idle_gaps": [list(t) for t in gaps]}


def _device_id(plane_name: str) -> int | None:
    m = re.fullmatch(r"/device:TPU:(\d+)", plane_name)
    return int(m.group(1)) if m else None


def reduce(profile, chip_ids, window: str, prefix: str = "chipbench."
           ) -> Reduction:
    """Reduce a ``jax.profiler.ProfileData`` over the chips ``chip_ids``,
    inside the host annotation named ``window``.  Times are seconds."""
    annotations, planes = [], {}
    for plane in profile.planes:
        dev = _device_id(plane.name)
        if dev is not None:
            planes[dev] = plane
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    s = ev.start_ns * 1e-9
                    annotations.append((ev.name, s, s + ev.duration_ns * 1e-9))
    spans = [(s, e) for name, s, e in annotations if name == window]
    if not spans:
        raise ValueError(f"no {window!r} annotation in the trace")
    lo, hi = spans[0]
    inner = sorted((a for a in annotations if a[0] != window),
                   key=lambda a: a[1])

    def label(t: float) -> str:
        open_ = [a for a in inner if a[1] <= t < a[2]]
        # the innermost: the one that started last
        return max(open_, key=lambda a: a[1])[0] if open_ else window

    busy, programs, ops, gaps = {}, {}, {}, []
    for dev in chip_ids if planes else ():
        plane = planes.get(dev)
        lines = {ln.name: ln for ln in plane.lines} if plane else {}
        intervals = []
        if OPS_LINE in lines:
            for ev in lines[OPS_LINE].events:
                s = ev.start_ns * 1e-9
                s, e = max(s, lo), min(s + ev.duration_ns * 1e-9, hi)
                if e > s:           # the part inside the window
                    intervals.append((s, e))
                    ops[ev.name] = ops.get(ev.name, 0.0) + e - s
        progs = programs[dev] = {}
        if MODULES_LINE in lines:
            for ev in lines[MODULES_LINE].events:
                s = ev.start_ns * 1e-9
                if lo <= s < hi:
                    progs.setdefault(program_name(ev.name), []).append(
                        ev.duration_ns * 1e-9)
        busy[dev] = union_seconds(intervals)
        gaps += [(f"{label((s + e) / 2)} tpu:{dev}", e - s)
                 for s, e in idle_gaps(intervals, lo, hi)]
    return Reduction(window_s=hi - lo, busy=busy, programs=programs, ops=ops,
                     gaps=gaps)


def reduce_dir(log_dir: str, chips, window: str) -> Reduction:
    """Reduce the newest ``.xplane.pb`` under ``log_dir``."""
    import jax

    path = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))[-1]
    profile = jax.profiler.ProfileData.from_file(str(path))
    return reduce(profile, [c.id for c in chips], window)
