"""Reduction of a profiler trace (`.xplane.pb`) to the benchmark's numbers.

* Device operations: on a TPU, the events of the `XLA Ops` line of each
  `/device:TPU:<i>` plane; in a trace with no device plane (the CPU
  backend, used only by the tests) the host events that carry an
  `hlo_op` stat.
* The traced window: from the first to the last host annotation the
  harness wrote (`jax.profiler.TraceAnnotation` around its own calls,
  named by the entry; the harness passes their names in).
* Busy time: the union of the device-op intervals inside the window,
  averaged over the chips used; the idle share is 1 - busy / window.
* Top device ops: self time (an op's duration less that of the ops
  nested in it) summed by op name.
* Idle gaps: the stretches of the window in which no op ran on the
  device (chip 0), split by what the host was doing then: the
  outermost harness annotation and the innermost host event inside it.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

TOP = 10


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns, e.start_ns + e.duration_ns, e


def load(path: str, n_chips: int, names):
    """(device op intervals per chip, intervals of the host annotations
    named in `names`, host event intervals of the annotated thread(s));
    times in ns."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    has_tpu = any(p.name.startswith("/device:TPU:") for p in planes)
    chips: dict[int, list] = {}
    cpu_ops: list = []
    annots: list = []
    host: list = []
    for plane in planes:
        if plane.name.startswith("/device:TPU:"):
            i = int(plane.name.rsplit(":", 1)[1])
            if i >= n_chips:
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    chips.setdefault(i, []).extend(
                        (s, t, n) for n, s, t, _ in _events(line))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = list(_events(line))
                mine = [(s, t, n) for n, s, t, _ in evs if n in names]
                if mine:
                    annots.extend(mine)
                    host.extend((s, t, n) for n, s, t, _ in evs)
                if not has_tpu:
                    for n, s, t, e in evs:
                        stats = dict(e.stats)
                        if "hlo_op" in stats:
                            cpu_ops.append((s, t, stats["hlo_op"]))
    if not chips and cpu_ops:
        chips = {0: cpu_ops}
    return chips, annots, host


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Disjoint, sorted union of [s, t) intervals clipped to [lo, hi)."""
    out: list[list[float]] = []
    for s, t, *_ in sorted(intervals):
        s, t = max(s, lo), min(t, hi)
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(a, b) for a, b in out]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    out, cur = [], lo
    for s, t in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, t)
    if cur < hi:
        out.append((cur, hi))
    return out


def self_times(intervals) -> dict[str, float]:
    """Op name -> summed self time (ns); nested ops are subtracted from
    the op that contains them."""
    acc: dict[str, float] = defaultdict(float)
    stack: list[list] = []  # [end, name, child_ns, start]

    def pop():
        end, name, child, start = stack.pop()
        acc[name] += (end - start) - child
        if stack:
            stack[-1][2] += end - start

    for s, t, n in sorted(intervals, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            pop()
        stack.append([t, n, 0.0, s])
    while stack:
        pop()
    return acc


def host_segments(host, names) -> list[tuple[float, float, str]]:
    """Flatten nested host events into [s, t) segments labelled
    `<outermost annotation>` or `<outermost>><innermost event>`, the
    annotations being the host events named in `names`."""
    bounds = sorted({x for s, t, _ in host for x in (s, t)})
    if not bounds:
        return []
    evs = sorted(host, key=lambda x: (x[0], -x[1]))
    segs, stack, i = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(evs) and evs[i][0] <= a:
            stack.append(evs[i])
            i += 1
        stack = [e for e in stack if e[1] > a]
        outer = [e for e in stack if e[2] in names]
        if not outer:
            continue
        inner = min(stack, key=lambda e: e[1] - e[0])
        label = outer[0][2] if inner is outer[0] else f"{outer[0][2]}>{inner[2]}"
        segs.append((a, b, label))
    return segs


def attribute(idle, segs) -> dict[str, float]:
    """Idle ns per host label (overlap of each gap with each segment)."""
    acc: dict[str, float] = defaultdict(float)
    j = 0
    for s, t in idle:
        covered = 0.0
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < t:
            ov = min(t, segs[k][1]) - max(s, segs[k][0])
            if ov > 0:
                acc[segs[k][2]] += ov
                covered += ov
            k += 1
        if t - s - covered > 0:
            acc["host outside any annotation"] += t - s - covered
    return acc


def _top(acc: dict, n: int = TOP) -> list:
    return [[k, v / 1e9] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def summarize(trace_dir: str, n_chips: int, names) -> dict:
    """The trace's numbers; `names` are the harness's annotations."""
    chips, annots, host = load(find_xplane(trace_dir), n_chips, names)
    ops = [x for evs in chips.values() for x in evs]
    if annots:
        lo, hi = min(s for s, _, _ in annots), max(t for _, t, _ in annots)
    else:
        lo, hi = min(s for s, _, _ in ops), max(t for _, t, _ in ops)
    window_ns = hi - lo
    busy_per_chip = [sum(b - a for a, b in union(evs, lo, hi))
                     for evs in chips.values()] or [0.0]
    busy_ns = sum(busy_per_chip) / max(len(chips), 1)
    idle = gaps(union(chips.get(0, []), lo, hi), lo, hi)
    n_annot = defaultdict(int)
    for _, _, n in annots:
        n_annot[n] += 1
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": window_ns / 1e9,
        "idle_share": 1.0 - busy_ns / window_ns if window_ns > 0 else None,
        "n_device_ops": len(ops),
        "annotations": dict(n_annot),
        "device_ops": _top(self_times([x for x in ops if lo <= x[0] < hi])),
        "idle_gaps": _top(attribute(idle, host_segments(host, names))),
    }
