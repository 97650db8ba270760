"""From a profiler trace (``*.xplane.pb``) to device busy time, the device
operations that took most of it, and the longest idle gaps.

Busy is the union of the intervals in which an operation ran on the device
(the ``XLA Ops`` line of each ``/device:TPU:n`` plane; a ``while`` holds its
body's operations inside its own interval, and the union counts that time
once). The idle share is 1 - busy / window, worked out by the caller from
``busy_s`` and the host-clock length of the traced window.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def _short(name: str) -> str:
    """``%fusion.3 = f32[..] fusion(...)`` -> ``fusion.3``."""
    m = re.match(r"\s*%?([^\s=]+)\s*=", name)
    return m.group(1) if m else name.split("(")[0].strip()


def _union(intervals: list) -> tuple[float, list]:
    """(total covered ns, merged [start, end] list) of sorted-able intervals."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def reduce_trace(path: str, top: int = 10) -> dict:
    """``busy_s`` averaged over the device planes found, ``devices``,
    ``module_runs`` {program: runs}, ``device_ops`` [[module/op, seconds], ...] by exclusive time, and
    ``idle_gaps`` [[what the host was doing or between which ops, seconds]]."""
    from jax.profiler import ProfileData  # jax only parses here: no backend

    data = ProfileData.from_file(path)
    busy_ns: list = []
    runs: dict = {}
    op_ns: dict = {}
    gaps: list = []
    host_events: list = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 1e6:  # >1 ms: candidates to name a gap
                        host_events.append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
        if not plane.name.startswith("/device:TPU:"):
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name == _OPS_LINE:
                ops = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                       for ev in line.events]
            elif line.name == _MODULES_LINE:
                modules = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                           for ev in line.events]
        if not ops:
            continue
        total, merged = _union([(s, e) for s, e, _ in ops])
        busy_ns.append(total)
        modules.sort()
        for _, _, name in modules:
            key = re.sub(r"\(\d+\)$", "", name)
            runs[key] = runs.get(key, 0) + 1

        def module_of(t: float) -> str:
            # modules do not overlap on one device; traces hold ~1e5 ops
            i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
            if i >= 0 and modules[i][0] <= t < modules[i][1]:
                return re.sub(r"\(\d+\)$", "", modules[i][2])
            return "?"

        # exclusive time: an op's interval less what its children cover.
        # Ops on one line nest properly, so a stack walk gives it.
        ops.sort(key=lambda o: (o[0], -o[1]))
        stack: list = []  # [end, key, own_ns]

        def close(upto: float) -> None:
            while stack and stack[-1][0] <= upto:
                end, key, own = stack.pop()
                op_ns[key] = op_ns.get(key, 0.0) + own

        for s, e, name in ops:
            close(s)
            if stack:
                stack[-1][2] -= min(e, stack[-1][0]) - s
            stack.append([e, f"{module_of(s)}/{_short(name)}", e - s])
        close(float("inf"))

        names = {round(s): n for s, _, n in ops}
        ends = {round(e): n for _, e, n in ops}
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            gaps.append((s1 - e0, e0, s1,
                         _short(ends.get(round(e0), "?")),
                         _short(names.get(round(s1), "?"))))
    if not busy_ns:
        return {"busy_s": 0.0, "devices": 0, "module_runs": {}, "device_ops": [],
                "idle_gaps": []}
    gaps.sort(reverse=True)
    idle: list = []
    for length, g0, g1, before, after in gaps[:top]:
        # the host event that covers most of the gap names it, if any does
        best, best_cover = None, 0.0
        for s, e, name in host_events:
            cover = min(e, g1) - max(s, g0)
            if cover > best_cover:
                best, best_cover = name, cover
        label = (best if best is not None and best_cover >= 0.5 * length
                 else f"between {before} and {after}")
        idle.append([label[:120], length / 1e9])
    device_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "devices": len(busy_ns),
        "module_runs": runs,  # program name -> times it ran, over all devices
        "device_ops": [[k[:120], v / 1e9] for k, v in device_ops],
        "idle_gaps": idle,
    }
