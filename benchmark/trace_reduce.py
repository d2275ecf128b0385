"""From the profiler's ``.xplane.pb`` to numbers: how long the device was
busy, which programs took the time, and where it sat idle.

Reads the file with JAX's ``ProfileData`` alone.  Run it as its own process
(``python benchmark/trace_reduce.py <file or dir>``) with ``JAX_PLATFORMS=cpu``:
the process that reads a trace must never reach for the chip.

A device plane is one named ``/device:TPU:<i>`` (the trace also holds
planes that are no chip: ``/device:CUSTOM:Megascale Trace``, ``#Chip0 ...``).
Its ``XLA Ops`` line holds every operation that ran, its ``XLA Modules``
line one event per program run.  Busy time is the union of the ops'
intervals (ops of one chip do not overlap, the union guards against nested
events), averaged over the device planes.  The traced window runs from the
first to the last event of any plane, host threads included.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: host events that say a thread waits, not what the host is doing
_WAITING = re.compile(r"wait|sleep|select|poll|accept|recv|acquire|idle|Listener",
                      re.I)


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(
        path, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _program(name: str) -> str:
    """An XLA module's event name without its run id: ``jit_solve(123)``."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce(path: str, top: int = 10) -> dict:
    """``reduce_data`` of the trace file at (or under) ``path``."""
    from jax.profiler import ProfileData

    return reduce_data(ProfileData.from_file(find_xplane(path)), top)


def reduce_data(data, top: int = 10) -> dict:
    """{"busy_s", "window_s", "idle_share", "device_planes", "device_ops",
    "programs", "idle_gaps"} of one trace; ``busy_s`` 0.0 when no
    operation ran on a device."""
    t_lo, t_hi = None, None
    device, host_lines = [], []
    for plane in data.planes:
        lines = [(line.name, _events(line)) for line in plane.lines]
        for _, evs in lines:
            for s, e, _ in evs:
                t_lo = s if t_lo is None else min(t_lo, s)
                t_hi = e if t_hi is None else max(t_hi, e)
        if DEVICE_PLANE.match(plane.name):
            device.append((plane.name, dict(lines)))
        elif plane.name.startswith("/host:"):
            host_lines.extend(evs for _, evs in lines)
    window_ns = (t_hi - t_lo) if t_lo is not None else 0
    result = {"busy_s": 0.0, "window_s": window_ns / 1e9,
              "device_planes": [n for n, _ in device], "device_ops": [],
              "programs": [], "idle_gaps": []}
    if not device or not window_ns:
        return result
    busy_ns, ops_time, prog_time, gaps = 0.0, {}, {}, []
    for _, lines in device:
        ops = lines.get(OPS_LINE)
        if ops is None:  # an unknown layout: every line of the plane
            ops = [ev for evs in lines.values() for ev in evs]
        merged = _union((s, e) for s, e, _ in ops)
        busy_ns += sum(e - s for s, e in merged)
        for s, e, name in ops:
            op = name.split(" = ")[0][:80]  # "%fusion.3", not its whole HLO
            ops_time[op] = ops_time.get(op, 0.0) + (e - s)
        modules = lines.get(MODULES_LINE, [])
        for s, e, name in modules:
            key = _program(name)
            prog_time[key] = prog_time.get(key, 0.0) + (e - s)
        starts = sorted((s, _program(n)) for s, _, n in modules)
        edges = [[t_lo, t_lo]] + merged + [[t_hi, t_hi]]
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b > a:
                nxt = next((n for s, n in starts if s >= b - 1), "end of trace")
                gaps.append((a, b, nxt))
    n_dev = len(device)
    result["busy_s"] = busy_ns / n_dev / 1e9
    result["idle_share"] = 100.0 * (1.0 - busy_ns / n_dev / window_ns)
    by_time = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
    result["device_ops"] = [[n, t / n_dev / 1e9] for n, t in by_time(ops_time)]
    result["programs"] = [[n, t / n_dev / 1e9] for n, t in by_time(prog_time)]
    # idle time by what the host was doing: each of the longest gaps is named
    # by the host event that covers most of it (the innermost one that is
    # not a wait) and the program that ended it; gaps of one name add up
    gaps.sort(key=lambda g: g[0] - g[1])
    by_name: dict = {}
    for a, b, nxt in gaps[:200]:
        name = f"{_host_doing(host_lines, a, b)} -> {nxt}"
        by_name[name] = by_name.get(name, 0.0) + (b - a)
    result["idle_gaps"] = [[n, t / n_dev / 1e9] for n, t in by_time(by_name)]
    return result


def _host_doing(host_lines, a: float, b: float) -> str:
    best, best_cover, best_len = "host (no event)", 0.0, float("inf")
    for evs in host_lines:
        for s, e, name in evs:
            if e <= a or s >= b or _WAITING.search(name):
                continue
            cover = min(e, b) - max(s, a)
            # most of the gap first, then the innermost (shortest) event
            if cover > best_cover * 1.05 or (
                    cover >= best_cover * 0.95 and (e - s) < best_len):
                best, best_cover, best_len = name, cover, e - s
    return best[:80]


if __name__ == "__main__":
    json.dump(reduce(sys.argv[1]), sys.stdout)
    sys.stdout.write("\n")
