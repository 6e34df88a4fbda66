"""From a profiler trace (``.xplane.pb``) to busy time, time by name, and gaps.

``jax.profiler.ProfileData`` reads the file with nothing but JAX. A TPU writes
one plane per chip (``/device:TPU:<n>``) whose line ``XLA Ops`` holds one event
per operation run and whose line ``XLA Modules`` one per program run; host
threads are lines of ``/host:CPU``. All planes share one clock. The CPU
backend has no device plane: there, for the rehearsal only, the XLA client's
threads stand in for the device.

Only the process that holds the chip can take the trace, so it reduces the
trace too and ships the result, a plain dict, back to the benchmark.
"""
from __future__ import annotations

import glob
import os
import re

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def short_name(event_name: str) -> tuple:
    """A TPU names an operation by its whole HLO instruction,
    ``%fusion.4 = bf16[..] fusion(..), kind=..``: the name is what stands
    before `` = ``, the rest is detail for patterns to search. A custom call
    keeps all of it (its operands tell the Pallas kernels apart: the program
    gives them no name), anything else its first 160 characters."""
    name, _, rest = event_name.partition(" = ")
    return name.lstrip("%"), rest[:2000 if "custom_call_target" in rest else 160]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under {trace_dir}")
    return files[-1]


def read_xplane(path: str) -> list:
    """[{name, lines: [{name, events: [(name, start_ns, dur_ns, detail)]}]}]"""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                name, detail = short_name(ev.name)
                events.append((name, float(ev.start_ns), float(ev.duration_ns), detail))
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _is_device(plane_name: str) -> bool:
    return re.fullmatch(r"/device:TPU:\d+", plane_name) is not None


def split_planes(planes: list) -> tuple:
    """(device planes, host lines). On a CPU the XLA client's threads are
    the one 'device' and every other thread is the host."""
    devices = [p for p in planes if _is_device(p["name"])]
    host = [ln for p in planes if p["name"].startswith("/host:") for ln in p["lines"]]
    if devices:
        return devices, host
    xla = [ln for ln in host if ln["name"].startswith("tf_XLA")]
    rest = [ln for ln in host if not ln["name"].startswith("tf_XLA")]
    ops = [e for ln in xla for e in ln["events"] if e[2] > 0]
    programs = [("jit_" + e[0][len("PjitFunction("):-1],) + e[1:] for ln in rest
                for e in ln["events"] if e[0].startswith("PjitFunction(")]
    stand_in = {"name": "/host:CPU (XLA threads)", "lines": [
        {"name": OPS_LINE, "events": ops}, {"name": MODULES_LINE, "events": programs}]}
    return ([stand_in] if ops else []), rest


def union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _line(plane: dict, name: str) -> list:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def _self_times(events: list) -> list:
    """[(name, duration, self time, detail)]: an operation that holds others
    (a ``while`` around its body) keeps as its own only the time none of
    them covers."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    own = [e[2] for e in events]
    stack = []
    for i in order:
        _, start, dur, _ = events[i]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= dur
        stack.append(i)
    return [(e[0], e[2], max(0.0, own[i]), e[3]) for i, e in enumerate(events)]


def _by_name(events: list) -> dict:
    out = {}
    for name, dur, own, detail in _self_times(events):
        row = out.setdefault(name, {"seconds": 0.0, "self_seconds": 0.0, "count": 0, "detail": detail})
        row["seconds"] += dur * 1e-9
        row["self_seconds"] += own * 1e-9
        row["count"] += 1
    return out


def _attribute(gap: tuple, host_lines: list) -> str:
    """The host event that covers most of the gap; of equals, the shortest."""
    a, b = gap
    best, best_key = "unattributed", (0.0, 0.0)
    for ln in host_lines:
        for name, s, d, _ in ln["events"]:
            cover = min(b, s + d) - max(a, s)
            if cover > 0 and (cover, -d) > best_key:
                best, best_key = f"{ln['name'].split('/')[0]}:{name}", (cover, -d)
    return best[:120] if best_key[0] >= 0.5 * (b - a) else "unattributed"


def reduce_planes(planes: list, top: int = 10) -> dict:
    """window_s, busy_s (mean over the devices), seconds and self seconds by
    operation and by program (means over the devices), the longest idle gaps
    of the first device with what the host was in, and the time the cores
    spent in collective operations."""
    devices, host_lines = split_planes(planes)
    if not devices:
        raise RuntimeError("the trace holds no device plane: planes are "
                           + ", ".join(p["name"] for p in planes))
    spans = [(s, s + d) for p in planes for ln in p["lines"] for _, s, d, _ in ln["events"]]
    t0, t1 = min(s for s, _ in spans), max(e for _, e in spans)
    busy, ops, modules, exposed, gaps = [], {}, {}, [], []
    for i, plane in enumerate(devices):
        op_events = _line(plane, OPS_LINE) or [e for ln in plane["lines"] for e in ln["events"]]
        merged = union([(s, s + d) for _, s, d, _ in op_events if d > 0])
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        for table, events in ((ops, op_events), (modules, _line(plane, MODULES_LINE))):
            for name, row in _by_name(events).items():
                acc = table.setdefault(name, {"seconds": 0.0, "self_seconds": 0.0, "count": 0,
                                              "detail": row["detail"]})
                for k in ("seconds", "self_seconds", "count"):
                    acc[k] += row[k] / len(devices)
        exposed.append(_exposed_collective_s(op_events))
        if i == 0:
            edges = [t0] + [x for ab in merged for x in ab] + [t1]
            gaps = sorted(((edges[j + 1] - edges[j], (edges[j], edges[j + 1]))
                           for j in range(0, len(edges), 2) if edges[j + 1] > edges[j]),
                          reverse=True)[:top]
    if not sum(busy) > 0:
        raise RuntimeError("no operation ran on the device inside the traced window")
    return {
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": sum(busy) / len(busy),
        "busy_s_per_device": busy,
        "ops": ops,
        "modules": modules,
        "collective_exposed_s": sum(exposed) / len(exposed),
        "idle_gaps": [[_attribute(g, host_lines), d * 1e-9] for d, g in gaps],
        "device_planes": [p["name"] for p in devices],
        "device_lines": sorted({ln["name"] for p in devices for ln in p["lines"]}),
    }


COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute")


def _exposed_collective_s(op_events: list) -> float:
    """Time the core spends in collective operations themselves (their own
    time on ``XLA Ops``, where one operation runs at a time): while it waits
    there it computes nothing. What a collective moves in the background,
    beside other operations, is hidden and is not counted."""
    return sum(own for name, _d, own, _ in _self_times(op_events) if COLLECTIVE.search(name)) * 1e-9


def seconds_matching(table: dict, pattern: str) -> tuple:
    """(seconds, count) of the rows of ``ops`` or ``modules`` whose name or
    detail matches ``pattern``."""
    rx = re.compile(pattern)
    rows = [r for n, r in table.items() if rx.search(n) or rx.search(r.get("detail", ""))]
    return sum(r["seconds"] for r in rows), sum(r["count"] for r in rows)


def breakdown(reduced: dict, top: int = 10) -> dict:
    ops = sorted(reduced["ops"].items(), key=lambda kv: -kv[1]["self_seconds"])[:top]
    return {"device_ops": [[n[:120], r["self_seconds"]] for n, r in ops],
            "idle_gaps": [[n, s] for n, s in reduced["idle_gaps"][:top]]}

