"""The contract of the last line, as code.

``check_line`` takes the last line a run printed, the manifest, the cell's
name and the trace mode, and returns what is wrong with it (nothing, when it
is right). ``python3 -m chipbench`` runs it on its own line before printing
and exits non-zero on a violation. ``check_manifest`` holds the character and
size rules of ``BENCHMARK.json`` itself.
"""
from __future__ import annotations

import json
import math
import re

from chipbench.manifest import metrics_for

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TOP_KEYS = ("correct", "attempted", "failed", "metrics", "device")


def _number(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) and math.isfinite(x)


def check_line(line: str, manifest: dict, workload: str, trace: int,
               platform: str = "tpu", may_lack=()) -> list:
    """Everything wrong with ``line`` as the result of one run of ``workload``."""
    try:
        obj = json.loads(line)
    except (TypeError, ValueError) as e:
        return [f"the line is not JSON: {e}"]
    if not isinstance(obj, dict):
        return ["the line is not a JSON object"]
    wrong = [f"key {k!r} is missing" for k in TOP_KEYS if k not in obj]
    extra = set(obj) - set(TOP_KEYS) - ({"breakdown"} if trace else set())
    wrong += [f"key {k!r} does not belong on the line" for k in sorted(extra)]
    if wrong:
        return wrong
    if not isinstance(obj["correct"], bool):
        wrong.append("'correct' is not true or false")
    for k in ("attempted", "failed"):
        if not isinstance(obj[k], int) or isinstance(obj[k], bool) or obj[k] < 0:
            wrong.append(f"{k!r} is not a count")
    if not wrong and (obj["attempted"] < 1 or obj["failed"] > obj["attempted"]):
        wrong.append("'attempted' is 0, or 'failed' is above it")

    section = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m for m in metrics_for(manifest, workload, section)}
    metrics = obj["metrics"]
    if not isinstance(metrics, dict):
        return wrong + ["'metrics' is not an object"]
    for name in sorted(set(metrics) - set(declared)):
        wrong.append(f"metric {name!r} is reported and is not one of this cell's {section} metrics")
    for name, m in declared.items():
        got = metrics.get(name)
        if got is None:
            if name not in may_lack:
                wrong.append(f"metric {name!r} is missing")
            continue
        if not isinstance(got, dict) or set(got) != {"value", "unit"}:
            wrong.append(f"metric {name!r} is not {{value, unit}}")
        elif not _number(got["value"]):
            wrong.append(f"metric {name!r} has no finite number as its value")
        elif got["unit"] != m["unit"]:
            wrong.append(f"metric {name!r} has the unit {got['unit']!r}, not {m['unit']!r}")
        elif (name.endswith("_roofline") or "mfu" in name) and got["value"] > 105:
            wrong.append(f"share {name!r} reads {got['value']}, above 105%")
    if not trace and not any(n != "setup_s" for n in metrics):
        wrong.append("no end-to-end metric beside setup_s")

    dev = obj["device"]
    if not isinstance(dev, dict):
        return wrong + ["'device' is not an object"]
    need = DEVICE_KEYS + (("window_s", "busy_s") if trace else ())
    wrong += [f"device.{k} is missing" for k in need if k not in dev]
    if any(k not in dev for k in need):
        return wrong
    cell = next(w for w in manifest["workloads"] if w["name"] == workload)
    if dev["platform"] != platform:
        wrong.append(f"device.platform is {dev['platform']!r}, not {platform!r}")
    if not isinstance(dev["kind"], str) or not dev["kind"]:
        wrong.append("device.kind is not a name")
    if dev["count"] != cell["chips"]:
        wrong.append(f"device.count is {dev['count']!r}, the cell asks for {cell['chips']}")
    if not isinstance(dev["memory_peak_bytes"], int) or dev["memory_peak_bytes"] <= 0:
        wrong.append("device.memory_peak_bytes is not a positive whole number")
    if trace:
        w, b = dev["window_s"], dev["busy_s"]
        if not (_number(w) and _number(b)):
            wrong.append("device.window_s or device.busy_s is not a number")
        elif not 0 < b <= w:
            wrong.append(f"device.busy_s {b} is not above 0 and at most window_s {w}")
        bd = obj.get("breakdown")
        if bd is not None:
            wrong += _check_breakdown(bd)
    return wrong


def _check_breakdown(bd) -> list:
    if not isinstance(bd, dict) or set(bd) - {"device_ops", "idle_gaps"}:
        return ["'breakdown' has other keys than device_ops and idle_gaps"]
    wrong = []
    for key, rows in bd.items():
        if not isinstance(rows, list) or len(rows) > 10:
            wrong.append(f"breakdown.{key} is not a list of at most 10 entries")
            continue
        for row in rows:
            if not (isinstance(row, list) and len(row) == 2
                    and isinstance(row[0], str) and _number(row[1])):
                wrong.append(f"breakdown.{key} has an entry that is not [name, seconds]")
                break
    return wrong


def check_manifest(manifest: dict) -> list:
    """The name, unit and size rules of ``BENCHMARK.json``."""
    wrong = []
    keys = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    if set(manifest) != keys:
        wrong.append(f"keys {sorted(set(manifest) ^ keys)} are missing or do not belong")
        return wrong
    if len(json.dumps(manifest)) > 64 * 1024:
        wrong.append("the file is over 64 KiB")
    if not (isinstance(manifest["run_seconds"], int) and 1 <= manifest["run_seconds"] <= 51):
        wrong.append("run_seconds is not a whole number from 1 to 51")
    for p in manifest["paths"]:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            wrong.append(f"path {p!r}")
    if not 1 <= len(manifest["command"]) <= 32:
        wrong.append("command has no word or more than 32")

    def names(entries, what, allowed, required):
        seen = set()
        for e in entries:
            if not NAME.match(str(e.get("name", ""))):
                wrong.append(f"{what} name {e.get('name')!r}")
            if e.get("name") in seen:
                wrong.append(f"{what} {e.get('name')!r} appears twice")
            seen.add(e.get("name"))
            if set(e) - allowed or required - set(e):
                wrong.append(f"{what} {e.get('name')!r} has the keys {sorted(e)}")
            lines = [k for k in ("why", "layer") if k in e] + (["source"] if what == "configuration" else [])
            for k in lines:
                text = e[k]
                if not (isinstance(text, str) and 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text):
                    wrong.append(f"{what} {e.get('name')!r}: {k} is not one line of 1 to 200 characters")
        return seen

    ckeys = {"name", "source", "file", "reduced", "why"}
    configs = names(manifest["configs"], "configuration", ckeys, ckeys)
    wkeys = {"name", "config", "traffic", "chips", "why"}
    cells = names(manifest["workloads"], "workload", wkeys, wkeys)
    ekeys = {"name", "unit", "better", "bound", "source"}
    e2e = names(manifest["end_to_end"], "end-to-end metric", ekeys | {"workloads"}, ekeys)
    lkeys = {"name", "unit", "better", "source", "layer", "moves"}
    names(manifest["per_layer"], "per-layer metric", lkeys | {"workloads"}, lkeys)
    if e2e & {m["name"] for m in manifest["per_layer"]}:
        wrong.append("a per-layer metric has the name of an end-to-end metric")
    for c in manifest["configs"]:
        if not any(c["file"].startswith(p.rstrip("/") + "/") for p in manifest["paths"]):
            wrong.append(f"configuration file {c['file']!r} is outside paths")
        if len(c["reduced"]) > 16 or not all(NAME.match(k) for k in c["reduced"]):
            wrong.append(f"configuration {c['name']!r}: reduced")
    pairs = set()
    for w in manifest["workloads"]:
        if w["config"] not in configs:
            wrong.append(f"workload {w['name']!r} names no configuration")
        if not NAME.match(str(w["traffic"])) or w["chips"] not in (1, 4):
            wrong.append(f"workload {w['name']!r}: traffic or chips")
        if (w["config"], w["traffic"]) in pairs:
            wrong.append(f"workload {w['name']!r} repeats a pair of configuration and traffic")
        pairs.add((w["config"], w["traffic"]))
    if {c["name"] for c in manifest["configs"]} - {w["config"] for w in manifest["workloads"]}:
        wrong.append("a configuration is used by no cell")
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    if four > max(1, len(manifest["workloads"]) // 4):
        wrong.append("more than a quarter of the cells ask for 4 chips")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not UNIT.match(str(m.get("unit", ""))):
            wrong.append(f"metric {m['name']!r}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            wrong.append(f"metric {m['name']!r}: better")
        if m.get("source") not in SOURCES:
            wrong.append(f"metric {m['name']!r}: source")
        if set(m.get("workloads", [])) - cells:
            wrong.append(f"metric {m['name']!r} lists a cell that is not there")
    for m in manifest["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            wrong.append(f"end-to-end metric {m['name']!r} is not taken by the benchmark itself")
        if not (_number(m.get("bound")) and 0 < m["bound"] <= 0.1):
            wrong.append(f"end-to-end metric {m['name']!r}: bound")
    if "setup_s" not in e2e:
        wrong.append("no setup_s")
    for m in manifest["per_layer"]:
        if m.get("moves") not in e2e:
            wrong.append(f"per-layer metric {m['name']!r} moves no end-to-end metric")
            continue
        for cell in m.get("workloads", cells):
            if m["moves"] not in {x["name"] for x in metrics_for(manifest, cell, "end_to_end")}:
                wrong.append(f"per-layer metric {m['name']!r}: cell {cell!r} does not report {m['moves']!r}")
    for cell in cells:
        if len(metrics_for(manifest, cell, "end_to_end")) < 2 or not metrics_for(manifest, cell, "per_layer"):
            wrong.append(f"cell {cell!r} lacks an end-to-end metric beside setup_s, or a per-layer metric")
    return wrong
