"""``ray-tpu`` CLI.

Reference: python/ray/scripts/scripts.py (:2548-2579 — start/stop/status/
submit/memory/timeline/logs/microbenchmark). argparse instead of click;
subcommands connect to the running cluster via the address file
``<temp_dir>/ray_current_cluster`` that ``start --head`` writes.

Usage:
  ray-tpu start --head [--num-cpus N] [--resources JSON] [--block]
  ray-tpu start --address HOST:PORT [--num-cpus N]   # join as a node
  ray-tpu stop
  ray-tpu status
  ray-tpu submit -- python my_script.py              # run as a job
  ray-tpu job list | job logs ID | job stop ID
  ray-tpu summary tasks|actors|objects|memory|lifecycle|rl|train|profiling|errors
  ray-tpu timeline [--output FILE]
  ray-tpu profile stacks|cpu|device|incidents|captures [...]
  ray-tpu memory [--node N] [--leaks] [--limit K] [--offline] [--json]
  ray-tpu logs [FILENAME] [--node N] [--task T] [--actor A] [--grep RE]
               [--err] [--tail N] [--follow] [--offline]
  ray-tpu microbenchmark
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _addr_file() -> str:
    from ray_tpu.config import get_config

    return os.path.join(get_config().temp_dir, "ray_current_cluster")


def _connect():
    import ray_tpu

    ray_tpu.init(address="auto")
    return ray_tpu


# ---------------------------------------------------------------------------
def cmd_start(args):
    from ray_tpu.core import api

    if args.head:
        resources = json.loads(args.resources) if args.resources else {}
        resources.setdefault("CPU", args.num_cpus or os.cpu_count() or 1)
        if args.num_tpus:
            resources["TPU"] = args.num_tpus
        address, proc, session_dir = api._start_controller(resources, {}, owned=False)
        os.makedirs(os.path.dirname(_addr_file()), exist_ok=True)
        with open(_addr_file(), "w") as f:
            f.write(address)
        print(f"started head at {address} (session: {session_dir})")
        print(f"connect with ray_tpu.init(address='auto') or --address {address}")
        if args.block:
            try:
                proc.wait()
            except KeyboardInterrupt:
                pass
        return 0
    if not args.address:
        print("either --head or --address is required", file=sys.stderr)
        return 1
    import subprocess

    from ray_tpu.core.node_agent import child_env

    res = json.loads(args.resources) if args.resources else {}
    res.setdefault("CPU", args.num_cpus or os.cpu_count() or 1)
    if args.num_tpus:
        res["TPU"] = args.num_tpus
    cmd = [
        sys.executable,
        "-m",
        "ray_tpu.core.node_agent",
        "--controller",
        args.address,
        "--session-dir",
        args.session_dir or "/tmp/ray_tpu/cli_node",
        "--resources",
        json.dumps(res),
    ]
    os.makedirs(os.path.join(args.session_dir or "/tmp/ray_tpu/cli_node", "logs"), exist_ok=True)
    proc = subprocess.Popen(cmd, env=child_env())
    print(f"node agent joining {args.address} (pid {proc.pid})")
    if args.block:
        proc.wait()
    return 0


def cmd_stop(args):
    import ray_tpu

    try:
        ray_tpu.init(address="auto")
    except ConnectionError as e:
        print(str(e), file=sys.stderr)
        return 1
    from ray_tpu.core.api import _require_worker
    from ray_tpu.core.cluster_utils import end_cluster

    end_cluster(_require_worker())
    try:
        os.unlink(_addr_file())
    except FileNotFoundError:
        pass
    print("cluster stopped")
    return 0


def _gb(n) -> str:
    return f"{(n or 0) / (1 << 30):.1f}"


def _render_status(summary: dict, total: dict, avail: dict, out=print):
    """The `ray-tpu status` cluster view (reference: `ray status` +
    the dashboard's cluster page): resource availability, per-node host/
    store/HBM/compile telemetry, and the top-skew collectives table."""
    nodes = summary.get("nodes", {})
    totals = summary.get("totals", {})
    alive = sum(1 for n in nodes.values() if n.get("state") == "ALIVE")
    out(f"nodes: {len(nodes)} ({alive} alive)")
    for k in sorted(total):
        out(f"  {k}: {avail.get(k, 0):g}/{total[k]:g} available")
    out(
        f"host memory: {_gb(totals.get('mem_used_bytes'))}/"
        f"{_gb(totals.get('mem_total_bytes'))} GB  "
        f"object store: {_gb(totals.get('object_store_used'))}/"
        f"{_gb(totals.get('object_store_capacity'))} GB"
    )
    if totals.get("num_devices"):
        out(
            f"device HBM: {_gb(totals.get('hbm_used_bytes'))}/"
            f"{_gb(totals.get('hbm_limit_bytes'))} GB over "
            f"{totals['num_devices']} device(s) "
            f"(peak {_gb(totals.get('hbm_peak_bytes'))} GB)"
        )
    out("")
    hdr = f"{'node':<14}{'host':<16}{'cpu%':>6}{'mem GB':>12}{'store GB':>11}{'compiles/min':>14}  devices (HBM used/limit GB)"
    out(hdr)
    for nid, row in nodes.items():
        host = row.get("host", {})
        store = row.get("object_store", {})
        comp = row.get("compile", {})
        devs = row.get("devices", [])
        dev_str = " ".join(
            f"{d['id']}:{_gb(d['bytes_in_use'])}/{_gb(d['bytes_limit'])}"
            for d in devs
        ) or "-"
        name = nid[:10] + ("*" if row.get("is_head") else "")
        mem = f"{_gb(host.get('mem_used_bytes'))}/{_gb(host.get('mem_total_bytes'))}"
        st = f"{_gb(store.get('used'))}/{_gb(store.get('capacity'))}"
        out(
            f"{name:<14}{row.get('hostname', '?')[:15]:<16}"
            f"{host.get('cpu_percent', 0):>6.1f}{mem:>12}{st:>11}"
            f"{comp.get('compiles_per_min', 0):>14.1f}  {dev_str}"
        )
        for storm in comp.get("active_storms", ()):
            out(f"    !! recompilation storm: {storm}")
    skew = totals.get("collective_skew_ms") or []
    if skew:
        out("")
        out("top-skew collectives (max-min last op latency per ring):")
        out(f"  {'group':<16}{'op':<14}{'skew ms':>9}{'max ms':>9}{'min ms':>9}  slowest rank")
        for r in skew[:8]:
            out(
                f"  {r['group'][:15]:<16}{r['op']:<14}{r['skew_ms']:>9.2f}"
                f"{r['max_ms']:>9.2f}{r['min_ms']:>9.2f}  {r['slowest_rank']}"
            )


def _status_fixture() -> tuple:
    """Canned summarize_resources()-shaped data for `status --offline`:
    exercises every rendering path (devices, storms, skew) with no
    cluster — the tier-1 smoke that keeps the view from rotting."""
    summary = {
        "nodes": {
            "aabbccddee00": {
                "hostname": "tpu-host-0", "is_head": True, "state": "ALIVE",
                "num_workers": 4,
                "host": {"cpu_percent": 37.5, "mem_used_bytes": 9 << 30,
                         "mem_total_bytes": 64 << 30, "load_1m": 2.5},
                "object_store": {"used": 1 << 28, "capacity": 2 << 30,
                                 "num_objects": 12, "num_spilled": 0},
                "resources": {"total": {"CPU": 8, "TPU": 4},
                              "available": {"CPU": 6, "TPU": 2}},
                "telemetry_age_s": 1.2,
                "devices": [
                    {"id": i, "platform": "tpu", "kind": "TPU v5e", "pid": 1234,
                     "bytes_in_use": (11 + i) << 30,
                     "peak_bytes_in_use": (12 + i) << 30,
                     "bytes_limit": 16 << 30}
                    for i in range(2)
                ],
                "compile": {"compiles": 42, "compile_seconds": 31.5,
                            "compiles_per_min": 6.0, "storms_total": 1,
                            "active_storms": ["decode_step"]},
            },
        },
        "totals": {
            "mem_used_bytes": 9 << 30, "mem_total_bytes": 64 << 30,
            "hbm_used_bytes": 23 << 30, "hbm_limit_bytes": 32 << 30,
            "hbm_peak_bytes": 25 << 30, "num_devices": 2,
            "object_store_used": 1 << 28, "object_store_capacity": 2 << 30,
            "compiles": 42, "compile_seconds": 31.5,
            "active_storms": ["decode_step"],
            "collective_skew_ms": [
                {"group": "train-ring", "op": "allreduce", "skew_ms": 18.4,
                 "max_ms": 42.1, "min_ms": 23.7, "slowest_rank": "3",
                 "ranks": 8},
            ],
        },
    }
    total = {"CPU": 8.0, "TPU": 4.0}
    avail = {"CPU": 6.0, "TPU": 2.0}
    return summary, total, avail


def cmd_status(args):
    if args.offline:
        summary, total, avail = _status_fixture()
        _render_status(summary, total, avail)
        return 0
    rt = _connect()
    from ray_tpu.util import state as state_api

    total = rt.cluster_resources()
    avail = rt.available_resources()
    _render_status(state_api.summarize_resources(), total, avail)
    return 0


def cmd_dashboard(args):
    """Print the dashboard URL (reference: `ray dashboard`)."""
    _connect()
    from ray_tpu.util import state as state_api

    url = state_api.dashboard_url()
    if url is None:
        print("dashboard disabled (dashboard_port=-1)")
        return 1
    print(url)
    return 0


def cmd_submit(args):
    from ray_tpu.job import JobSubmissionClient

    _connect()
    client = JobSubmissionClient()
    entrypoint = " ".join(args.entrypoint)
    job_id = client.submit_job(entrypoint=entrypoint)
    print(f"submitted {job_id}")
    if args.no_wait:
        return 0
    status = client.wait_until_finished(job_id, timeout=args.timeout)
    print(client.get_job_logs(job_id), end="")
    print(f"job {job_id}: {status}")
    return 0 if status == "SUCCEEDED" else 1


def cmd_job(args):
    from ray_tpu.job import JobSubmissionClient

    _connect()
    client = JobSubmissionClient()
    if args.action == "list":
        for j in client.list_jobs():
            print(f"{j['job_id']}  {j['status']:10s}  {j['entrypoint']}")
    elif args.action == "logs":
        print(client.get_job_logs(args.job_id), end="")
    elif args.action == "stop":
        print(client.stop_job(args.job_id))
    return 0


def cmd_summary(args):
    from ray_tpu.util import state

    _connect()
    fn = {
        "tasks": state.summarize_tasks,
        "actors": state.summarize_actors,
        "objects": state.summarize_objects,
        "memory": state.summarize_memory,
        "lifecycle": state.summarize_lifecycle,
        "rl": state.summarize_rl,
        "train": state.summarize_train,
        "profiling": state.summarize_profiling,
        "errors": state.summarize_errors,
    }[args.what]
    print(json.dumps(fn(), indent=2))
    return 0


def cmd_timeline(args):
    from ray_tpu.util import state

    _connect()
    out = args.output or f"timeline-{int(time.time())}.json"
    trace = state.timeline_chrome(
        out,
        include_lifecycle=not args.no_lifecycle,
        include_spans=not args.no_spans,
        include_device=not args.no_device,
    )
    by_cat = {}
    for ev in trace:
        cat = ev.get("cat", "span")
        by_cat[cat] = by_cat.get(cat, 0) + 1
    detail = ", ".join(f"{n} {cat}" for cat, n in sorted(by_cat.items()))
    print(
        f"wrote {len(trace)} events ({detail or 'none'}) to {out} "
        "(load in chrome://tracing or perfetto)"
    )
    return 0


def cmd_stack(args):
    """Live stacks of every cluster process (reference: `ray stack`)."""
    from ray_tpu.util import state

    _connect()
    dumps = state.get_stack_traces(timeout_s=args.timeout)
    for name in sorted(dumps):
        print(f"===== {name} =====")
        print(dumps[name])
    return 0


def _render_memory(summary: dict, leaks_only: bool = False, out=print):
    """The `ray-tpu memory` census view (reference: `ray memory` + the
    dashboard memory view): per-node store occupancy, open objects
    grouped by creation call-site across all tiers, process censuses,
    and the leak detector's flags."""
    totals = summary.get("totals", {})
    leaks = summary.get("leaks", [])
    if not leaks_only:
        out(
            f"objects: {totals.get('objects', 0)}  "
            f"inline {_gb(totals.get('inline_bytes'))} GB  "
            f"shm {_gb(totals.get('shm_bytes'))} GB  "
            f"spilled {_gb(totals.get('spilled_bytes'))} GB"
        )
        out(
            f"open local refs: {totals.get('open_refs', 0)}  "
            f"zero-copy pins: {totals.get('pins', 0)} "
            f"({_gb(totals.get('pin_bytes'))} GB)  "
            f"memory-store entries: {totals.get('memory_store_entries', 0)}"
        )
        out("")
        out(
            f"{'node':<14}{'store GB':>12}{'objects':>9}{'spilled GB':>12}"
            f"{'pins':>6}{'deferred':>10}"
        )
        for nid, store in summary.get("nodes", {}).items():
            st = f"{_gb(store.get('used'))}/{_gb(store.get('capacity'))}"
            out(
                f"{nid[:12]:<14}{st:>12}{store.get('num_objects', 0):>9}"
                f"{_gb(store.get('spilled_bytes')):>12}"
                f"{store.get('pinned_slots', 0):>6}"
                f"{store.get('deferred_deletes', 0):>10}"
            )
        out("")
        rows = summary.get("by_callsite", {})
        if rows:
            out("open objects by creation call-site"
                + (" (truncated)" if summary.get("truncated") else "") + ":")
            out(
                f"  {'objects':>8}{'refs':>7}{'pins':>6}{'MB':>10}"
                f"{'spilled MB':>12}  call-site"
            )
            for site, r in rows.items():
                out(
                    f"  {r.get('objects', 0):>8}{r.get('local_refs', 0):>7}"
                    f"{r.get('pins', 0):>6}"
                    f"{(r.get('bytes', 0) or 0) / (1 << 20):>10.1f}"
                    f"{(r.get('spilled_bytes', 0) or 0) / (1 << 20):>12.1f}"
                    f"  {site}"
                )
        procs = summary.get("procs", {})
        if procs:
            out("")
            out("per-process census:")
            for name, p in sorted(procs.items()):
                if p.get("error"):
                    out(f"  {name}: !! {p['error']}")
                    continue
                ms = p.get("memory_store", {})
                pins = p.get("pins", {})
                out(
                    f"  {name}: {p.get('open_refs', 0)} open refs, "
                    f"{ms.get('entries', 0)} memory-store entries "
                    f"({(ms.get('ready_bytes', 0) or 0) / (1 << 20):.1f} MB), "
                    f"{pins.get('count', 0)} pins"
                )
    if leaks:
        out("")
        out("!! leak suspects (open refs rising monotonically):")
        for r in leaks:
            out(
                f"  {r.get('count', 0):>7} open (+{r.get('growth', 0)})  "
                f"{r.get('callsite', '?')}"
            )
    elif leaks_only:
        out("no leak suspects flagged")


def _memory_fixture() -> dict:
    """Canned summarize_memory()-shaped data for `memory --offline`:
    exercises every rendering path (tiers, pins, procs, leaks) with no
    cluster — the tier-1 smoke that keeps the view from rotting."""
    return {
        "totals": {
            "objects": 1312, "inline_bytes": 3 << 20,
            "shm_bytes": 6 << 30, "spilled_bytes": 2 << 30,
            "open_refs": 1840, "pins": 3, "pin_bytes": 192 << 20,
            "memory_store_entries": 24, "memory_store_bytes": 1 << 20,
        },
        "nodes": {
            "aabbccddee00": {
                "used": 5 << 30, "capacity": 8 << 30, "num_objects": 900,
                "num_spilled": 120, "spilled_bytes": 2 << 30,
                "pinned_slots": 3, "pinned_bytes": 192 << 20,
                "deferred_deletes": 2, "spill_ops": 804,
            },
            "ffee00112233": {
                "used": 1 << 30, "capacity": 8 << 30, "num_objects": 412,
                "num_spilled": 0, "spilled_bytes": 0,
                "pinned_slots": 0, "pinned_bytes": 0,
                "deferred_deletes": 0, "spill_ops": 0,
            },
        },
        "by_callsite": {
            "app/train.py:91:load_shards": {
                "objects": 800, "bytes": 5 << 30, "spilled_bytes": 2 << 30,
                "local_refs": 820, "pins": 3,
                "tiers": {"shm": 680, "spilled": 120},
            },
            "(task) preprocess": {
                "objects": 400, "bytes": 1 << 30, "spilled_bytes": 0,
                "local_refs": 400, "pins": 0, "tiers": {"shm": 400},
            },
            "app/eval.py:12:collect": {
                "objects": 112, "bytes": 3 << 20, "spilled_bytes": 0,
                "local_refs": 620, "pins": 0, "tiers": {"inline": 112},
            },
        },
        "truncated": False,
        "procs": {
            "driver:0": {
                "open_refs": 1220,
                "memory_store": {"entries": 24, "ready_bytes": 1 << 20,
                                 "pending": 2, "shm": 4},
                "pins": {"count": 0, "bytes": 0},
            },
            "worker:aaaa0000:pid201": {
                "open_refs": 620,
                "memory_store": {"entries": 0, "ready_bytes": 0},
                "pins": {"count": 3, "bytes": 192 << 20},
            },
            "worker:bbbb0000:pid202": {"error": "timed out"},
        },
        "leaks": [
            {"callsite": "app/eval.py:12:collect", "count": 620,
             "growth": 480, "first_flagged": 0.0},
        ],
    }


def cmd_memory(args):
    if args.offline:
        _render_memory(_memory_fixture(), leaks_only=args.leaks)
        return 0
    from ray_tpu.util import state

    _connect()
    summary = state.summarize_memory(limit=args.limit, node=args.node)
    if args.json:
        print(json.dumps(summary, indent=2, default=str))
        return 0
    _render_memory(summary, leaks_only=args.leaks)
    return 0


def _health_fixture() -> dict:
    """Canned summarize_health()-shaped data for `health --offline`:
    exercises every rendering path (actuator table, outcomes, actions,
    avoids, remote actions) with no cluster — the tier-1 smoke that
    keeps the view from rotting."""
    return {
        "enabled": True,
        "max_actions_per_min": 6,
        "actuators": [
            {"name": "leak_backpressure", "triggers": ["memory_leak"],
             "cooldown_s": 30.0, "dry_run": False},
            {"name": "pressure_spill", "triggers": ["memory_pressure"],
             "cooldown_s": 30.0, "dry_run": False},
            {"name": "storm_pin", "triggers": ["recompile_storm"],
             "cooldown_s": 30.0, "dry_run": True},
            {"name": "spike_quarantine", "triggers": ["error_spike"],
             "cooldown_s": 30.0, "dry_run": False},
        ],
        "signals": {"memory_pressure": 4, "error_spike": 1},
        "outcomes": {
            "pressure_spill": {"acted": 2, "cooldown": 2},
            "spike_quarantine": {"acted": 1},
            "storm_pin": {"dry_run": 1},
        },
        "actions_recent": [
            {"id": "act-1-100", "ts": 1700000000.0,
             "actuator": "pressure_spill", "trigger": "memory_pressure",
             "key": "aabbccddee00", "target": "aabbccddee00",
             "dry_run": False, "outcome": "acted",
             "detail": {"reason": "occupancy", "spilled": 41,
                        "freed_bytes": 2 << 30}},
            {"id": "act-2-250", "ts": 1700000012.5,
             "actuator": "spike_quarantine", "trigger": "error_spike",
             "key": "ffee00112233", "target": "ffee00112233",
             "dry_run": False, "outcome": "acted",
             "detail": {"signature": "ValueError@Loader.fetch",
                        "quarantine_s": 60.0}},
            {"id": "act-3-311", "ts": 1700000031.1,
             "actuator": "storm_pin", "trigger": "recompile_storm",
             "key": "aabbccddee00/pid201:train_step",
             "target": "aabbccddee00/pid201", "dry_run": True,
             "outcome": "dry_run", "detail": {"function": "train_step"}},
        ],
        "avoids": {
            "ffee00112233": {"mode": "quarantine", "remaining_s": 41.2},
        },
        "remote_actions": [
            {"ts": 1700000044.0, "kind": "action", "id": "padr-1",
             "state": "FINISHED", "actuator": "podracer_cadence",
             "trigger": "policy_lag", "target": "learner",
             "outcome": "acted", "remote": True},
        ],
    }


def _render_health(summary: dict, out=print):
    """The `ray-tpu health` self-healing view: actuator configs, live
    avoids, and the recent trigger → action → outcome audit."""
    if not summary.get("enabled", False):
        out("health actuators disabled (health_actuators=False)")
        return
    out(f"{'actuator':<20}{'triggers':<22}{'cooldown':>9}{'dry-run':>9}  outcomes")
    outcomes = summary.get("outcomes", {})
    for a in summary.get("actuators", []):
        tally = outcomes.get(a["name"], {})
        tstr = " ".join(f"{k}:{n}" for k, n in sorted(tally.items())) or "-"
        out(
            f"{a['name']:<20}{','.join(a['triggers']):<22}"
            f"{a['cooldown_s']:>8.0f}s{('yes' if a['dry_run'] else 'no'):>9}  {tstr}"
        )
    sig = summary.get("signals", {})
    if sig:
        out("")
        out("signals seen: " + "  ".join(f"{k}={n}" for k, n in sorted(sig.items())))
    avoids = summary.get("avoids", {})
    if avoids:
        out("")
        out("active avoids:")
        for nid, row in avoids.items():
            out(f"  {nid}  {row['mode']:<11} {row['remaining_s']:.0f}s remaining")
    rows = summary.get("actions_recent", []) + summary.get("remote_actions", [])
    if rows:
        out("")
        out("recent actions:")
        for r in rows:
            det = r.get("detail", {})
            extra = " ".join(
                f"{k}={v}" for k, v in sorted(det.items()) if k != "signature"
            )
            out(
                f"  {r.get('actuator', '?'):<20}{r.get('trigger', '?'):<18}"
                f"→ {r.get('target', '?')[:24]:<26}{r.get('outcome', '?'):<10}"
                + (f" {extra}" if extra else "")
            )
    else:
        out("")
        out("no actions taken")


def cmd_health(args):
    if args.offline:
        summary = _health_fixture()
    else:
        from ray_tpu.util import state

        _connect()
        summary = state.summarize_health(limit=args.limit)
    if args.json:
        print(json.dumps(summary, indent=2, default=str))
        return 0
    _render_health(summary)
    return 0


def cmd_drain_node(args):
    import ray_tpu

    ray_tpu.init(address="auto")
    ray_tpu.drain_node(args.node_id, timeout_s=args.timeout)
    print(f"draining {args.node_id}")


def _logs_fixture() -> list:
    """Canned search_logs()-shaped records for `logs --offline`:
    exercises the record renderer (severity, node/worker attribution,
    task tags, raw-grep fallback rows) with no cluster — the tier-1
    smoke that keeps the view from rotting."""
    return [
        {"ts": 1700000000.103, "sev": "INFO", "node": "aabbccddee00",
         "worker": "aaaa0000", "pid": 201, "task": "train_loop",
         "task_id": "11" * 16, "actor_id": None,
         "msg": "step 41 loss 2.31", "file": "worker-aaaa0000.jsonl",
         "line": 7},
        {"ts": 1700000000.250, "sev": "STDOUT", "node": "aabbccddee00",
         "worker": "aaaa0000", "pid": 201, "task": "train_loop",
         "task_id": "11" * 16, "actor_id": None,
         "msg": "checkpoint saved to /tmp/ck-41",
         "file": "worker-aaaa0000.jsonl", "line": 8},
        {"ts": 1700000000.912, "sev": "ERROR", "node": "ffee00112233",
         "worker": "bbbb0000", "pid": 202, "task": "Loader.fetch",
         "task_id": "22" * 16, "actor_id": "33" * 16,
         "exc": "ValueError",
         "msg": "task Loader.fetch failed: Traceback (most recent call "
                "last):\n  ...\nValueError: bad shard 7",
         "file": "worker-bbbb0000.jsonl", "line": 3},
        {"ts": None, "sev": None, "node": None, "worker": None,
         "msg": "[controller] WARNING lease queue deep",
         "file": "controller.log", "line": 4021},
    ]


def _render_log_records(rows: list, out=print) -> int:
    from ray_tpu.core.log_plane import format_record

    for rec in rows:
        out(format_record(rec))
    return 0


def cmd_logs(args):
    """``ray-tpu logs``: list files, fetch one, search with attribution
    filters, or live-follow (reference: `ray logs` + the StateHead logs
    API; `--task/--actor/--grep/--err` need the structured sidecars the
    log plane writes — core/log_plane.py)."""
    severity = "ERROR" if args.err else args.severity
    filtered = any((args.grep, args.task, args.actor, severity))
    if args.offline:
        from ray_tpu.core.log_plane import match_record

        rows = [
            r for r in _logs_fixture()
            if match_record(r, pattern=args.grep, severity=severity,
                            task=args.task, actor=args.actor,
                            node=args.node)
        ]
        return _render_log_records(rows)
    from ray_tpu.util import state

    _connect()
    if args.follow:
        import queue as _q

        records: "_q.Queue" = _q.Queue()
        stop = state.follow_logs(
            records.put, pattern=args.grep, severity=severity,
            task=args.task, actor=args.actor, node=args.node,
        )
        print("following cluster logs (ctrl-c to stop)...", file=sys.stderr)
        try:
            while True:
                _render_log_records(records.get())
        except KeyboardInterrupt:
            stop()
            return 0
    if args.filename and not filtered:
        print(state.get_log(args.filename, tail=args.tail, node=args.node),
              end="")
        return 0
    if filtered:
        rows = state.search_logs(
            args.grep, severity=severity,
            task=args.task, actor=args.actor, node=args.node,
            limit=args.tail,
        )
        return _render_log_records(rows)
    for row in state.list_log_files(node=args.node):
        mark = "*" if row.get("structured") else " "
        node = (row.get("node") or "?")[:12]
        print(f"{row['filename']:<40} {mark} {row['size']:>12}  {node}")
    return 0


def cmd_metrics(args):
    """``ray-tpu metrics dashboard``: importable Grafana dashboard JSON
    generated from the LIVE metric registry (reference:
    dashboard/modules/metrics/grafana_dashboard_factory.py)."""
    _connect()
    from ray_tpu.util.grafana import dashboard_json

    text = dashboard_json()
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
        print(f"wrote {args.out}")
    else:
        print(text)
    return 0


_PROFILE_ACTIONS = ("stacks", "cpu", "device", "incidents", "captures")


def _profile_stacks_fixture() -> dict:
    """Canned fan-out dumps for `profile stacks --offline`: exercises the
    merge/dedup/held-lock rendering with no cluster (the tier-1 smoke
    that keeps the report from rotting)."""
    idle = [
        {"file": "/usr/lib/python3.10/threading.py", "line": 324,
         "func": "wait"},
    ]
    busy = [
        {"file": "/app/train.py", "line": 91, "func": "train_loop"},
        {"file": "/app/train.py", "line": 44, "func": "loss_fn"},
    ]

    def dump(proc, pid, threads):
        return {"process": proc, "pid": pid, "ts": 0.0, "threads": threads}

    return {
        "controller": dump("controller", 100, [
            {"ident": 1, "name": "MainThread", "daemon": False, "task": None,
             "idle": True, "frames": idle, "held_locks": []},
        ]),
        "worker:aaaa0000:pid201": dump("worker-aaaa0000", 201, [
            {"ident": 2, "name": "task-exec", "daemon": True,
             "task": "train_loop", "idle": False, "frames": busy,
             "held_locks": [{"lock": "Lock@train.py:12",
                             "acquired_at": "train.py:90",
                             "held_ms": 1503.2}]},
            {"ident": 3, "name": "metrics-flush", "daemon": True,
             "task": None, "idle": True, "frames": idle, "held_locks": []},
        ]),
        "worker:bbbb0000:pid202": dump("worker-bbbb0000", 202, [
            {"ident": 2, "name": "task-exec", "daemon": True, "task": None,
             "idle": True, "frames": idle, "held_locks": []},
        ]),
        "agent:cccc0000": "<unavailable: timed out>",
    }


def _profile_cpu_fixture() -> dict:
    from ray_tpu.util import profiling

    results = {
        "worker:aaaa0000:pid201": {
            "samples": 480, "duration_s": 5.0,
            "task_cpu_ms": {"train_loop": 4200.0},
            "stacks": [
                {"thread": "task-exec", "task": "train_loop", "count": 420,
                 "busy": 420, "frames": ["train.train_loop", "train.loss_fn"]},
                {"thread": "metrics-flush", "task": None, "count": 60,
                 "busy": 0, "frames": ["threading.wait"]},
            ],
        },
        "controller": {
            "samples": 500, "duration_s": 5.0, "task_cpu_ms": {},
            "stacks": [
                {"thread": "MainThread", "task": None, "count": 500,
                 "busy": 120, "frames": ["controller.run", "selectors.select"]},
            ],
        },
    }
    merged = profiling.merge_cpu_results(results)
    merged.update(hz=100.0, duration_s=5.0, ms_per_sample=10.0)
    return merged


def _print_cpu_profile(res: dict, args) -> int:
    from ray_tpu.util import profiling

    print(
        f"{res.get('samples', 0)} samples @ {res.get('hz', '?')} Hz over "
        f"{res.get('duration_s', '?')}s from {len(res.get('procs', {}))} "
        "process(es)"
    )
    task_cpu = res.get("task_cpu_ms", {})
    if task_cpu:
        print("task CPU attribution (sampled busy ms):")
        for name, ms in list(task_cpu.items())[:15]:
            print(f"  {ms:>10.1f} ms  {name}")
    for proc, err in res.get("errors", {}).items():
        print(f"!! {proc}: {err}")
    if args.out:
        if args.format == "collapsed":
            text = profiling.collapsed_text(res)
        else:
            text = json.dumps(profiling.speedscope_json(
                res, ms_per_sample=res.get("ms_per_sample", 10.0)
            ))
        with open(args.out, "w") as f:
            f.write(text)
        print(f"wrote {args.format} profile to {args.out} "
              "(collapsed: flamegraph.pl; speedscope: speedscope.app)")
    else:
        top = sorted(
            res.get("collapsed", {}).items(), key=lambda kv: -kv[1]
        )[:15]
        if top:
            print("top stacks (collapsed; use --out for the full profile):")
            for line, n in top:
                print(f"  {n:>6}  {line[:160]}")
    return 0


def _profile_captures(args):
    """Legacy list/fetch of jax.profiler captures (both per-task
    runtime_env={'jax_profiler': True} and on-demand device traces)."""
    from ray_tpu.util import state

    _connect()
    if args.target_id:
        info = state.get_profile(args.target_id)
        print(json.dumps({k: v for k, v in info.items() if k != "files"}, indent=1))
        for f in info["files"]:
            print(f)
    else:
        rows = state.list_profiles()
        if not rows:
            print("no profiles captured (use runtime_env={'jax_profiler': "
                  "True} or `ray-tpu profile device`)")
        for r in rows:
            print(f"{r['id']}  task={r.get('task_id', '?')[:12]}  "
                  f"dur={r.get('duration_s', '?')}s  {r['path']}")
    return 0


def cmd_profile(args):
    """On-demand distributed profiling (reference: `ray stack` + the
    dashboard reporter's per-worker py-spy stack/CPU-profile endpoints):

      ray-tpu profile stacks [--node N | --actor ID]
      ray-tpu profile cpu --duration 10 [--hz 100] [--out f --format ...]
      ray-tpu profile device [--workers W1,W2] --duration 5
      ray-tpu profile incidents [ID]
      ray-tpu profile captures [ID]        (also: legacy `profile [ID]`)
    """
    from ray_tpu.util import profiling

    action = args.action
    if action not in _PROFILE_ACTIONS:
        # legacy invocation: `ray-tpu profile [capture_id]`
        args.target_id = action
        return _profile_captures(args)
    if action == "stacks":
        if args.offline:
            print(profiling.merge_stack_dumps(_profile_stacks_fixture()))
            return 0
        from ray_tpu.util import state

        _connect()
        res = state.profile_stacks(
            node=args.node, actor=args.actor, timeout_s=args.timeout
        )
        print(res["merged"])
        return 0
    if action == "cpu":
        if args.offline:
            return _print_cpu_profile(_profile_cpu_fixture(), args)
        from ray_tpu.util import state

        _connect()
        workers = args.workers.split(",") if args.workers else None
        res = state.profile_cpu(
            duration_s=args.duration, hz=args.hz, node=args.node,
            workers=workers,
        )
        return _print_cpu_profile(res, args)
    if action == "device":
        from ray_tpu.util import state

        _connect()
        workers = args.workers.split(",") if args.workers else None
        res = state.profile_device(workers=workers, duration_s=args.duration)
        print(f"capture {res['capture']} ({res['duration_s']}s):")
        ok = 0
        for name, r in sorted(res.get("workers", {}).items()):
            if r.get("ok"):
                ok += 1
                print(f"  {name}: {r.get('dir')}")
            else:
                print(f"  {name}: FAILED — {r.get('error')}")
        print(f"{ok} capture(s); list with `ray-tpu profile captures`, "
              "merge into one trace with `ray-tpu timeline`")
        return 0 if ok or not res.get("workers") else 1
    if action == "incidents":
        from ray_tpu.util import state

        _connect()
        if args.target_id:
            info = state.get_incident(args.target_id)
            print(json.dumps(
                {k: v for k, v in info.items() if k != "contents"}, indent=1
            ))
            for name, content in info.get("contents", {}).items():
                print(f"===== {name} =====")
                print(content)
        else:
            rows = state.list_incidents()
            if not rows:
                print("no incidents captured")
            for r in rows:
                print(f"{r['id']}  trigger={r.get('trigger', '?')}  "
                      f"proc={r.get('process', '?')}  {r['path']}")
        return 0
    return _profile_captures(args)


def cmd_microbenchmark(args):
    """Core perf smoke (reference: `ray microbenchmark`,
    python/ray/_private/ray_perf.py:93)."""
    import numpy as np

    import ray_tpu

    ray_tpu.init(num_cpus=4)
    results = {}

    @ray_tpu.remote
    def noop():
        return 0

    # warm the worker pool
    ray_tpu.get([noop.remote() for _ in range(20)])
    t0 = time.perf_counter()
    n = 300
    ray_tpu.get([noop.remote() for _ in range(n)])
    results["tasks_per_s"] = n / (time.perf_counter() - t0)

    @ray_tpu.remote
    class A:
        def ping(self):
            return 0

    a = A.remote()
    ray_tpu.wait_actor_ready(a)
    t0 = time.perf_counter()
    for _ in range(100):
        ray_tpu.get(a.ping.remote())
    results["sync_actor_calls_per_s"] = 100 / (time.perf_counter() - t0)

    t0 = time.perf_counter()
    ray_tpu.get([a.ping.remote() for _ in range(500)])
    results["async_actor_calls_per_s"] = 500 / (time.perf_counter() - t0)

    data = np.zeros(16 * 1024 * 1024, dtype=np.uint8)
    t0 = time.perf_counter()
    for _ in range(10):
        ref = ray_tpu.put(data)
        ray_tpu.get(ref)
    gib = 10 * data.nbytes / (1 << 30)
    results["put_get_GiB_per_s"] = gib / (time.perf_counter() - t0)

    ray_tpu.shutdown()
    print(json.dumps({k: round(v, 1) for k, v in results.items()}, indent=2))
    return 0


# ---------------------------------------------------------------------------


def cmd_lint(args):
    """Project-aware static analysis (see ray_tpu/tools/lint/)."""
    from ray_tpu.tools.lint.cli import cmd_lint as run

    return run(args)


def cmd_sanitize(args):
    """Concurrency sanitizer gate (see ray_tpu/tools/sanitizer/)."""
    from ray_tpu.tools.sanitizer.cli import cmd_sanitize as run

    return run(args)


def cmd_up(args):
    from ray_tpu.autoscaler.commands import create_or_update_cluster

    state = create_or_update_cluster(args.cluster_config)
    print(f"cluster {state['cluster_name']} up at {state['address']}")
    print(f"session: {state['session_dir']}")
    print(f"attach:  ray-tpu attach {state['cluster_name']}")
    print(f"exec:    ray-tpu exec {state['cluster_name']} -- <cmd...>")
    print(f"down:    ray-tpu down {state['cluster_name']}")
    return 0


def cmd_down(args):
    from ray_tpu.autoscaler.commands import teardown_cluster

    state = teardown_cluster(args.cluster)
    print(f"cluster {state['cluster_name']} torn down")
    return 0


def cmd_exec(args):
    from ray_tpu.autoscaler.commands import exec_on_cluster

    cmd = list(args.command)
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        print("usage: ray-tpu exec <cluster> -- <cmd...>", file=sys.stderr)
        return 1
    return exec_on_cluster(args.cluster, cmd).returncode


def cmd_attach(args):
    from ray_tpu.autoscaler.commands import attach_cluster

    return attach_cluster(args.cluster)


def main(argv=None):
    p = argparse.ArgumentParser(prog="ray-tpu", description=__doc__.split("\n")[0])
    sub = p.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("start", help="start a head node or join as a worker node")
    sp.add_argument("--head", action="store_true")
    sp.add_argument("--address")
    sp.add_argument("--num-cpus", type=int)
    sp.add_argument("--num-tpus", type=int)
    sp.add_argument("--resources")
    sp.add_argument("--session-dir")
    sp.add_argument("--block", action="store_true")
    sp.set_defaults(fn=cmd_start)

    sub.add_parser("stop", help="stop the running cluster").set_defaults(fn=cmd_stop)

    sp = sub.add_parser("up", help="launch a cluster from a cluster YAML")
    sp.add_argument("cluster_config")
    sp.set_defaults(fn=cmd_up)

    sp = sub.add_parser("down", help="tear down a launched cluster (name or YAML)")
    sp.add_argument("cluster")
    sp.set_defaults(fn=cmd_down)

    sp = sub.add_parser("exec", help="run a command against a launched cluster")
    sp.add_argument("cluster")
    sp.add_argument("command", nargs=argparse.REMAINDER)
    sp.set_defaults(fn=cmd_exec)

    sp = sub.add_parser("attach", help="interactive shell wired to a launched cluster")
    sp.add_argument("cluster")
    sp.set_defaults(fn=cmd_attach)
    sp = sub.add_parser(
        "status",
        help="cluster table: resources, host/HBM telemetry, compiles, skew",
    )
    sp.add_argument(
        "--offline", action="store_true",
        help="render from a built-in fixture (no cluster; smoke-tests the view)",
    )
    sp.set_defaults(fn=cmd_status)

    sp = sub.add_parser("submit", help="submit a job: ray-tpu submit -- python x.py")
    sp.add_argument("--no-wait", action="store_true")
    sp.add_argument("--timeout", type=float, default=600.0)
    sp.add_argument("entrypoint", nargs=argparse.REMAINDER)
    sp.set_defaults(fn=cmd_submit)

    sp = sub.add_parser("job", help="manage jobs")
    sp.add_argument("action", choices=["list", "logs", "stop"])
    sp.add_argument("job_id", nargs="?")
    sp.set_defaults(fn=cmd_job)

    sp = sub.add_parser("summary", help="state summaries")
    sp.add_argument(
        "what",
        choices=["tasks", "actors", "objects", "memory", "lifecycle", "rl",
                 "train", "profiling", "errors"],
    )
    sp.set_defaults(fn=cmd_summary)

    sp = sub.add_parser(
        "timeline",
        help="chrome trace: task slices + control-plane lifecycle + user spans",
    )
    sp.add_argument("--output", "-o")
    sp.add_argument(
        "--no-lifecycle", action="store_true",
        help="omit flight-recorder lifecycle rows",
    )
    sp.add_argument(
        "--no-spans", action="store_true",
        help="omit RAY_TPU_TRACE span files",
    )
    sp.add_argument(
        "--no-device", action="store_true",
        help="omit captured XLA device-trace events",
    )
    sp.set_defaults(fn=cmd_timeline)

    sp = sub.add_parser(
        "memory",
        help="cluster memory census: objects by call-site, store "
             "occupancy, pins, leak suspects",
    )
    sp.add_argument("--node", help="filter to one node (node-id hex prefix)")
    sp.add_argument("--leaks", action="store_true",
                    help="show only the leak detector's flagged call-sites")
    sp.add_argument("--limit", type=int, default=20,
                    help="call-site rows to show (default 20)")
    sp.add_argument("--json", action="store_true",
                    help="raw summarize_memory() JSON")
    sp.add_argument("--offline", action="store_true",
                    help="render from a built-in fixture (no cluster)")
    sp.set_defaults(fn=cmd_memory)

    sp = sub.add_parser(
        "profile",
        help="on-demand profiling: stacks|cpu|device|incidents|captures",
    )
    sp.add_argument(
        "action", nargs="?",
        help="stacks|cpu|device|incidents|captures (or a capture id — "
             "the legacy `profile [ID]` list/fetch still works)",
    )
    sp.add_argument("target_id", nargs="?", help="incident or capture id")
    sp.add_argument("--duration", type=float, default=5.0,
                    help="cpu/device: capture seconds")
    sp.add_argument("--hz", type=float,
                    help="cpu: sample rate (default: profiling_sample_hz)")
    sp.add_argument("--node", help="filter to one node (node-id hex prefix)")
    sp.add_argument("--actor",
                    help="stacks: filter to one actor's worker (id prefix)")
    sp.add_argument("--workers",
                    help="cpu/device: comma-separated worker-id prefixes")
    sp.add_argument("--out", help="cpu: write the full profile here")
    sp.add_argument("--format", choices=["speedscope", "collapsed"],
                    default="speedscope", help="cpu --out format")
    sp.add_argument("--timeout", type=float, default=10.0)
    sp.add_argument("--offline", action="store_true",
                    help="render from built-in fixtures (no cluster)")
    sp.set_defaults(fn=cmd_profile)

    sp = sub.add_parser(
        "metrics", help="metrics tooling (dashboard: emit Grafana JSON)"
    )
    sp.add_argument("action", choices=["dashboard"])
    sp.add_argument("--out", default="", help="write JSON here (default: stdout)")
    sp.set_defaults(fn=cmd_metrics)
    sub.add_parser("dashboard", help="print the dashboard URL").set_defaults(
        fn=cmd_dashboard
    )

    sp = sub.add_parser("stack", help="live thread stacks of all cluster processes")
    sp.add_argument("--timeout", type=float, default=10.0)
    sp.set_defaults(fn=cmd_stack)

    sp = sub.add_parser("drain-node", help="gracefully drain a node")
    sp.add_argument("node_id", help="node id (hex, from `ray-tpu status`)")
    sp.add_argument("--timeout", type=float, default=300.0)
    sp.set_defaults(fn=cmd_drain_node)

    sp = sub.add_parser(
        "logs",
        help="cluster logs: list/tail files, search with task/actor/"
             "severity attribution, or live-follow",
    )
    sp.add_argument("filename", nargs="?")
    sp.add_argument("--tail", type=int, default=1000,
                    help="lines to fetch / search-result cap")
    sp.add_argument("--node", help="filter to one node (node-id hex prefix)")
    sp.add_argument("--task",
                    help="filter to one task (name substring or id prefix)")
    sp.add_argument("--actor", help="filter to one actor (id prefix)")
    sp.add_argument("--grep", help="regex over structured log messages")
    sp.add_argument("--severity",
                    help="severity floor (DEBUG/INFO/WARNING/ERROR)")
    sp.add_argument("--err", action="store_true",
                    help="shortcut for --severity ERROR")
    sp.add_argument("--follow", "-f", action="store_true",
                    help="stream matching records live (ctrl-c to stop)")
    sp.add_argument("--offline", action="store_true",
                    help="render from a built-in fixture (no cluster)")
    sp.set_defaults(fn=cmd_logs)

    sp = sub.add_parser(
        "health",
        help="self-healing plane: actuators, recent actions, active avoids",
    )
    sp.add_argument("--limit", type=int, default=50,
                    help="recent actions to show")
    sp.add_argument("--json", action="store_true", help="raw JSON summary")
    sp.add_argument("--offline", action="store_true",
                    help="render from a built-in fixture (no cluster)")
    sp.set_defaults(fn=cmd_health)

    sub.add_parser("microbenchmark", help="core perf smoke").set_defaults(fn=cmd_microbenchmark)

    sp = sub.add_parser(
        "lint",
        help="static analysis: concurrency/asyncio/jit-recompile/metrics rules",
    )
    from ray_tpu.tools.lint.cli import add_lint_args

    add_lint_args(sp)
    sp.set_defaults(fn=cmd_lint)

    sp = sub.add_parser(
        "sanitize",
        help="concurrency sanitizer: guard-annotation checks (RTL009-011), "
        "lock-order cross-check, runtime witness reports",
    )
    from ray_tpu.tools.sanitizer.cli import add_sanitize_args

    add_sanitize_args(sp)
    sp.set_defaults(fn=cmd_sanitize)

    args = p.parse_args(argv)
    entry = getattr(args, "entrypoint", None)
    if entry and entry[0] == "--":
        args.entrypoint = entry[1:]
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
