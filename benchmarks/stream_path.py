"""What the path from a replica's generator to the proxy's socket carries (CPU; PR 51, step 0).

A streaming deployment whose generator yields small dicts in bursts of ten, 64
concurrent requests through the HTTP proxy with NDJSON clients that read a line
at a time (as ``chipbench/drivers/serve.py:Client`` does), at offered rates of
1,000 / 2,000 / 4,000 / 8,000 items a second in total. For each rate: delivered
items a second, the clients' lag behind the producer (receive time less the
``time.time()`` the item carries: one host, one clock), and the CPU seconds of
the replica's, the controller's and the proxy's process over the run
(``/proc/<pid>/stat``), which say what hop saturates.

    JAX_PLATFORMS=cpu python benchmarks/stream_path.py --out benchmarks/STREAM_PATH.json

No device is touched; nothing here is a device metric.
"""
from __future__ import annotations

import argparse
import http.client
import json
import os
import statistics
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # run from a checkout

BURST = 10
STREAMS = 64
_TICK = os.sysconf("SC_CLK_TCK")


def cpu_seconds(pid: int) -> float:
    with open(f"/proc/{pid}/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    return (int(fields[11]) + int(fields[12])) / _TICK  # utime + stime


def pid_listening_on(port: int) -> int:
    inodes = set()
    for table in ("/proc/net/tcp", "/proc/net/tcp6"):
        try:
            with open(table) as f:
                for line in list(f)[1:]:
                    cols = line.split()
                    if cols[3] == "0A" and int(cols[1].rsplit(":", 1)[1], 16) == port:
                        inodes.add(cols[9])
        except OSError:
            pass
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            for fd in os.listdir(f"/proc/{pid}/fd"):
                link = os.readlink(f"/proc/{pid}/fd/{fd}")
                if link.startswith("socket:[") and link[8:-1] in inodes:
                    return int(pid)
        except OSError:
            continue
    raise RuntimeError(f"nobody listens on {port}")


def pid_of_module(module: str) -> int:
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                if module.encode() in f.read():
                    return int(pid)
        except OSError:
            continue
    raise RuntimeError(f"no process runs {module}")


def client(port: int, body: dict, out: list):
    for attempt in range(5):  # 64 connects at once overrun the proxy's listen backlog
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
        try:
            conn.request("POST", "/bursts", body=json.dumps(body).encode(), headers={
                "Accept": "application/x-ndjson", "Content-Type": "application/json"})
            resp = conn.getresponse()
            break
        except OSError:
            conn.close()
            time.sleep(0.05 * (attempt + 1))
    else:
        return
    try:
        while True:
            line = resp.readline()
            if not line:
                break
            frame = json.loads(line)
            out.append((time.time(), frame["t"], frame["pid"]))
    finally:
        conn.close()


def run_rate(port: int, rate: float, seconds: float, pids: dict) -> dict:
    gap = STREAMS * BURST / rate  # seconds between one stream's bursts
    bursts = max(1, round(seconds / gap))
    body = {"bursts": bursts, "gap_s": gap}
    got = [[] for _ in range(STREAMS)]
    threads = [threading.Thread(target=client, args=(port, body, got[i])) for i in range(STREAMS)]
    before = {name: cpu_seconds(pid) for name, pid in pids.items()}
    began = time.time()
    for t in threads:
        t.start()
        time.sleep(0.005)
    for t in threads:
        t.join()
    wall = time.time() - began
    after = {name: cpu_seconds(pid) for name, pid in pids.items()}
    items = [x for one in got for x in one]
    lags = sorted(recv - made for recv, made, _ in items)
    return {
        "offered_items_per_s": rate, "streams": STREAMS, "burst": BURST,
        "items": len(items), "expected_items": STREAMS * bursts * BURST,
        "wall_s": wall, "produce_s": bursts * gap,
        # over the clients' own span: first line read to last line read
        "delivered_items_per_s": len(items) / max(1e-9, max(r for r, _, _ in items) - min(r for r, _, _ in items)),
        "lag_p50_s": statistics.median(lags), "lag_p95_s": lags[int(0.95 * (len(lags) - 1))],
        "lag_max_s": lags[-1],
        "cpu_s": {name: after[name] - before[name] for name in pids},
        "cpu_share_of_wall": {name: (after[name] - before[name]) / wall for name in pids},
        "replica_pid": items[0][2] if items else None,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--rates", default="1000,2000,4000,8000")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out", default="")
    ap.add_argument("--label", default="")
    args = ap.parse_args(argv)

    import ray_tpu
    from ray_tpu import serve

    @serve.deployment(name="bursts", max_ongoing_requests=2 * STREAMS)
    class Bursts:
        def __call__(self, body):
            pid = os.getpid()
            start = time.time()
            n = 0
            for b in range(int(body["bursts"])):
                due = start + b * float(body["gap_s"])
                wait = due - time.time()
                if wait > 0:
                    time.sleep(wait)
                for _ in range(BURST):
                    yield {"tok": n, "t": time.time(), "pid": pid}
                    n += 1

    ray_tpu.init(num_cpus=8)
    rows = []
    try:
        serve.run(Bursts.bind(), http_port=0)
        port = serve.api.get_proxy_port()
        warm: list = []
        client(port, {"bursts": 2, "gap_s": 0.01}, warm)
        pids = {"replica": warm[0][2], "proxy": pid_listening_on(port),
                "controller": pid_of_module("ray_tpu.core.controller"), "clients": os.getpid()}
        for rate in (float(r) for r in args.rates.split(",")):
            row = {"label": args.label, **run_rate(port, rate, args.seconds, pids)}
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
