"""``python3 -m chipbench --workload <name> --seed <n> --seconds <s> --trace <0|1>``

One cell, once, in a new process: set up, warm up, measure for ``--seconds``,
check the outputs against the reference, print one line, exit. This process
never touches JAX: the chip belongs to the worker or replica it starts.
"""
from __future__ import annotations

import time

T_START = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402


def parse(argv=None):
    p = argparse.ArgumentParser(prog="python3 -m chipbench", description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None, help="default: run_seconds")
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="tiny sizes on whatever JAX finds, for tests; never a measurement")
    p.add_argument("--check-seeds", default="",
                   help="a..b: after one set-up, judge the program and the control on each "
                        "seed against the reference; prints a table and no result line")
    p.add_argument("--sweep", default="", help="serving, open loop: r1,r2,..: each rate for --seconds "
                                               "after one set-up; prints a table and no result line")
    p.add_argument("--control", default="", help="with --check-seeds: the control's precision (int8)")
    p.add_argument("--keep-trace", default="", help="copy the traced run's .xplane.pb here")
    p.add_argument("--facts-to", default="", help="write the run's collected facts here (JSON)")
    args = p.parse_args(argv)
    if args.check_seeds:
        a, b = args.check_seeds.split("..")
        args.check_seeds = list(range(int(a), int(b) + 1))
    else:
        args.check_seeds = []
    args.sweep = [float(r) for r in args.sweep.split(",")] if args.sweep else []
    return args


def main(argv=None) -> int:
    args = parse(argv)
    try:
        import ray_tpu  # noqa: F401
    except ImportError as e:
        print(f"chipbench: the system under test is not here: {e}", file=sys.stderr)
        return 2
    from chipbench import manifest as M
    from chipbench import trace_reduce, validate

    manifest = M.load_manifest()
    cell = M.Cell(manifest, args.workload)
    if args.seconds is None:
        args.seconds = float(manifest["run_seconds"])
    if not args.rehearse:
        # The quick way out, by the program's own detector. The worker that
        # opens JAX checks again, for real.
        from ray_tpu.accelerators.tpu import TPUAcceleratorManager

        seen, where = TPUAcceleratorManager.detect_chips()
        if seen < cell.chips:
            print(f"chipbench: found no accelerator, or fewer chips than the cell's {cell.chips}: "
                  f"{seen} ({where}). A cell is measured on the chip only (--rehearse is for "
                  "tests).", file=sys.stderr)
            return 2
    # Sessions, logs and spill files of the cluster go under TMPDIR, which the
    # driver gives each side for itself, never to a fixed path.
    os.environ.setdefault("RAY_TPU_TMPDIR", os.path.join(tempfile.gettempdir(), "ray_tpu"))
    out = cell.driver().run(cell, args, T_START)
    if "jax" in sys.modules:
        from ray_tpu.accelerators.tpu import jax_backend_initialized

        if jax_backend_initialized():
            raise RuntimeError("the benchmark's own process opened a JAX backend")
    if args.check_seeds or args.sweep:
        return 0
    facts = out.pop("facts")
    # The rehearsal's shares are arithmetic on a CPU's times, never a measurement:
    # they borrow a chip's row of the peaks table so that the readers run.
    facts["peaks_of"] = "TPU v5 lite" if args.rehearse else out["device"]["kind"]
    if args.facts_to:
        os.makedirs(os.path.dirname(os.path.abspath(args.facts_to)), exist_ok=True)
        with open(args.facts_to, "w") as f:
            json.dump(facts, f, default=str)
    metrics = {}
    section, declared = (("layer_metrics", cell.per_layer) if args.trace
                         else ("end_to_end", cell.end_to_end))
    for m in declared:
        value = M.reader(section, m["name"]).read(facts)
        if value is None:
            print(f"[chipbench] {m['name']}: nothing to read in this run", flush=True)
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        print(f"[chipbench] {m['name']} = {value!r} {m['unit']}", flush=True)
    line = {"correct": out["correct"], "attempted": out["attempted"], "failed": out["failed"],
            "metrics": metrics, "device": dict(out["device"])}
    if args.trace:
        reduced = facts["trace"]
        line["device"]["window_s"] = reduced["window_s"]
        line["device"]["busy_s"] = reduced["busy_s"]
        line["breakdown"] = trace_reduce.breakdown(reduced)
    text = json.dumps(line)
    # A CPU's trace names no program: there, and only there, a metric read
    # from the device's trace may find nothing to read.
    may_lack = {m["name"] for m in declared if m["source"] == "device_trace"} if args.rehearse else ()
    wrong = validate.check_line(text, manifest, cell.name, args.trace,
                                platform="cpu" if args.rehearse else "tpu", may_lack=may_lack)
    if wrong:
        print("chipbench: the result line breaks the contract:\n  " + "\n  ".join(wrong),
              file=sys.stderr)
        print("[chipbench] refused line: " + text[:2000], file=sys.stderr)
        return 3
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
