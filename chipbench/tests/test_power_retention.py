"""The power retention configuration's side of the benchmark, on the CPU: the
manifest with its cell, the configuration file against the catalog's row, the work
counts against hand arithmetic, each new reader on hand-made facts (and on the
facts of a program without the counters or the kernel), and the tiny rehearsal of
the cell in both trace modes.

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q

Nothing here pins the END of a list of the manifest: a later cell may join behind.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from chipbench import manifest as M
from chipbench import validate
from chipbench import weights_power_retention as W
from chipbench import work_power_retention as work

MANIFEST = M.load_manifest()
DRIVER = "serve_power_retention"
NEW = ("power_update_roofline", "power_update_share_pct", "power_decode_hbm_roofline")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def the_cell() -> str:
    """The cell of this configuration's driver, found in the manifest and not by name."""
    for w in MANIFEST["workloads"]:
        if M.load_json("traffic", w["traffic"] + ".json")["driver"] == DRIVER:
            return w["name"]
    raise AssertionError(DRIVER)


def the_config() -> dict:
    return M.Cell(MANIFEST, the_cell()).config


def test_the_manifest_with_the_cell_passes_the_contracts_rules():
    assert validate.check_manifest(MANIFEST) == []
    cell = M.Cell(MANIFEST, the_cell())
    assert cell.chips == 1 and cell.traffic["generator"] == "sessions"
    assert [m["name"] for m in cell.end_to_end] == ["tpot_p95_ms", "setup_s"]
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert all(name in names for name in NEW)
    # every entry that was there before this configuration's stands before its own
    assert [c["name"] for c in MANIFEST["configs"]].index(cell.entry["config"]) >= 6
    assert names.index(NEW[0]) >= 35
    reported = {m["name"] for m in cell.per_layer}
    assert {"decode_step_ms", "chunk_call_ms", "state_slots_live_pct", *NEW} <= reported


def test_the_configuration_keeps_every_published_number_it_does_not_list_as_reduced():
    conf = the_config()
    entry = next(c for c in MANIFEST["configs"] if c["name"] == M.Cell(MANIFEST, the_cell()).entry["config"])
    assert conf["source"] == entry["source"] and sorted(conf["reduced"]) == sorted(entry["reduced"])
    assert entry["reduced"] == ["num_hidden_layers"]
    if os.path.exists(CATALOG):
        row = next(r for r in map(json.loads, open(CATALOG)) if r["source_url"] == conf["source"])
        for key, value in row["config"].items():
            if key in entry["reduced"]:
                assert conf[key] == conf["reduced"][key]["to"] and conf["reduced"][key]["from"] == value, key
            else:
                assert conf[key] == value, key
    d = W.Dims.from_config(conf)
    assert (d.layers, d.heads, d.kv_heads, d.group, d.head_dim) == (8, 40, 8, 5, 128)
    for key in ("assumed", "deployment", "memory", "correct"):
        assert conf[key], key
    assert {"degree", "gate", "normaliser", "qk_norm", "state_precision", "phi_layout",
            "gate_init"} <= set(conf["assumed"])
    assert conf["engine"] == {"decode_window": 10, "overlap": True, "enable_prefix_cache": False,
                              "prefill_chunk": 1024, "warmup_buckets": False}
    paged = conf["paged"]  # no pool of blocks: the table's length is the model's positions and no more
    assert paged["block_size"] * paged["max_blocks_per_seq"] == conf["max_position_embeddings"]
    assert paged["num_blocks"] == 1 and paged["max_batch"] == 16
    traffic = M.Cell(MANIFEST, the_cell()).traffic["params"]
    assert traffic["clients"] == 1.25 * paged["max_batch"] and traffic["turns"] == 1
    # every prompt is two chunk calls: one whole, one carried
    chunk = conf["engine"]["prefill_chunk"]
    lo = traffic["system_tokens"] + traffic["user_tokens"]["min"]
    hi = traffic["system_tokens"] + traffic["user_tokens"]["max"]
    assert chunk < lo and hi <= 2 * chunk


def test_the_program_holds_what_the_configuration_says():
    """The program's own pool at the served sizes against the file's arithmetic:
    8 layers x 16 slots x 8 heads x 136 x 8,320 float32, and the parameters."""
    import jax

    from ray_tpu.models import paged, power_retention

    conf = the_config()
    d = W.Dims.from_config(conf)
    cfg = W.program_config(d, "bfloat16")
    pool = paged.paged_model(cfg).pools["power"]
    assert (pool.layers, pool.unit, pool.row) == (8, "slots", (8, 136, 8320))
    assert paged.block_pools(cfg) == ()
    state = 8 * conf["paged"]["max_batch"] * 8 * 136 * 8320 * 4
    assert state == 4634705920
    shapes = jax.eval_shape(lambda k: power_retention.init_params(k, cfg), jax.random.PRNGKey(0))
    held = sum(int(a.size) for a in jax.tree.leaves(shapes))
    assert held == work.stored_params(d) == 4198652992  # x 2 B = 8.40 GB
    assert 0.76 < (2 * held + state) / 16.9e9 < 0.78


def test_work_counts_match_hand_arithmetic():
    d = W.Dims.from_config(the_config())
    mixer = 2 * 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8
    assert work.mixer_params(d) == mixer == 62955520
    assert work.layer_params(d) == mixer + 3 * 5120 * 17408 == 330342400
    fixed = 8 * 330342400 + 5120 * 151936
    assert work.matmul_params(d) == fixed == 3420651520
    assert work.phi_entries(d) == 128 * 129 // 2 == 8256  # the least map, not the program's 8,320
    assert work.state_entries(d) == 8 * 8256 * 129 == 8520192
    assert work.state_bytes_per_slot(d) == 8 * 8520192 * 4 == 272646144
    step = work.decode_step_bytes(d, 16)
    assert step == fixed * 2 + 2 * 16 * 272646144 == 15565979648  # 19.0 ms at 819 GB/s
    assert 0.56 < 2 * 16 * 272646144 / step < 0.57
    assert work.decode_step_bytes(d, 0) == fixed * 2
    flops, bytes_ = work.power_update_work(d, 16)
    assert flops == 13 * 8520192 * 16
    assert bytes_ == (2 * 8520192 + 2 * 5120 + 2 * 1024 + 8) * 4 * 16 == 1091371520
    assert flops / bytes_ < 2  # far under the chip's ridge of 240: bytes bound it


def op(seconds: float, detail: str = "", count: int = 2) -> dict:
    return {"seconds": seconds, "self_seconds": seconds, "count": count, "detail": detail}


def facts(stats: dict, ops=None, modules=None, dims=None, traced_at=None) -> dict:
    trace = None if ops is None else {"ops": ops, "modules": modules or {}, "busy_s": 2.0}
    if traced_at:
        trace.update(host_t0=traced_at[0], host_t1=traced_at[1])
    return {
        "dims": dims or the_config(), "peaks_of": "TPU v5 lite", "trace": trace,
        "engine": {"stats": stats, "decode_window": 10, "max_batch": 16,
                   # the second and the fourth dispatched a window inside (7, 11): 15 and 16 live
                   "steps": [{"ts": 5.0, "active": 12, "state_slots_live": 12},
                             {"ts": 8.0, "active": 16, "state_slots_live": 15},
                             {"ts": 9.0, "active": 16, "state_slots_live": 0},
                             {"ts": 10.0, "active": 16, "state_slots_live": 16},
                             {"ts": 12.0, "active": 0}],
                   "requests": [{"prompt_tokens": 1500, "output_tokens": 400}]},
    }


# 100 windows dispatched at 14 live slots of 16
COUNTED = {"steps": 100, "state_slots_live": 1400, "state_slots_table": 1600}
KERNEL = {"power_state_update.3": op(16.0, "custom-call(...)", count=8000),  # 2 ms a call
          # a consumer names the kernel among its operands: not the kernel
          "fusion.9": op(0.3, "f32[16,8,5,136]{3,2,1,0} fusion(%power_state_update.3), kind=kLoop")}
WINDOW = {"jit__decode(123)": op(25.0, count=100)}  # 25 ms a step


def hand_roofline(live=14.0):
    d = W.Dims.from_config(the_config())
    return 100.0 * (work.decode_step_bytes(d, live) / 819e9) / 0.025


def hand_kernel(live=14.0):
    d = W.Dims.from_config(the_config())
    flops, bytes_ = work.power_update_work(d, live)
    return 100.0 * max(flops / 197e12, bytes_ / 819e9) / (16.0 / 8000)


OTHER = M.load_json("configs", "granite-4.0-h-micro-serve.json")  # state by slot, not this family's
CASES = [
    ("power_update_share_pct", facts(COUNTED, KERNEL, WINDOW), 100.0 * 16.0 / 25.0),
    ("power_update_share_pct", facts(COUNTED, {"fusion.9": KERNEL["fusion.9"]}, WINDOW), None),
    ("power_update_share_pct", facts(COUNTED), None),  # no trace
    ("power_update_share_pct", facts(COUNTED, KERNEL, {}), None),  # no decode program traced
    ("power_update_roofline", facts(COUNTED, KERNEL, WINDOW), hand_kernel()),
    # the live slots of the windows dispatched inside the traced seconds, not the whole window's
    ("power_update_roofline", facts(COUNTED, KERNEL, WINDOW, traced_at=(7.0, 11.0)), hand_kernel(15.5)),
    ("power_update_roofline", facts(COUNTED, KERNEL, WINDOW, traced_at=(20.0, 24.0)), hand_kernel()),
    ("power_update_roofline", facts(COUNTED), None),
    ("power_update_roofline", facts({"steps": 100}, KERNEL, WINDOW), None),  # no counters: the parent
    ("power_update_roofline", facts(COUNTED, KERNEL, WINDOW, dims=OTHER), None),
    ("power_decode_hbm_roofline", facts(COUNTED, KERNEL, WINDOW), hand_roofline()),
    ("power_decode_hbm_roofline", facts(COUNTED, KERNEL, WINDOW, traced_at=(7.0, 11.0)), hand_roofline(15.5)),
    ("power_decode_hbm_roofline", facts({"steps": 100}, KERNEL, WINDOW), None),
    ("power_decode_hbm_roofline", facts(COUNTED, KERNEL, {}), None),
    ("power_decode_hbm_roofline", facts(COUNTED, {}, WINDOW, dims=OTHER), None),
    # what the cell joined: the slots' counters read by the accepted reader
    ("state_slots_live_pct", facts(COUNTED), 100.0 * 1400 / 1600),
]


@pytest.mark.parametrize("case", range(len(CASES)), ids=[f"{c[0]}-{i}" for i, c in enumerate(CASES)])
def test_new_readers_on_canned_facts(case):
    name, given, answer = CASES[case]
    got = M.reader("layer_metrics", name).read(given)
    if answer is None:
        assert got is None
    else:
        assert got == pytest.approx(answer, rel=1e-9)


def test_the_new_readers_agree_with_the_manifest_and_stay_under_their_ceiling():
    for name in NEW:
        m = next(x for x in MANIFEST["per_layer"] if x["name"] == name)
        r = M.reader("layer_metrics", name)
        assert (r.UNIT, r.SOURCE, r.LAYER, r.MOVES) == (m["unit"], m["source"], m["layer"], m["moves"])
        assert the_cell() in m["workloads"]
    assert hand_roofline() < 100 and hand_kernel() < 100


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_prints_a_line_the_validator_accepts(trace, tmp_path):
    cell = the_cell()
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TMPDIR": str(tmp_path),
           "JAX_ENABLE_COMPILATION_CACHE": "false", "BENCH_RUN": "ignored"}
    facts_to = tmp_path / "facts.json"
    run = subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload", cell, "--seed", str(2**31 + 53),
         "--seconds", "3", "--trace", str(trace), "--rehearse", "--facts-to", str(facts_to)],
        cwd=M.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-2000:]
    last = run.stdout.strip().splitlines()[-1]
    line = json.loads(last)
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    lacking = {m["name"] for m in M.metrics_for(MANIFEST, cell, "per_layer")
               if m["source"] == "device_trace"}
    assert validate.check_line(last, MANIFEST, cell, trace, platform="cpu", may_lack=lacking) == []
    # the program's own counters, whatever the device: prompts longer than the
    # chunk carried their state between calls, and no block was tabled
    stats = json.loads(facts_to.read_text())["engine"]["stats"]
    assert stats["state_segments_carried"] > 0 and stats["state_segments_fresh"] > 0
    assert stats["decode_blocks_table"] == 0 and stats["decode_blocks_live"] == 0
    if trace:
        assert "state_slots_live_pct" in line["metrics"]
