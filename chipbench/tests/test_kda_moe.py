"""The KDA / latent-attention expert configuration's side of the benchmark, on the
CPU: the manifest with its cell, the configuration file against the catalog's row,
the work counts against hand arithmetic, each new reader on hand-made facts (and on
the facts of a program without the counters or the kernel), and the tiny rehearsal
of the cell in both trace modes.

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
from chipbench import weights_kda_moe as W
from chipbench import work_kda_moe as work

MANIFEST = M.load_manifest()
DRIVER = "serve_kda_moe"
NEW = ("kda_update_roofline", "kda_update_share_pct", "kda_decode_hbm_roofline")
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
    assert [c["name"] for c in MANIFEST["configs"]].index(cell.entry["config"]) >= 5
    assert names.index(NEW[0]) >= 32


def test_the_configuration_keeps_every_published_number_it_does_not_list_as_reduced():
    conf = the_config()
    entry = next(c for c in MANIFEST["configs"] if c["name"] == M.Cell(MANIFEST, the_cell()).entry["config"])
    assert conf["source"] == entry["source"] and sorted(conf["reduced"]) == sorted(entry["reduced"])
    assert set(entry["reduced"]) == {"num_hidden_layers", "first_k_dense_replace", "num_experts",
                                     "vocab_size"}
    if os.path.exists(CATALOG):
        row = next(r for r in map(json.loads, open(CATALOG)) if r["source_url"] == conf["source"])
        for key, value in row["config"].items():
            if key in entry["reduced"]:
                assert conf[key] == conf["reduced"][key]["to"] and conf["reduced"][key]["from"] == value, key
            else:
                assert conf[key] == value, key
    assert (conf["num_experts"], conf["num_experts_published"], conf["n_routed_experts"]) == (128, 512, 128)
    # the stage's seven layers: five KDA mixers to one latent, and none of them clamps
    d = W.Dims.from_config(conf)
    assert [d.kind(i) for i in range(d.layers)] == ["kda"] * 5 + ["latent", "kda"]
    for clamp in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert not any(conf[clamp][:7]), clamp
    for key in ("assumed", "deployment", "memory", "correct", "not_served"):
        assert conf[key], key
    assert set(conf["not_served"]) == {"vision_tower", "multi_token_prediction"}
    assert conf["engine"] == {"decode_window": 10, "overlap": True, "enable_prefix_cache": False,
                              "prefill_chunk": 1024, "warmup_buckets": False}
    paged = conf["paged"]  # 1,248 tokens a slot: 64 + 384 + 768 + the overshoot; every slot at its longest
    assert paged["block_size"] * paged["max_blocks_per_seq"] == 1248
    assert paged["num_blocks"] == paged["max_batch"] * paged["max_blocks_per_seq"] + 1
    traffic = M.Cell(MANIFEST, the_cell()).traffic["params"]
    assert traffic["clients"] == 1.25 * paged["max_batch"] and traffic["turns"] == 1


def test_work_counts_match_hand_arithmetic():
    d = W.Dims.from_config(the_config())
    kda = 2560 * 12288 + 2 * 2560 * 4096 + 2560 * 32 + 4096 * 2560
    assert work.kda_mixer_params(d) == kda == 62996480
    latent = 2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 2560 * 32 + 32 * 128 * 2560
    assert work.latent_mixer_params(d) == latent == 31965184
    assert work.expert_params(d) == 3 * 2560 * 768 == 5898240
    assert (work.layers_of(d, "kda"), work.layers_of(d, "latent"), work.expert_layers(d)) == (6, 1, 6)
    fixed = 6 * kda + latent + 3 * 2560 * 6144 + 6 * (2560 * 512 + 5898240) + 2560 * 39296
    assert work.fixed_matmul_params(d) == fixed == 600981504
    stored = work.stored_params(d)
    assert stored - fixed - 6 * 128 * 5898240 - 39296 * 2560 < 1e6  # norms, convolutions, biases
    assert 5.23e9 < stored < 5.24e9  # 10.46 GB in bfloat16
    assert work.state_bytes_per_slot(d) == 6 * 32 * 128 * 128 * 4 == 12582912
    assert work.latent_bytes_per_token(d) == 576 * 2
    # 128 live slots of 500 tokens, 110 of 128 experts touched a layer
    step = work.decode_step_bytes(d, 128, 64000, 660)
    assert step == (fixed + 660 * 5898240) * 2 + 2 * 128 * 12582912 + 64000 * 1152
    assert 0.25 < 2 * 128 * 12582912 / step < 0.27 and 0.63 < 660 * 5898240 * 2 / step < 0.65
    flops, bytes_ = work.kda_update_work(d, 128)
    assert flops == 7 * 32 * 128 * 128 * 128
    assert bytes_ == (2 * 524288 + 5 * 4096 + 32) * 4 * 128
    assert flops / bytes_ < 1  # far under the chip's ridge of 240: bytes bound it


def op(seconds: float, detail: str = "", count: int = 2) -> dict:
    return {"seconds": seconds, "self_seconds": seconds, "count": count, "detail": detail}


def facts(stats: dict, ops=None, modules=None, dims=None, traced_at=None) -> dict:
    trace = None if ops is None else {"ops": ops, "modules": modules or {}, "busy_s": 2.0}
    if traced_at:
        trace.update(host_t0=traced_at[0], host_t1=traced_at[1])
    return {
        "dims": dims or the_config(), "peaks_of": "TPU v5 lite", "trace": trace,
        "engine": {"stats": stats, "decode_window": 10, "max_batch": 128,
                   # the second and the fourth dispatched a window inside (7, 11): 120 and 124 live
                   "steps": [{"ts": 5.0, "active": 90, "state_slots_live": 60},
                             {"ts": 8.0, "active": 128, "state_slots_live": 120},
                             {"ts": 9.0, "active": 128, "state_slots_live": 0},
                             {"ts": 10.0, "active": 128, "state_slots_live": 124},
                             {"ts": 12.0, "active": 0}],
                   "requests": [{"prompt_tokens": 300, "output_tokens": 200},
                                {"prompt_tokens": 8000, "output_tokens": 0}]},
    }


# 100 windows dispatched at 90 live slots of 128; six expert layers a step, 100 of 128 touched each
COUNTED = {"steps": 100, "state_slots_live": 9000, "state_slots_table": 12800,
           "moe_pairs_here": 6000 * 230, "moe_experts_touched": 6000 * 100, "moe_layer_steps": 6000}
KERNEL = {"kda_state_update.3": op(36.0, "custom-call(...)", count=60000),  # 600 us a call
          # a consumer names the kernel among its operands: not the kernel
          "fusion.9": op(0.3, "f32[128,4096]{1,0} fusion(%kda_state_update.3), kind=kLoop")}
WINDOW = {"jit__decode(123)": op(20.0, count=100)}  # 20 ms a step


def hand_roofline(live=90.0):
    d = W.Dims.from_config(the_config())
    return 100.0 * (work.decode_step_bytes(d, live, live * 400.0, 600.0) / 819e9) / 0.020


def hand_kernel(live=90.0):
    d = W.Dims.from_config(the_config())
    flops, bytes_ = work.kda_update_work(d, live)
    return 100.0 * max(flops / 197e12, bytes_ / 819e9) / (36.0 / 60000)


OTHER = M.load_json("configs", "granite-4.0-h-micro-serve.json")  # state by slot, not this family's
CASES = [
    ("kda_update_share_pct", facts(COUNTED, KERNEL, WINDOW), 100.0 * 36.0 / 20.0),
    ("kda_update_share_pct", facts(COUNTED, {"fusion.9": KERNEL["fusion.9"]}, WINDOW), None),
    ("kda_update_share_pct", facts(COUNTED), None),  # no trace
    ("kda_update_share_pct", facts(COUNTED, KERNEL, {}), None),  # no decode program traced
    ("kda_update_roofline", facts(COUNTED, KERNEL, WINDOW), hand_kernel()),
    # the live slots of the windows dispatched inside the traced seconds, not the whole window's
    ("kda_update_roofline", facts(COUNTED, KERNEL, WINDOW, traced_at=(7.0, 11.0)), hand_kernel(122.0)),
    ("kda_update_roofline", facts(COUNTED, KERNEL, WINDOW, traced_at=(20.0, 24.0)), hand_kernel()),
    ("kda_update_roofline", facts(COUNTED), None),
    ("kda_update_roofline", facts({"steps": 100}, KERNEL, WINDOW), None),  # no counters: the parent
    ("kda_update_roofline", facts(COUNTED, KERNEL, WINDOW, dims=OTHER), None),
    ("kda_decode_hbm_roofline", facts(COUNTED, KERNEL, WINDOW), hand_roofline()),
    ("kda_decode_hbm_roofline", facts(COUNTED, KERNEL, WINDOW, traced_at=(7.0, 11.0)), hand_roofline(122.0)),
    ("kda_decode_hbm_roofline", facts({"steps": 100}, KERNEL, WINDOW), None),
    ("kda_decode_hbm_roofline", facts(COUNTED, KERNEL, {}), None),
    ("kda_decode_hbm_roofline", facts(COUNTED, {}, WINDOW, dims=OTHER), None),
    # what the cell joined: the experts' counters read by the accepted readers, against the 128 held
    ("moe_experts_touched_pct", facts(COUNTED), 100.0 * 100 / 128),
    ("moe_pairs_per_expert", facts(COUNTED), 2.3),
    ("state_slots_live_pct", facts(COUNTED), 100.0 * 9000 / 12800),
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
    run = subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload", cell, "--seed", str(2**31 + 50),
         "--seconds", "3", "--trace", str(trace), "--rehearse"],
        cwd=M.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-2000:]
    last = run.stdout.strip().splitlines()[-1]
    line = json.loads(last)
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    lacking = {m["name"] for m in M.metrics_for(MANIFEST, cell, "per_layer")
               if m["source"] == "device_trace"}
    assert validate.check_line(last, MANIFEST, cell, trace, platform="cpu", may_lack=lacking) == []
    if trace:  # the program's own counters: on the line whatever the device
        counters = {"state_slots_live_pct", "moe_experts_touched_pct", "moe_pairs_per_expert"}
        assert counters <= set(line["metrics"])
