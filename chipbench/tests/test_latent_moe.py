"""The latent-attention expert configuration's side of the benchmark, on the CPU:
the manifest with its cell, the work counts against hand arithmetic, each new
reader on hand-made facts (and on the facts of a program without the counters or
the kernel), the configuration file against the published widths, and the tiny
rehearsal of the cell in both trace modes.

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from chipbench import manifest as M
from chipbench import validate
from chipbench import weights_latent_moe as W
from chipbench import work_latent_moe as work

MANIFEST = M.load_manifest()
DRIVER = "serve_latent_moe"


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


def test_the_configuration_keeps_every_published_width_and_states_its_cut():
    published = dict(
        attention_bias=False, first_k_dense_replace=3, hidden_act="silu", hidden_size=7680,
        intermediate_size=18432, kv_lora_rank=512, max_position_embeddings=131072,
        model_type="pangu_ultra_moe", moe_intermediate_size=2048, n_routed_experts=256,
        n_shared_experts=1, norm_topk_prob=True, num_attention_heads=128, num_experts_per_tok=8,
        num_hidden_layers=61, num_key_value_heads=128, num_nextn_predict_layers=1, q_lora_rank=1536,
        qk_nope_head_dim=128, qk_rope_head_dim=64, rms_norm_eps=1e-05, rope_theta=25600000,
        routed_scaling_factor=2.5, sandwich_norm=True, tie_word_embeddings=False, v_head_dim=128,
        vocab_size=153600)
    conf = the_config()
    entry = next(c for c in MANIFEST["configs"] if c["file"].endswith(
        M.Cell(MANIFEST, the_cell()).entry["config"] + ".json"))
    reduced = conf["reduced"]
    assert sorted(reduced) == sorted(entry["reduced"]) and len(reduced) == 5
    for key, value in published.items():
        if key in reduced:
            assert (reduced[key]["from"], reduced[key]["to"]) == (value, conf[key]), key
            assert reduced[key]["why"]
        else:
            assert conf[key] == value, key
    assert conf["n_routed_experts_published"] == 256  # the router keeps its width
    assert conf["vocab_size"] * 8 == 153600 and conf["n_routed_experts"] * 16 == 256
    assert conf["num_hidden_layers"] - conf["first_k_dense_replace"] >= 4  # the floor
    assert conf["source"] == entry["source"]
    for key in ("assumed", "deployment", "memory", "correct"):
        assert conf[key], key


def test_work_counts_match_hand_arithmetic_for_the_cut():
    d = W.Dims.from_config(the_config())
    # W_dq 7680x1536, W_uq 1536x24576, W_dkv 7680x576, W_ukv 512x32768, W_o 16384x7680
    assert work.attention_params(d) == 11796480 + 37748736 + 4423680 + 16777216 + 125829120 == 196575232
    assert work.dense_ffn_params(d) == 3 * 7680 * 18432 == 424673280
    assert work.expert_params(d) == 3 * 7680 * 2048 == 47185920
    assert work.router_params(d) == 7680 * 256
    fixed = 5 * 196575232 + 424673280 + 4 * (1966080 + 47185920) + 7680 * 19200
    assert work.fixed_matmul_params(d) == fixed
    norms = 5 * (4 * 7680 + 1536 + 512) + 7680
    assert work.stored_params(d) == fixed + 4 * 16 * 47185920 + 19200 * 7680 + norms == 4919139840
    assert work.latent_bytes_per_token(d) == 5 * 576 * 2 == 5760
    # 32 slots of 9,000 tokens, 10 experts touched in each of 4 layers
    assert work.decode_step_bytes(d, 288000, 40) == (fixed + 40 * 47185920) * 2 + 288000 * 5760
    flops, bytes_ = work.latent_attend_work(d, 32, 288000)
    assert flops == 2 * 128 * (576 + 512) * 288000
    assert bytes_ == 288000 * 1152 + 32 * 128 * 1088 * 2
    assert 230 < flops / bytes_ < 242  # 241.8 for the rows alone: the v5e's own ridge (197e12 / 819e9 = 240.5)


def op(seconds: float, detail: str = "", count: int = 2) -> dict:
    return {"seconds": seconds, "self_seconds": seconds, "count": count, "detail": detail}


def facts(stats: dict, ops=None, modules=None) -> dict:
    trace = None if ops is None else {"ops": ops, "modules": modules or {}, "busy_s": 2.0}
    return {
        "dims": the_config(), "peaks_of": "TPU v5 lite", "trace": trace,
        "engine": {"stats": stats, "decode_window": 10,
                   "steps": [{"active": 32}, {"active": 0}, {"active": 32}],
                   "requests": [{"prompt_tokens": 8950, "output_tokens": 100},
                                {"prompt_tokens": 8000, "output_tokens": 0}]},
    }


# 100 windows of 10 steps x 4 expert layers, and 25 counted chunk calls x 4 layers
COUNTED = {"steps": 100, "moe_layer_steps": 4100, "moe_experts_touched": 40000 + 1600,
           "moe_pairs_here": 64000 + 51200}
KERNEL = {"latent_attend.7": op(0.5, "custom-call(...)", count=1000),
          # a consumer names the kernel among its operands: not the kernel
          "fusion.9": op(0.3, "bf16[32,16384]{1,0} fusion(%latent_attend.7), kind=kLoop")}
WINDOW = {"jit__decode(123)": op(1.2, count=10)}  # 12 ms a step


def hand_roofline():
    d = W.Dims.from_config(the_config())
    cached = 32 * 9000.0
    return 100.0 * (work.decode_step_bytes(d, cached, 40.0) / 819e9) / 0.012


def hand_kernel():
    d = W.Dims.from_config(the_config())
    flops, bytes_ = work.latent_attend_work(d, 32.0, 32 * 9000.0)
    return 100.0 * max(flops / 197e12, bytes_ / 819e9) / (0.5 / 1000)


CASES = [
    ("moe_experts_touched_pct", facts(COUNTED), 100.0 * 41600 / (16 * 4100)),
    ("moe_pairs_per_expert", facts(COUNTED), 115200 / 41600),
    ("moe_experts_touched_pct", facts({"steps": 100}), None),  # a program without the counters
    ("moe_pairs_per_expert", facts({"steps": 100, "moe_layer_steps": 0}), None),
    ("latent_attend_share_pct", facts(COUNTED, KERNEL, WINDOW), 100.0 * 0.5 / 2.0),
    ("latent_attend_share_pct", facts(COUNTED, {"fusion.9": KERNEL["fusion.9"]}, WINDOW), None),
    ("latent_attend_share_pct", facts(COUNTED), None),  # no trace
    ("latent_attend_roofline", facts(COUNTED, KERNEL, WINDOW), hand_kernel()),
    ("latent_attend_roofline", facts(COUNTED), None),
    # the chunk calls' 100 layer-steps are taken to touch all 16: 40,000 are the decode steps'
    ("latent_decode_hbm_roofline", facts(COUNTED, KERNEL, WINDOW), hand_roofline()),
    ("latent_decode_hbm_roofline", facts({"steps": 100}, KERNEL, WINDOW), None),
    ("latent_decode_hbm_roofline", facts(COUNTED, KERNEL, {}), None),  # no decode program traced
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
    for name in ("latent_decode_hbm_roofline", "latent_attend_roofline", "latent_attend_share_pct",
                 "moe_experts_touched_pct", "moe_pairs_per_expert"):
        m = next(x for x in MANIFEST["per_layer"] if x["name"] == name)
        r = M.reader("layer_metrics", name)
        assert (r.UNIT, r.SOURCE, r.LAYER, r.MOVES) == (m["unit"], m["source"], m["layer"], m["moves"])
        assert m["workloads"] == [the_cell()]
    assert hand_roofline() < 100 and hand_kernel() < 100


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_prints_a_line_the_validator_accepts(trace, tmp_path):
    cell = the_cell()
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TMPDIR": str(tmp_path),
           "JAX_ENABLE_COMPILATION_CACHE": "false", "BENCH_RUN": "ignored"}
    run = subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload", cell, "--seed", str(2**31 + 3),
         "--seconds", "3", "--trace", str(trace), "--rehearse"],
        cwd=M.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-2000:]
    last = run.stdout.strip().splitlines()[-1]
    line = json.loads(last)
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    lacking = {m["name"] for m in M.metrics_for(MANIFEST, cell, "per_layer")
               if m["source"] == "device_trace"}
    assert validate.check_line(last, MANIFEST, cell, trace, platform="cpu", may_lack=lacking) == []
    if trace:  # the counters ride the tokens: both counter metrics are on the line
        assert {"moe_experts_touched_pct", "moe_pairs_per_expert"} <= set(line["metrics"])
