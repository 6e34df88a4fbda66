"""The hybrid state-space configuration's side of the benchmark, on the CPU: the
manifest with its cell, the work counts against hand arithmetic, each new reader
on hand-made facts (and on the facts of a program without the counters or the
kernel), the configuration file against the catalog's row, and the tiny
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
from chipbench import weights_hybrid_ssm as W
from chipbench import work_hybrid_ssm as work

MANIFEST = M.load_manifest()
DRIVER = "serve_hybrid_ssm"
NEW = ("ssm_decode_hbm_roofline", "ssm_update_roofline", "ssm_update_share_pct",
       "state_slots_live_pct")


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
    # appended, behind what was there
    assert MANIFEST["workloads"][-1]["name"] == the_cell() and MANIFEST["workloads"][-1]["chips"] == 1
    assert [m["name"] for m in MANIFEST["per_layer"][-4:]] == list(NEW)


def test_the_configuration_is_the_published_one_uncut():
    published = dict(
        attention_bias=False, attention_multiplier=0.015625, embedding_multiplier=12,
        hidden_act="silu", hidden_size=2048, intermediate_size=8192, logits_scaling=8,
        mamba_chunk_size=256, mamba_conv_bias=True, mamba_d_conv=4, mamba_d_head=64,
        mamba_d_state=128, mamba_expand=2, mamba_n_groups=1, mamba_n_heads=64,
        mamba_proj_bias=False, max_position_embeddings=131072, model_type="granitemoehybrid",
        normalization_function="rmsnorm", num_attention_heads=32, num_experts_per_tok=0,
        num_hidden_layers=40, num_key_value_heads=8, num_local_experts=0,
        position_embedding_type="nope", residual_multiplier=0.22, rms_norm_eps=1e-05,
        rope_scaling=None, rope_theta=10000, shared_intermediate_size=8192,
        tie_word_embeddings=True, vocab_size=100352)
    conf = the_config()
    entry = next(c for c in MANIFEST["configs"] if c["name"] == M.Cell(MANIFEST, the_cell()).entry["config"])
    assert conf["reduced"] == {} and entry["reduced"] == []
    for key, value in published.items():
        assert conf[key] == value, key
    period = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert conf["layer_types"] == period * 4
    assert conf["source"] == entry["source"]
    for key in ("assumed", "deployment", "memory", "correct"):
        assert conf[key], key
    assert conf["engine"]["enable_prefix_cache"] is False and conf["engine"]["warmup_buckets"] is False
    assert conf["paged"] == {"block_size": 16, "num_blocks": 6273, "max_batch": 64,
                             "max_blocks_per_seq": 98}  # 1,568 tokens: 1,024 + 512 + the overshoot


def test_work_counts_match_hand_arithmetic():
    d = W.Dims.from_config(the_config())
    mlp = 2048 * 16384 + 8192 * 2048
    assert work.mlp_params(d) == mlp == 50331648
    # in-projection 2048 x (4096 + 4352 + 64), out-projection 4096 x 2048
    assert work.mamba_matmul_params(d) == 2048 * 8512 + 4096 * 2048 + mlp == 76152832
    # q and o 2048 x 2048, k and v 2048 x 512
    assert work.attention_matmul_params(d) == 2 * 2048 * 2048 + 2 * 2048 * 512 + mlp == 60817408
    matrices = 36 * 76152832 + 4 * 60817408 + 100352 * 2048
    assert work.matmul_params(d) == matrices == 3190292480
    # a state-space layer besides: convolution 4352 x 4 + 4352, dt_bias, A_log, D, gate norm, two norms
    small = 4352 * 5 + 3 * 64 + 4096 + 2 * 2048
    assert work.stored_params(d) == matrices + 36 * small + 4 * 2 * 2048 + 2048 == 3191396096
    assert work.state_bytes_per_slot(d) == 36 * 64 * 64 * 128 * 4 == 75497472
    assert work.kv_bytes_per_token(d) == 2 * 4 * 8 * 64 * 2 == 8192
    # 45 live slots of 400 tokens: the state, read and written, is as much as the weights
    step = work.decode_step_bytes(d, 45, 18000)
    assert step == matrices * 2 + 2 * 45 * 75497472 + 18000 * 8192
    assert 0.50 < 2 * 45 * 75497472 / step < 0.52
    flops, bytes_ = work.ssm_update_work(d, 45)
    assert flops == 5 * 64 * 64 * 128 * 45
    assert bytes_ == (2 * 524288 + 3 * 4096 + 256) * 4 * 45
    assert flops / bytes_ < 1  # far under the chip's ridge of 240: bytes bound it


def op(seconds: float, detail: str = "", count: int = 2) -> dict:
    return {"seconds": seconds, "self_seconds": seconds, "count": count, "detail": detail}


def facts(stats: dict, ops=None, modules=None, dims=None, traced_at=None) -> dict:
    trace = None if ops is None else {"ops": ops, "modules": modules or {}, "busy_s": 2.0}
    if traced_at:
        trace.update(host_t0=traced_at[0], host_t1=traced_at[1])
    return {
        "dims": dims or the_config(), "peaks_of": "TPU v5 lite", "trace": trace,
        "engine": {"stats": stats, "decode_window": 10, "max_batch": 64,
                   # the second and the fourth dispatched a window inside (7, 11): 60 and 62 live
                   "steps": [{"ts": 5.0, "active": 45, "state_slots_live": 30},
                             {"ts": 8.0, "active": 64, "state_slots_live": 60},
                             {"ts": 9.0, "active": 64, "state_slots_live": 0},
                             {"ts": 10.0, "active": 64, "state_slots_live": 62},
                             {"ts": 12.0, "active": 0}],
                   "requests": [{"prompt_tokens": 300, "output_tokens": 200},
                                {"prompt_tokens": 8000, "output_tokens": 0}]},
    }


# 100 windows dispatched at 45 live slots of 64
COUNTED = {"steps": 100, "state_slots_live": 4500, "state_slots_table": 6400}
KERNEL = {"ssm_state_update.3": op(10.8, "custom-call(...)", count=36000),  # 300 us a call
          # a consumer names the kernel among its operands: not the kernel
          "fusion.9": op(0.3, "f32[64,4096]{1,0} fusion(%ssm_state_update.3), kind=kLoop")}
WINDOW = {"jit__decode(123)": op(18.0, count=100)}  # 18 ms a step


def hand_roofline(live=45.0):
    d = W.Dims.from_config(the_config())
    return 100.0 * (work.decode_step_bytes(d, live, live * 400.0) / 819e9) / 0.018


def hand_kernel(live=45.0):
    d = W.Dims.from_config(the_config())
    flops, bytes_ = work.ssm_update_work(d, live)
    return 100.0 * max(flops / 197e12, bytes_ / 819e9) / (10.8 / 36000)


DENSE = M.load_json("configs", "mistral-7b-serve-l24.json")  # a model with no such state
CASES = [
    ("state_slots_live_pct", facts(COUNTED), 100.0 * 4500 / 6400),
    ("state_slots_live_pct", facts({"steps": 100}), None),  # a program without the counters
    ("state_slots_live_pct", facts({"steps": 100, "state_slots_table": 0, "state_slots_live": 0}), None),
    ("ssm_update_share_pct", facts(COUNTED, KERNEL, WINDOW), 100.0 * 10.8 / 18.0),
    ("ssm_update_share_pct", facts(COUNTED, {"fusion.9": KERNEL["fusion.9"]}, WINDOW), None),
    ("ssm_update_share_pct", facts(COUNTED), None),  # no trace
    ("ssm_update_share_pct", facts(COUNTED, KERNEL, {}), None),  # no decode program traced
    ("ssm_update_roofline", facts(COUNTED, KERNEL, WINDOW), hand_kernel()),
    # the live slots of the windows dispatched inside the traced seconds, not the whole window's
    ("ssm_update_roofline", facts(COUNTED, KERNEL, WINDOW, traced_at=(7.0, 11.0)), hand_kernel(61.0)),
    ("ssm_update_roofline", facts(COUNTED, KERNEL, WINDOW, traced_at=(20.0, 24.0)), hand_kernel()),
    ("ssm_update_roofline", facts(COUNTED), None),
    ("ssm_update_roofline", facts({"steps": 100}, KERNEL, WINDOW), None),
    ("ssm_decode_hbm_roofline", facts(COUNTED, KERNEL, WINDOW), hand_roofline()),
    ("ssm_decode_hbm_roofline", facts(COUNTED, KERNEL, WINDOW, traced_at=(7.0, 11.0)), hand_roofline(61.0)),
    ("ssm_decode_hbm_roofline", facts({"steps": 100}, KERNEL, WINDOW), None),
    ("ssm_decode_hbm_roofline", facts(COUNTED, KERNEL, {}), None),
    ("ssm_decode_hbm_roofline", facts({"steps": 100}, {}, WINDOW, dims=DENSE), None),
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
        assert m["workloads"] == [the_cell()]
    assert hand_roofline() < 100 and hand_kernel() < 100


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_prints_a_line_the_validator_accepts(trace, tmp_path):
    cell = the_cell()
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TMPDIR": str(tmp_path),
           "JAX_ENABLE_COMPILATION_CACHE": "false", "BENCH_RUN": "ignored"}
    run = subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload", cell, "--seed", str(2**31 + 45),
         "--seconds", "3", "--trace", str(trace), "--rehearse"],
        cwd=M.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-2000:]
    last = run.stdout.strip().splitlines()[-1]
    line = json.loads(last)
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    lacking = {m["name"] for m in M.metrics_for(MANIFEST, cell, "per_layer")
               if m["source"] == "device_trace"}
    assert validate.check_line(last, MANIFEST, cell, trace, platform="cpu", may_lack=lacking) == []
    if trace:  # the program's own counter: on the line whatever the device
        assert "state_slots_live_pct" in line["metrics"]
