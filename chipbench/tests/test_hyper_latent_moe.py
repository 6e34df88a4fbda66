"""The multi-stream latent-attention expert configuration's side of the benchmark, on the
CPU: the manifest with its cell, the configuration file against the catalog's row, the
weights' maps at the published width, the work counts against hand arithmetic at the tiny
size, each new reader on hand-made facts (and on the facts of a program without the
counter, and of another family's cell) and on the facts a traced run on the chip recorded,
and the tiny rehearsal of the cell in both trace modes.

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
from chipbench import weights_hyper_latent_moe as W
from chipbench import work_hyper_latent_moe as work

MANIFEST = M.load_manifest()
DRIVER = "serve_hyper_latent_moe"
NEW = ("hc_mix_share_pct", "hc_mix_hbm_roofline", "hc_decode_hbm_roofline")
JOINED = ("proxy_overhead_ms", "engine_queue_ms", "decode_step_ms", "prefill_share_pct", "chunk_call_ms",
          "overlap_window_pct", "idle_attributed_pct", "device_starved_pct", "starved_dispatch_ms",
          "starved_admit_ms", "moe_experts_touched_pct", "moe_pairs_per_expert", "latent_attend_roofline",
          "latent_attend_share_pct")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
RECORDED = os.path.join(os.path.dirname(__file__), "data", "hyper_latent_moe_facts.json")


def the_cell() -> str:
    """The cell of this configuration's driver, found in the manifest and not by name."""
    for w in MANIFEST["workloads"]:
        if M.load_json("traffic", w["traffic"] + ".json")["driver"] == DRIVER:
            return w["name"]
    raise AssertionError(DRIVER)


def the_config() -> dict:
    return M.Cell(MANIFEST, the_cell()).config


def tiny() -> dict:
    conf = the_config()
    return {**conf, **conf["rehearsal"]}


def test_the_manifest_with_the_cell_passes_the_contracts_rules():
    assert validate.check_manifest(MANIFEST) == []
    cell = M.Cell(MANIFEST, the_cell())
    assert cell.chips == 1 and cell.traffic["generator"] == "sessions"
    assert [m["name"] for m in cell.end_to_end] == ["tpot_p95_ms", "setup_s"]
    reported = [m["name"] for m in cell.per_layer]
    assert all(name in reported for name in NEW + JOINED)
    # every entry that was there before this configuration's stands before its own
    assert [c["name"] for c in MANIFEST["configs"]].index(cell.entry["config"]) >= 8
    assert [m["name"] for m in MANIFEST["per_layer"]].index(NEW[0]) >= 43
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1 and len(MANIFEST["workloads"]) >= 10


def test_the_configuration_keeps_every_published_number_it_does_not_list_as_reduced():
    conf = the_config()
    entry = next(c for c in MANIFEST["configs"] if c["name"] == M.Cell(MANIFEST, the_cell()).entry["config"])
    assert conf["source"] == entry["source"] and sorted(conf["reduced"]) == sorted(entry["reduced"])
    assert set(entry["reduced"]) == {"n_routed_experts", "vocab_size", "num_nextn_predict_layers"}
    if os.path.exists(CATALOG):
        row = next(r for r in map(json.loads, open(CATALOG)) if r["source_url"] == conf["source"])
        for key, value in row["config"].items():
            if key in entry["reduced"]:
                assert conf[key] == conf["reduced"][key]["to"] and conf["reduced"][key]["from"] == value, key
            else:
                assert conf[key] == value, key
    # every published width and the whole depth, by hand: nothing but the share is cut
    kept = dict(
        hidden_size=3584, num_hidden_layers=40, first_k_dense_replace=2, num_attention_heads=32,
        q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        intermediate_size=9216, moe_intermediate_size=1024, num_experts_per_tok=4, n_shared_experts=1,
        routed_scaling_factor=2, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
        mhc_h_res_clamp_max=30, rope_theta=10000)
    assert {k: conf[k] for k in kept} == kept
    assert conf["rope_scaling"] == {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                                    "mscale_all_dim": 1, "original_max_position_embeddings": 4096, "type": "yarn"}
    assert (conf["n_routed_experts"], conf["n_routed_experts_published"], conf["experts_held_first"]) == (8, 64, 0)
    assert conf["vocab_size"] * 8 == 131072 and conf["num_nextn_predict_layers"] == 0
    for key in ("assumed", "deployment", "memory", "correct", "rehearsal"):
        assert conf[key], key
    for item in ("streams", "maps", "sinkhorn", "sublayer", "map_weights", "map_spreads", "rope",
                 "softmax_scale", "scoring", "norms", "weights"):
        assert conf["assumed"][item], item
    assert conf["engine"] == {"decode_window": 10, "overlap": True, "enable_prefix_cache": True,
                              "prefill_chunk": 1024, "warmup_buckets": False}
    paged = conf["paged"]
    assert (paged["block_size"], paged["max_batch"], paged["max_blocks_per_seq"]) == (64, 32, 25)
    assert 801 <= paged["num_blocks"] <= 1025  # the amendment's floor: 32 x 25 and the trash block
    assert conf["rehearsal"]["hc_mult"] == 4 and conf["rehearsal"]["rope_scaling"]["factor"] > 1
    traffic = M.Cell(MANIFEST, the_cell()).traffic["params"]
    assert traffic["clients"] == 1.25 * paged["max_batch"] == 40 and traffic["turns"] == 1
    assert (traffic["system_prompts"], traffic["system_tokens"], traffic["check_requests"]) == (8, 64, 16)
    assert traffic["user_tokens"] == {"min": 448, "max": 960} and traffic["answer_tokens"] == {"min": 256, "max": 512}
    # every prompt is one chunk call, and the table holds the longest sequence with two windows of overshoot
    longest = traffic["system_tokens"] + traffic["user_tokens"]["max"]
    assert longest == conf["engine"]["prefill_chunk"]
    assert longest + traffic["answer_tokens"]["max"] + 2 * conf["engine"]["decode_window"] <= 25 * 64


def test_the_weights_maps_stand_where_the_configuration_says():
    """``assumed.map_spreads``, at the published width on random token states: ``Hres`` far
    from the identity AND from the uniform matrix, ``Hpre`` and ``Hpost`` far from constant,
    rows and columns of ``Hres`` summing to 1 as ``assumed.sinkhorn`` states."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import hyper_connections as hc

    dims = W.Dims.from_config(the_config())
    cfg = W.program_config(dims, jnp.bfloat16)
    key = W.seed_key(2**31 + 61)
    X = jnp.asarray(np.random.default_rng(0).normal(size=(512, 4, dims.hidden)), jnp.float32)
    made = [jax.jit(lambda hp: hc.maps(X, hp, cfg))(W.hc_params(key, layer, sub, dims))
            for layer in range(2) for sub in range(2)]
    pre, post, res = (np.stack([np.asarray(m[i]) for m in made]) for i in range(3))
    assert 0.33 < np.abs(res - np.eye(4)).mean() < 0.45 and 0.15 < np.abs(res - 0.25).mean() < 0.24
    assert np.abs(res.sum(-1) - 1).max() < 1e-5 and np.abs(res.sum(-2) - 1).max() < 0.05
    assert 0.2 < pre.std() < 0.32 and pre.std(1).mean() > 0.12  # over all, and over tokens within a sublayer
    assert 0.4 < post.std() < 0.65 and post.std(1).mean() > 0.25
    assert made[0][0].dtype == jnp.float32 and W.hc_params(key, 0, 0, dims)["phi"].shape == (4 * 3584, 24)


def test_work_counts_match_hand_arithmetic_at_the_tiny_size():
    """The rehearsal's sizes: 4 streams of 64, 4 layers (2 leading), 4 of 16 experts held."""
    d = W.Dims.from_config(tiny())
    assert (d.streams, d.hidden, d.layers, d.lead, d.held, d.experts) == (4, 64, 4, 2, 4, 16)
    assert work.map_params(d) == 4 * 64 * 24 + 3 + 4 + 4 + 16 == 6171
    assert work.mixes_a_call(d) == 2 * 4
    # a place, a sublayer: X (4 x 64) read twice and written once, u written and y read, bfloat16
    assert work.mix_bytes_a_place(d) == (3 * 4 + 2) * 64 * 2 == 1792
    # 8 sublayers of a chunk call of 32 places, one call: the places' bytes and phi and the rest once each
    assert work.mix_bytes(d, 8 * 32, 1) == 8 * 32 * 1792 + 8 * 6171 * 4
    attention = 64 * 32 + 32 * 4 * 24 + 64 * 40 + 32 * 4 * 32 + 4 * 16 * 64
    assert work.L.attention_params(d) == attention == 15872
    fixed = 4 * attention + 2 * 3 * 64 * 128 + 2 * (64 * 16 + 3 * 64 * 32) + 64 * 256
    assert work.L.fixed_matmul_params(d) == fixed
    step = work.decode_step_bytes(d, slots=3, cached_tokens=90, experts_touched=5)
    rows = 90 * 4 * (32 + 8) * 2
    assert step == (fixed + 5 * 3 * 64 * 32) * 2 + rows + 3 * 8 * 1792 + 8 * 6171 * 4
    norms = 4 * (2 * 64 + 32 + 32) + 64
    stored = fixed + 2 * 4 * 3 * 64 * 32 + 256 * 64 + norms
    assert work.stored_bytes(d) == stored * 2 + 8 * 6171 * 4
    # the published cut: 10.56 GB of weights, and the least a place moves is 100 KB a sublayer
    full = W.Dims.from_config(the_config())
    assert 10.55e9 < work.stored_bytes(full) < 10.58e9 and work.mix_bytes_a_place(full) == 100352


def op(seconds: float, detail: str = "", count: int = 2) -> dict:
    return {"seconds": seconds, "self_seconds": seconds, "count": count, "detail": detail}


def facts(stats: dict, ops=None, modules=None, dims=None) -> dict:
    trace = None if ops is None else {"ops": ops, "modules": modules or {}, "busy_s": 2.0, "window_s": 4.0}
    return {
        "dims": dims or the_config(), "peaks_of": "TPU v5 lite", "trace": trace, "seconds": 40.0,
        "engine": {"stats": stats, "decode_window": 10, "max_batch": 32,
                   "steps": [{"ts": 5.0, "active": 30}, {"ts": 8.0, "active": 32}, {"ts": 12.0, "active": 0}],
                   "requests": [{"prompt_tokens": 800, "output_tokens": 400},
                                {"prompt_tokens": 600, "output_tokens": 0}]},
    }


COUNTED = {"steps": 100, "hc_places_mixed": 40_000_000, "hc_tokens_mixed": 36_000_000,
           "moe_pairs_here": 38000 * 16, "moe_experts_touched": 38000 * 7, "moe_layer_steps": 38000}
OPS = {  # the parts by what they write, in the decode program and in a chunk call; what is no part
    "fusion.1": op(0.10, "f32[32,4,3584]{2,0,1:T(8,128)} fusion(%p0, %p1), kind=kLoop"),
    "convolution.2": op(0.05, "f32[32,24,1]{0,1,2:T(8,128)} convolution(%fusion.1, %copy.3)"),
    "copy.3": op(0.02, "f32[4,3584,24]{1,2,0:T(8,128)} copy(%p2)"),
    "fusion.4": op(0.06, "(f32[32,1]{0,1:T(1,128)S(1)}, f32[32,1]{0,1:T(1,128)S(1)}) fusion(%gte.1, %gte.2), kind=kLoop"),
    "while.5": op(0.03, "(s32[]{:T(128)}, f32[32,1]{0,1:T(1,128)S(1)}, f32[32,1]{0,1:T(1,128)S(1)}) while(%tuple.9), condition=%c, body=%b"),
    "fusion.6": op(0.04, "f32[32,1,4,4]{0,2,3,1:T(4,128)S(1)} fusion(%gte.3), kind=kLoop"),
    "fusion.7": op(0.20, "bf16[32,1,4,3584]{3,0,2,1:T(8,128)(2,1)S(1)} fusion(%p0, %fusion.6, %y), kind=kLoop"),
    "fusion.8": op(0.30, "bf16[1,1024,4,3584]{3,2,1,0:T(4,128)(2,1)} fusion(%p0, %f, %y), kind=kLoop"),
    "fusion.9": op(0.07, "f32[1,1024,24]{2,1,0:T(8,128)} fusion(%convolution.12), kind=kLoop"),
    "fusion.10": op(0.50, "bf16[32,1,3584]{2,0,1:T(8,128)(2,1)} fusion(%fusion.7), kind=kLoop"),  # the norm mix_in is fused into
    "while.11": op(0.90, "(s32[]{:T(128)}, bf16[32,1,4,3584]{3,0,2,1:T(8,128)(2,1)}, bf16[41000,64,640]{2,1,0}) while(%tuple), condition=%c, body=%b"),
}
MODULES = {"jit__decode(123)": op(4.0, count=20), "jit__chunk(77)": op(1.0, count=15)}
OTHER = M.load_json("configs", "pangu-ultra-moe-serve-ep16-l5.json")  # latent attention with ONE stream
MAPS, OUT = 0.10 + 0.05 + 0.02 + 0.06 + 0.03 + 0.04 + 0.07, 0.20 + 0.30


def hand_mix_roofline() -> float:
    """A tenth of the window traced: 4,000,000 places x sublayers at 100,352 B, and ``phi``
    (5.5 MB of float32 over 80 sublayers) once in each of 20 x 10 + 15 calls."""
    per_call = 80 * (4 * 3584 * 24 + 3 + 4 + 4 + 16) * 4
    return 100.0 * ((4_000_000 * 100352 + 215 * per_call) / 819e9) / (MAPS + OUT)


def hand_decode() -> float:
    """38 x 7 experts touched a step, 31 slots of 1,000 cached, a step of 20 ms."""
    d = W.Dims.from_config(the_config())
    return 100.0 * (work.decode_step_bytes(d, 31.0, 1000.0 * 31.0, 38 * 7.0) / 819e9) / (4.0 / 20 / 10)


CASES = [
    ("hc_mix_share_pct", facts(COUNTED, OPS, MODULES), 100.0 * (MAPS + OUT) / 2.0),
    ("hc_mix_share_pct", facts(COUNTED), None),  # no trace
    ("hc_mix_share_pct", facts(COUNTED, {"fusion.10": OPS["fusion.10"], "while.11": OPS["while.11"]}, MODULES), None),
    ("hc_mix_share_pct", facts(COUNTED, OPS, MODULES, dims=OTHER), None),  # another family's cell
    ("hc_mix_hbm_roofline", facts(COUNTED, OPS, MODULES), hand_mix_roofline()),
    ("hc_mix_hbm_roofline", facts({"steps": 100}, OPS, MODULES), None),  # no counter: the parent
    ("hc_mix_hbm_roofline", facts({**COUNTED, "hc_places_mixed": 0}, OPS, MODULES), None),
    ("hc_mix_hbm_roofline", facts(COUNTED), None),
    ("hc_mix_hbm_roofline", facts(COUNTED, OPS, MODULES, dims=OTHER), None),
    ("hc_decode_hbm_roofline", facts(COUNTED, OPS, MODULES), hand_decode()),
    ("hc_decode_hbm_roofline", facts(COUNTED), None),  # no trace
    ("hc_decode_hbm_roofline", facts({"steps": 100}, OPS, MODULES), None),  # no counters: the parent
    ("hc_decode_hbm_roofline", facts(COUNTED, OPS, MODULES, dims=OTHER), None),
    # what the cell joined: the experts' counters read by the accepted readers, against the 8 held
    ("moe_experts_touched_pct", facts(COUNTED), 100.0 * 7 / 8),
    ("moe_pairs_per_expert", facts(COUNTED), 16 / 7),
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
    assert 0 < hand_mix_roofline() < 100 and 0 < hand_decode() < 100


@pytest.mark.parametrize("name", NEW)
def test_new_readers_on_the_facts_a_traced_run_recorded(name):
    """``data/hyper_latent_moe_facts.json``: what the readers were given in a traced run of
    the cell on a v5e (PR 61; the rings cut to what the readers read), and the values
    that run printed. A share of a roofline stays under its ceiling; with the trace or the
    counter taken away each reader finds nothing."""
    with open(RECORDED) as f:
        recorded = json.load(f)
    given, printed = recorded["facts"], recorded["printed"]
    reader = M.reader("layer_metrics", name)
    assert reader.read(given) == pytest.approx(printed[name], rel=1e-6)
    assert 0 < printed[name] < 100
    assert reader.read({**given, "trace": None}) is None
    if name != "hc_mix_share_pct":
        stats = {k: v for k, v in given["engine"]["stats"].items() if not k.startswith(("hc_", "moe_"))}
        assert reader.read({**given, "engine": {**given["engine"], "stats": stats}}) is None


def test_the_controls_of_the_check_are_a_precision_and_a_planted_fault():
    from chipbench.drivers import serve_hyper_latent_moe as D

    assert D.FAULTS == {"plain-residual": "plain"}
    assert D.check_positions({"answer_tokens": {"max": 512}}) == 512
    assert D.check_positions({"answer_tokens": {"max": 10}}) == 128


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_prints_a_line_the_validator_accepts(trace, tmp_path):
    cell = the_cell()
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TMPDIR": str(tmp_path),
           "JAX_ENABLE_COMPILATION_CACHE": "false", "BENCH_RUN": "ignored"}
    run = subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload", cell, "--seed", str(2**31 + 61),
         "--seconds", "3", "--trace", str(trace), "--rehearse"],
        cwd=M.ROOT, env=env, capture_output=True, text=True, timeout=280)
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-2000:]
    last = run.stdout.strip().splitlines()[-1]
    line = json.loads(last)
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    lacking = {m["name"] for m in M.metrics_for(MANIFEST, cell, "per_layer")
               if m["source"] == "device_trace"}
    assert validate.check_line(last, MANIFEST, cell, trace, platform="cpu", may_lack=lacking) == []
    assert "warmed 2 system prompts" in run.stdout and "compilations inside the window 0 " in run.stdout
    if trace:  # the program's own counters: on the line whatever the device
        assert {"moe_experts_touched_pct", "moe_pairs_per_expert"} <= set(line["metrics"])
