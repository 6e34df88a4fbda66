"""The selecting / sliding latent-attention configuration's side of the benchmark, on the
CPU: the manifest with its cell, the configuration file against the catalog's row, the
work counts against hand arithmetic, each new reader on hand-made facts (and on the facts
of a program without the counters, and of another family's cell), and the tiny rehearsal
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
from chipbench import weights_sparse_latent_moe as W
from chipbench import work_sparse_latent_moe as work

MANIFEST = M.load_manifest()
DRIVER = "serve_sparse_latent_moe"
NEW = ("sparse_select_share_pct", "sparse_score_hbm_roofline", "sparse_keys_read_pct",
       "sparse_gather_hbm_roofline", "sparse_decode_hbm_roofline")
JOINED = ("engine_queue_ms", "decode_step_ms", "prefill_share_pct", "chunk_call_ms",
          "overlap_window_pct", "idle_attributed_pct", "device_starved_pct", "starved_dispatch_ms",
          "starved_admit_ms", "moe_experts_touched_pct", "moe_pairs_per_expert")
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
    reported = [m["name"] for m in cell.per_layer]
    assert all(name in reported for name in NEW + JOINED)
    names = [m["name"] for m in MANIFEST["per_layer"]]
    # every entry that was there before this configuration's stands before its own
    assert [c["name"] for c in MANIFEST["configs"]].index(cell.entry["config"]) >= 7
    assert names.index(NEW[0]) >= 38
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) == 1 and len(MANIFEST["workloads"]) >= 9


def test_the_configuration_keeps_every_published_number_it_does_not_list_as_reduced():
    conf = the_config()
    entry = next(c for c in MANIFEST["configs"] if c["name"] == M.Cell(MANIFEST, the_cell()).entry["config"])
    assert conf["source"] == entry["source"] and sorted(conf["reduced"]) == sorted(entry["reduced"])
    assert set(entry["reduced"]) == {"num_hidden_layers", "layer_types", "n_routed_experts", "vocab_size"}
    if os.path.exists(CATALOG):
        row = next(r for r in map(json.loads, open(CATALOG)) if r["source_url"] == conf["source"])
        for key, value in row["config"].items():
            if key == "layer_types":
                assert conf[key] == value[:5]
            elif key in entry["reduced"]:
                assert conf[key] == conf["reduced"][key]["to"] and conf["reduced"][key]["from"] == value, key
            else:
                assert conf[key] == value, key
    # every published width, by hand: no width is cut
    widths = dict(
        hidden_size=5120, num_attention_heads=128, q_lora_rank=1024, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, swa_num_attention_heads=64,
        swa_q_lora_rank=1024, swa_kv_lora_rank=1024, swa_qk_nope_head_dim=192,
        swa_qk_rope_head_dim=64, swa_v_head_dim=128, moe_intermediate_size=1536,
        num_experts_per_tok=8, n_shared_experts=1, intermediate_size=13824, index_n_heads=64,
        index_head_dim=128, index_topk=2048, sliding_window_size=513, rope_theta=80000000,
        swa_rope_theta=50000)
    assert {k: conf[k] for k in widths} == widths
    assert (conf["n_routed_experts"], conf["n_routed_experts_published"]) == (32, 256)
    assert (conf["vocab_size"], conf["vocab_size_published"]) == (19008, 152064) and 19008 * 8 == 152064
    assert conf["layer_types"] == ["full_attention"] * 2 + ["sliding_attention"] * 3
    for key in ("assumed", "deployment", "memory", "correct", "rehearsal"):
        assert conf[key], key
    for item in ("rescale", "indexer", "index_norm", "hadamard", "index_keys", "window", "expert_bias",
                 "rope", "weights"):
        assert conf["assumed"][item], item
    assert conf["engine"] == {"decode_window": 10, "overlap": True, "enable_prefix_cache": True,
                              "prefill_chunk": 1024, "warmup_buckets": False}
    assert conf["paged"] == {"block_size": 64, "num_blocks": 4865, "max_batch": 32, "max_blocks_per_seq": 544}
    # the rehearsal selects and slides: both far under its contexts
    r, t = conf["rehearsal"], M.Cell(MANIFEST, the_cell()).traffic["rehearsal"]
    assert r["index_topk"] < t["system_tokens"] // 2 and r["sliding_window_size"] < t["system_tokens"] // 2
    traffic = M.Cell(MANIFEST, the_cell()).traffic["params"]
    assert traffic["clients"] == 1.5 * conf["paged"]["max_batch"] and traffic["system_tokens"] == 32768
    longest = traffic["system_tokens"] + traffic["turns"] * (
        traffic["user_tokens"]["max"] + traffic["answer_tokens"]["max"])
    assert longest + 2 * conf["engine"]["decode_window"] <= 544 * 64  # the table holds the longest session


def test_work_counts_match_hand_arithmetic():
    d = W.Dims.from_config(the_config())
    full = (5120 * 1024 + 1024 * 128 * 192 + 5120 * 576 + 512 * 128 * 256 + 5120 * 128 + 128 * 128 * 5120
            + 1024 * 64 * 128 + 5120 * 128 + 5120 * 64)
    sliding = 5120 * 1024 + 1024 * 64 * 256 + 5120 * 1088 + 1024 * 64 * 320 + 5120 * 64 + 64 * 128 * 5120
    assert work.mixer_params(d, work.FULL) == full == 144048128
    assert work.mixer_params(d, work.SLIDING) == sliding == 90832896
    assert work.expert_params(d) == 3 * 5120 * 1536 == 23592960
    assert (work.layers_of(d, work.FULL), work.layers_of(d, work.SLIDING), work.expert_layers(d)) == (2, 3, 4)
    fixed = 2 * full + 3 * sliding + 3 * 5120 * 13824 + 4 * (5120 * 256 + 23592960) + 5120 * 19008
    assert work.fixed_matmul_params(d) == fixed
    stored = work.stored_params(d)
    assert stored - fixed - 4 * 32 * 23592960 - 19008 * 5120 < 1e5  # norms and biases
    assert 4.08e9 < stored < 4.09e9  # 8.17 GB in bfloat16
    # 32 slots of 33,000 cached tokens: the score reads a key of 256 B a cached token a slot
    flops, bytes_ = work.score_work(d, 32, 32 * 33000)
    assert flops == 2 * 64 * 128 * 32 * 33000 and bytes_ == 32 * 33000 * 256 + 32 * 64 * (256 + 4)
    assert work.gather_bytes(d, 32 * 2048) == 32 * 2048 * 1152
    assert work.attend_flops(d, 32 * 2048) == 2 * 128 * (576 + 512) * 32 * 2048
    assert work.window_bytes(d, 32 * 513) == 32 * 513 * 2176
    step = work.decode_step_bytes(d, 32, 32 * 33000, 80)
    cache = 2 * (bytes_ + 32 * 2048 * 1152) + 3 * 32 * 513 * 2176
    assert step == (fixed + 80 * 23592960) * 2 + cache
    # selection leaves the cache a tenth of the step; the whole rows of 33k would be three times that
    assert 0.10 < cache / step < 0.13 and 2 * 32 * 33000 * 1152 > 0.35 * step


def op(seconds: float, detail: str = "", count: int = 2) -> dict:
    return {"seconds": seconds, "self_seconds": seconds, "count": count, "detail": detail}


def facts(stats: dict, ops=None, modules=None, dims=None) -> dict:
    trace = None if ops is None else {"ops": ops, "modules": modules or {}, "busy_s": 2.0}
    return {
        "dims": dims or the_config(), "peaks_of": "TPU v5 lite", "trace": trace,
        "engine": {"stats": stats, "decode_window": 10, "max_batch": 32,
                   "steps": [{"ts": 5.0, "active": 30}, {"ts": 8.0, "active": 32}, {"ts": 12.0, "active": 0}],
                   "requests": [{"prompt_tokens": 33000, "output_tokens": 100},
                                {"prompt_tokens": 8000, "output_tokens": 0}]},
    }


COUNTED = {"steps": 100, "sparse_keys_live": 66_000_000, "sparse_keys_selected": 4_096_000,
           "window_rows_read": 1_000_000, "moe_pairs_here": 4000 * 32, "moe_experts_touched": 4000 * 20,
           "moe_layer_steps": 4000}
OPS = {  # the decode program's parts by what they write; a chunk call's; what is no part
    "fusion.1": op(0.20, "bf16[32,34816,128]{2,1,0:T(8,128)(2,1)} fusion(%p0, %p1), kind=kLoop"),
    "fusion.2": op(0.30, "f32[32,34816]{1,0:T(8,128)} fusion(%fusion.1, %p2), kind=kOutput"),
    "sort.3": op(0.25, "(f32[32,2048]{1,0}, s32[32,2048]{1,0}) custom-call(%fusion.2), custom_call_target='TopK'"),
    "fusion.4": op(0.05, "bf16[32,2048,640]{2,1,0} fusion(%p3, %sort.3), kind=kLoop"),
    "fusion.5": op(0.04, "f32[32,128,2048]{2,1,0} fusion(%fusion.4, %q), kind=kOutput"),
    "fusion.6": op(0.02, "bf16[64,2048,640]{2,1,0} fusion(%p3), kind=kLoop"),  # a chunk call's gather
    "fusion.7": op(0.50, "bf16[32,5120]{1,0} fusion(%fusion.5), kind=kOutput"),  # a consumer: no part
    "while.8": op(0.90, "(s32[], f32[32,34816]{1,0}) while(%tuple), condition=%c, body=%b"),  # holds others
}
WINDOW = {"jit__decode(123)": op(4.0, count=20)}
OTHER = M.load_json("configs", "pangu-ultra-moe-serve-ep16-l5.json")  # latent attention that selects nothing


def hand_roofline() -> float:
    d = W.Dims.from_config(the_config())
    context, active = (33050 + 8000) / 2, 31.0  # ``_latent_moe.cached_tokens``: finished requests only
    context = 33050.0
    _, bytes_ = work.score_work(d, active, context * active)
    return 100.0 * (bytes_ / 819e9) / (0.50 / (20 * 10 * 2))


def hand_gather() -> float:
    d = W.Dims.from_config(the_config())
    return 100.0 * (work.gather_bytes(d, 31.0 * 2048) / 819e9) / (0.05 / (20 * 10 * 2))


def hand_decode() -> float:
    """80 experts touched a step (4 layers of 20), 31 slots of 33,050, a step of 20 ms."""
    d = W.Dims.from_config(the_config())
    return 100.0 * (work.decode_step_bytes(d, 31.0, 33050.0 * 31.0, 80.0) / 819e9) / (4.0 / 20 / 10)


CASES = [
    ("sparse_keys_read_pct", facts(COUNTED), 100.0 * 4_096_000 / 66_000_000),
    ("sparse_keys_read_pct", facts({"steps": 100}), None),  # no counters: the parent
    ("sparse_keys_read_pct", facts({"steps": 100, "sparse_keys_live": 0}), None),
    ("sparse_select_share_pct", facts(COUNTED, OPS, WINDOW), 100.0 * (0.20 + 0.30 + 0.25 + 0.05 + 0.04 + 0.02) / 2.0),
    ("sparse_select_share_pct", facts(COUNTED), None),  # no trace
    ("sparse_select_share_pct", facts(COUNTED, {"fusion.7": OPS["fusion.7"]}, WINDOW), None),
    ("sparse_select_share_pct", facts(COUNTED, OPS, WINDOW, dims=OTHER), None),  # another family's cell
    ("sparse_score_hbm_roofline", facts(COUNTED, OPS, WINDOW), hand_roofline()),
    ("sparse_score_hbm_roofline", facts(COUNTED, OPS, {}), None),  # no decode program traced
    ("sparse_score_hbm_roofline", facts(COUNTED, {"fusion.4": OPS["fusion.4"]}, WINDOW), None),  # no score part
    ("sparse_score_hbm_roofline", facts(COUNTED, OPS, WINDOW, dims=OTHER), None),
    ("sparse_gather_hbm_roofline", facts(COUNTED, OPS, WINDOW), hand_gather()),
    ("sparse_gather_hbm_roofline", facts(COUNTED, {"fusion.1": OPS["fusion.1"]}, WINDOW), None),  # no gather part
    ("sparse_gather_hbm_roofline", facts(COUNTED, OPS, WINDOW, dims=OTHER), None),
    ("sparse_decode_hbm_roofline", facts(COUNTED, OPS, WINDOW), hand_decode()),
    ("sparse_decode_hbm_roofline", facts(COUNTED), None),  # no trace
    ("sparse_decode_hbm_roofline", facts({"steps": 100}, OPS, WINDOW), None),  # no counters: the parent
    ("sparse_decode_hbm_roofline", facts(COUNTED, OPS, WINDOW, dims=OTHER), None),
    # what the cell joined: the experts' counters read by the accepted readers, against the 32 held
    ("moe_experts_touched_pct", facts(COUNTED), 100.0 * 20 / 32),
    ("moe_pairs_per_expert", facts(COUNTED), 1.6),
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
    assert 0 < hand_roofline() < 100 and 0 < hand_gather() < 100 and 0 < hand_decode() < 100


def test_a_whole_session_is_judged_at_every_position_the_program_served():
    from chipbench.drivers import serve_sparse_latent_moe as D

    script = {"system": 0, "turns": [{"user": [10, 11], "max_new_tokens": 2},
                                     {"user": [12], "max_new_tokens": 3}]}
    got = D.whole_session([1, 2, 3], script, [[20, 21], [22, 23, 24]])
    whole = [1, 2, 3, 10, 11, 20, 21, 12, 22, 23, 24]
    assert got["sequence"] == whole[:-1] and got["served"] == [20, 21, 22, 23, 24]
    # the logits at a position choose the token after it
    assert [whole[i + 1] for i in got["where"]] == got["served"]
    # a session played one turn only is judged at that turn's answer
    assert D.whole_session([1, 2, 3], script, [[20, 21]]) == {
        "sequence": [1, 2, 3, 10, 11, 20], "where": [4, 5], "served": [20, 21]}
    traffic = M.Cell(MANIFEST, the_cell()).traffic["params"]
    assert D.check_positions(traffic) == 512 >= traffic["turns"] * traffic["answer_tokens"]["max"]


def test_the_checked_sessions_are_those_played_furthest_and_drawn_from_the_seed():
    from chipbench.drivers import serve_sparse_latent_moe as D

    class Done:
        def __init__(self, cid, tokens, ok=True):
            self.cid, self.tokens, self.ok, self.sent = cid, tokens, ok, 1.0

    def session(j):
        return {"system": j % 2, "turns": [{"user": [100 + j], "max_new_tokens": 1}] * 3}

    plan = {"systems": [[1], [2]], "session": session}
    clients = [Done(f"{j}.{k}", [10 * j + k]) for j in range(5) for k in range(3 if j % 2 else 2)]
    clients.append(Done("4.2", [], ok=False))  # a request that failed is nobody's answer
    got = D._sessions(plan, clients, 2, seed=5)
    assert len(got) == 2 and got == D._sessions(plan, clients, 2, seed=5)
    for s in got:  # sessions 1 and 3 were played to the third turn
        assert s["sequence"][0] == 2 and len(s["served"]) == 3 and s["served"][0] in (10, 30)
    assert len(D._sessions(plan, clients, 4, seed=5)) == 2


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_prints_a_line_the_validator_accepts(trace, tmp_path):
    cell = the_cell()
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TMPDIR": str(tmp_path),
           "JAX_ENABLE_COMPILATION_CACHE": "false", "BENCH_RUN": "ignored"}
    run = subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload", cell, "--seed", str(2**31 + 59),
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
        assert {"sparse_keys_read_pct", "moe_experts_touched_pct", "moe_pairs_per_expert"} <= set(line["metrics"])
        # the rehearsal selects: 16 of contexts of 64-110
        assert 10 < line["metrics"]["sparse_keys_read_pct"]["value"] < 35
