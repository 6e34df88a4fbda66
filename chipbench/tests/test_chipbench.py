"""The benchmark's own tests, on the CPU: the contract of the last line, the
generators, the trace reduction, the counts of work, the manifest's rules, the
tiny rehearsal of each driver, and compile-only checks of the main kernels at
Mistral-7B widths for a described v5e.

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q
"""
from __future__ import annotations

import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import manifest as M
from chipbench import stats, trace_reduce, validate, work
from chipbench import weights as W

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = M.load_manifest()
MISTRAL = W.Dims(vocab=32768, hidden=4096, layers=32, heads=32, kv_heads=8, head_dim=128,
                 ffn=14336, rope_theta=1e6, rms_eps=1e-5)


def first_cell(driver: str) -> str:
    """A cell of each driver, found in the manifest and not by name."""
    for w in MANIFEST["workloads"]:
        if M.load_json("traffic", w["traffic"] + ".json")["driver"] == driver:
            return w["name"]
    raise AssertionError(driver)


# --- the last line ----------------------------------------------------------
def good_line(workload: str, trace: int) -> dict:
    section = "per_layer" if trace else "end_to_end"
    line = {
        "correct": True, "attempted": 40, "failed": 0,
        "metrics": {m["name"]: {"value": 12.5, "unit": m["unit"]}
                    for m in M.metrics_for(MANIFEST, workload, section)},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                   "memory_peak_bytes": 13958643712},
    }
    if trace:
        line["device"].update(window_s=4.0, busy_s=3.5)
        line["breakdown"] = {"device_ops": [["fusion.1", 1.5]], "idle_gaps": [["unattributed", 0.1]]}
    return line


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("driver", ["serve", "train"])
def test_validator_accepts_a_good_line(driver, trace):
    cell = first_cell(driver)
    assert validate.check_line(json.dumps(good_line(cell, trace)), MANIFEST, cell, trace) == []


def _drop(path):
    def change(line):
        node = line
        for k in path[:-1]:
            node = node[k]
        del node[path[-1]]
    return change


def _set(path, value):
    def change(line):
        node = line
        for k in path[:-1]:
            node = node[k]
        node[path[-1]] = value
    return change


def _first_metric(change):
    def apply(line):
        name = next(iter(line["metrics"]))
        change(line["metrics"], name)
    return apply


WRONG = {
    "no device key": (0, _drop(["device", "memory_peak_bytes"]), "memory_peak_bytes is missing"),
    "no device": (0, _drop(["device"]), "'device' is missing"),
    "busy_s absent in a traced run": (1, _drop(["device", "busy_s"]), "busy_s is missing"),
    "window_s absent in a traced run": (1, _drop(["device", "window_s"]), "window_s is missing"),
    "busy_s of 0": (1, _set(["device", "busy_s"], 0.0), "not above 0"),
    "busy_s above window_s": (1, _set(["device", "busy_s"], 4.5), "at most window_s"),
    "metric without a unit": (0, _first_metric(lambda ms, n: ms[n].pop("unit")), "not {value, unit}"),
    "metric with another unit": (0, _first_metric(lambda ms, n: ms[n].update(unit="furlongs")), "has the unit"),
    "metric that is not a number": (0, _first_metric(lambda ms, n: ms[n].update(value=float("nan"))), "no finite number"),
    "a metric missing": (1, _first_metric(lambda ms, n: ms.pop(n)), "is missing"),
    "an extra reported metric": (0, _set(["metrics", "made_up_ms"], {"value": 1.0, "unit": "ms"}), "is not one of this cell's"),
    "a per-layer metric on the end-to-end line": (0, _set(["metrics", "decode_step_ms"], {"value": 1.0, "unit": "ms"}), "is not one of this cell's"),
    "an extra key on the line": (0, _set(["facts"], {}), "does not belong"),
    "breakdown on an untraced line": (0, _set(["breakdown"], {}), "does not belong"),
    "another platform": (0, _set(["device", "platform"], "cpu"), "device.platform"),
    "another count of devices": (0, _set(["device", "count"], 4), "the cell asks for"),
    "failed above attempted": (0, _set(["failed"], 41), "above it"),
    "correct that is not a truth value": (0, _set(["correct"], "yes"), "not true or false"),
    "a breakdown of 11 entries": (1, _set(["breakdown", "device_ops"], [["x", 0.1]] * 11), "at most 10"),
    "a roofline share above 105": (1, _set(["metrics", "decode_hbm_roofline"], {"value": 120.0, "unit": "%"}), "above 105%"),
}


@pytest.mark.parametrize("case", sorted(WRONG))
def test_validator_rejects(case):
    trace, change, says = WRONG[case]
    cell = first_cell("serve")
    line = good_line(cell, trace)
    change(line)
    wrong = validate.check_line(json.dumps(line), MANIFEST, cell, trace)
    assert any(says in w for w in wrong), wrong


@pytest.mark.parametrize("text", ["", "not json", "[1, 2]", "3"])
def test_validator_rejects_what_is_no_object(text):
    assert validate.check_line(text, MANIFEST, first_cell("serve"), 0)


# --- BENCHMARK.json ---------------------------------------------------------
def test_manifest_passes_the_contracts_rules():
    assert validate.check_manifest(MANIFEST) == []


BAD_MANIFESTS = {
    "a name with a space": lambda m: m["workloads"][0].update(name="serve chat"),
    "a name with a slash": lambda m: m["end_to_end"][0].update(name="ttft/p95"),
    "a unit with a space": lambda m: m["end_to_end"][0].update(unit="tokens per second"),
    "a unit with a Greek letter": lambda m: m["per_layer"][0].update(unit="μs"),
    "a bound over 0.1": lambda m: m["end_to_end"][0].update(bound=0.2),
    "an extra key on a metric": lambda m: m["per_layer"][0].update(why="because"),
    "two four-chip cells of four": lambda m: [w.update(chips=4) for w in m["workloads"][:2]],
    "a per-layer metric that moves nothing": lambda m: m["per_layer"][0].update(moves="nothing_ms"),
    "a program-read end-to-end metric": lambda m: m["end_to_end"][0].update(source="program_counter"),
    "no setup_s": lambda m: m["end_to_end"].pop(-1),
    "a path out of the repo": lambda m: m.update(paths=["../elsewhere"]),
    "run_seconds of 52": lambda m: m.update(run_seconds=52),
}


@pytest.mark.parametrize("case", sorted(BAD_MANIFESTS))
def test_manifest_rules_reject(case):
    m = copy.deepcopy(MANIFEST)
    BAD_MANIFESTS[case](m)
    assert validate.check_manifest(m), case


def test_every_metric_has_a_reader_that_agrees_with_the_manifest():
    for m in MANIFEST["end_to_end"]:
        r = M.reader("end_to_end", m["name"])
        assert (r.UNIT, r.SOURCE) == (m["unit"], m["source"]), m["name"]
    for m in MANIFEST["per_layer"]:
        r = M.reader("layer_metrics", m["name"])
        assert (r.UNIT, r.SOURCE, r.LAYER, r.MOVES) == (
            m["unit"], m["source"], m["layer"], m["moves"]), m["name"]


def test_no_python_file_of_the_benchmark_names_a_cell():
    names = [w["name"] for w in MANIFEST["workloads"]]
    for base, _dirs, files in os.walk(M.HERE):
        if os.path.basename(base) == "tests":
            continue
        for f in files:
            if f.endswith(".py"):
                text = open(os.path.join(base, f)).read()
                assert not [n for n in names if n in text], os.path.join(base, f)


def test_configurations_keep_the_published_widths():
    published = dict(hidden_size=4096, intermediate_size=14336, num_attention_heads=32,
                     num_key_value_heads=8, head_dim=128, vocab_size=32768,
                     rope_theta=1e6, rms_norm_eps=1e-5, max_position_embeddings=32768)
    for c in MANIFEST["configs"]:
        conf = json.load(open(os.path.join(M.ROOT, c["file"])))
        assert {k: conf[k] for k in published} == published, c["name"]
        assert c["reduced"] == ["num_hidden_layers"] == list(conf["reduced"])
        assert conf["num_hidden_layers"] == conf["reduced"]["num_hidden_layers"]["to"] < 32
        assert conf["source"] == c["source"]


# --- generators -------------------------------------------------------------
def _traffic(generator: str) -> dict:
    for w in MANIFEST["workloads"]:
        t = M.load_json("traffic", w["traffic"] + ".json")
        if t["generator"] == generator:
            return t
    raise AssertionError(generator)


def test_open_loop_is_a_pure_function_of_seed_and_parameters():
    from chipbench.generators import open_loop

    params = _traffic("open_loop")["params"]
    a = open_loop.plan(params, 2**31 + 7, 40.0, 32768)
    b = open_loop.plan(params, 2**31 + 7, 40.0, 32768)
    c = open_loop.plan(params, 11, 40.0, 32768)
    assert a == b and a != c
    # every seed gets the same set of sizes and gaps, in another order
    sizes = lambda p: sorted((len(r["prompt"]), r["max_new_tokens"]) for r in p["requests"])
    assert sorted(x for x, _ in sizes(a)) == sorted(x for x, _ in sizes(c))
    assert sorted(y for _, y in sizes(a)) == sorted(y for _, y in sizes(c))
    lens = [len(r["prompt"]) for r in a["requests"]]
    lo, hi = params["prompt_tokens"]["min"], params["prompt_tokens"]["max"]
    assert lo <= min(lens) and max(lens) <= hi
    assert abs(np.median(lens) - params["prompt_tokens"]["median"]) < 16
    n = len(a["requests"])
    assert abs(n / (params["ramp_s"] + 40.0) - params["rate_per_s"]) < 0.15 * params["rate_per_s"]
    # no two prompts share a block
    bs = 16
    firsts = {tuple(r["prompt"][:bs]) for r in a["requests"]}
    assert len(firsts) == n


def test_sessions_share_what_their_file_says_they_share():
    from chipbench.generators import sessions

    params = _traffic("sessions")["params"]
    plan = sessions.plan(params, 5, 40.0, 32768)
    again = sessions.plan(params, 5, 40.0, 32768)
    assert plan["systems"] == again["systems"]
    assert [plan["session"](j) for j in range(8)] == [again["session"](j) for j in range(8)]
    assert len(plan["systems"]) == params["system_prompts"]
    assert plan["clients"] == params["clients"]
    bs, answer = 16, [1] * 100
    prompts_by_system = {}
    for j in range(32):
        s = plan["session"](j)
        assert len(s["turns"]) == params["turns"]
        history = list(plan["systems"][s["system"]])
        previous = None
        for turn in s["turns"]:
            u, a = params["user_tokens"], params["answer_tokens"]
            assert u["min"] <= len(turn["user"]) <= u["max"]
            assert a["min"] <= turn["max_new_tokens"] <= a["max"]
            prompt = history + turn["user"]
            if previous is not None:  # a turn's prompt begins with the whole previous prompt
                assert prompt[:len(previous)] == previous
            previous = prompt
            history = prompt + answer[:turn["max_new_tokens"]]
        prompts_by_system.setdefault(s["system"], []).append(previous)
    # block by block: sessions of one system prompt share exactly its blocks
    shared_blocks = params["system_tokens"] // bs
    for system, prompts in prompts_by_system.items():
        for p, q in zip(prompts, prompts[1:]):
            same = 0
            while p[same * bs:(same + 1) * bs] == q[same * bs:(same + 1) * bs]:
                same += 1
            assert same == shared_blocks
    a, b = plan["systems"][0], plan["systems"][1]
    assert a[:bs] != b[:bs]


def test_token_batches_are_a_pure_function_of_seed_and_index():
    from chipbench.generators import token_batches as tb

    assert np.array_equal(tb.batch(2**31 + 1, 3, 2, 64, 32768), tb.batch(2**31 + 1, 3, 2, 64, 32768))
    assert not np.array_equal(tb.batch(1, 3, 2, 64, 32768), tb.batch(1, 4, 2, 64, 32768))
    assert tb.batch(1, 0, 2, 64, 32768).shape == (2, 65)


# --- trace reduction --------------------------------------------------------
def test_trace_reduce_on_a_trace_with_a_known_answer():
    planes = json.load(open(os.path.join(HERE, "data", "two_chips.json")))
    planes = [{"name": p["name"], "lines": [
        {"name": ln["name"], "events": [tuple(e) for e in ln["events"]]} for ln in p["lines"]]}
        for p in planes]
    r = trace_reduce.reduce_planes(planes)
    assert r["window_s"] == pytest.approx(40e-6)
    # chip 0: [0, 20) and [30, 40) busy = 30 us; chip 1 the same: mean 30 us of 40
    assert r["busy_s_per_device"] == pytest.approx([30e-6, 30e-6])
    assert r["busy_s"] / r["window_s"] == pytest.approx(0.75)
    assert r["modules"]["jit_step(1)"]["seconds"] == pytest.approx(30e-6)
    assert r["modules"]["jit_step(1)"]["count"] == 2
    # chip 0 sits 10 us in an all-gather, chip 1 in none: mean 5 us
    assert r["collective_exposed_s"] == pytest.approx(5e-6)
    # the while holds fusion.1 and the all-gather: none of its 20 us is its own
    assert r["ops"]["while.1"]["seconds"] == pytest.approx(10e-6)
    assert r["ops"]["while.1"]["self_seconds"] == pytest.approx(0.0)
    assert r["idle_gaps"][0] == ["python3:make_batch", pytest.approx(10e-6)]
    assert trace_reduce.seconds_matching(r["modules"], r"jit_step") == (pytest.approx(30e-6), 2)
    # fusion.1 (found by its detail): 10 us on chip 0, 20 us on chip 1, mean 15
    assert trace_reduce.seconds_matching(r["ops"], r"calls=dot")[0] == pytest.approx(15e-6)
    assert trace_reduce.short_name('%fusion.4 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop') == (
        "fusion.4", "bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop")
    top = trace_reduce.breakdown(r)
    assert top["device_ops"][0][0] == "fusion.1" and len(top["idle_gaps"]) <= 10


def test_trace_reduce_reads_a_recorded_trace():
    """Three matmuls recorded on a CPU (7 KB): the XLA client's threads stand
    in for the device, and some of the window is busy, not all of it."""
    r = trace_reduce.reduce_planes(
        trace_reduce.read_xplane(os.path.join(HERE, "data", "cpu_matmul.xplane.pb")))
    assert 0 < r["busy_s"] <= r["window_s"]
    assert any(name.startswith("dot") for name in r["ops"])
    assert r["modules"]["jit_<lambda>"]["count"] >= 3


def test_a_trace_without_a_device_fails_loudly():
    with pytest.raises(RuntimeError, match="no device plane"):
        trace_reduce.reduce_planes([{"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [("f", 0.0, 5.0, "")]}]}])


# --- work, peaks, statistics ------------------------------------------------
def test_work_counts_match_hand_arithmetic_for_mistral_7b():
    # one layer: q and o 4096 x 4096 each, k and v 4096 x 1024 each, three of 4096 x 14336
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808 == work.layer_matmul_params(MISTRAL)
    head = 4096 * 32768
    assert head == 134_217_728
    assert work.matmul_params(MISTRAL) == 32 * layer + head == 7_113_539_584
    # stored: + embedding, + 2 norms a layer, + final norm = the published 7.25B
    assert work.stored_params(MISTRAL) == 32 * (layer + 8192) + 2 * head + 4096 == 7_248_023_552
    # causal attention forward at 4096: 2 matmuls x 2 flops x 32 heads x 128 x 4096 x 4097 / 2 a layer
    attn = 32 * 4 * 32 * 128 * 4096 * 4097 // 2
    assert work.causal_attention_flops(MISTRAL, 4096) == attn
    assert work.train_flops_per_token(MISTRAL, 4096) == pytest.approx(
        6 * 7_113_539_584 + 3 * attn / 4096)
    assert work.kv_bytes_per_token(MISTRAL) == 2 * 32 * 8 * 128 * 2 == 131_072
    l24 = W.Dims(**{**MISTRAL.__dict__, "layers": 24})
    assert work.kv_bytes_per_token(l24) == 98_304
    assert work.decode_step_bytes(l24, 10_000) == (24 * layer + head) * 2 + 10_000 * 98_304
    flops, bytes_ = work.flash_fwd_work(MISTRAL, 3, 4096)
    assert flops == 3 * 4 * 32 * 128 * 4096 * 4097 // 2
    assert bytes_ == 2 * 3 * 32 * 4096 * 128 * 2 + 2 * 3 * 8 * 4096 * 128 * 2 + 3 * 32 * 4096 * 4


def test_an_unknown_device_has_no_peaks():
    from chipbench.peaks import peaks_for

    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("cpu")


def test_spread_is_the_contracts():
    xs = [10.0, 10.1, 9.9, 10.2, 10.0, 9.8]
    import statistics

    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / 10.0)
    assert stats.percentile(list(range(101)), 95) == 95


# --- weights and reference --------------------------------------------------
def test_reference_agrees_with_the_program_in_float32_and_the_control_does_not():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import transformer as tf

    from chipbench import reference as R

    d = W.Dims(vocab=256, hidden=64, layers=3, heads=4, kv_heads=2, head_dim=16, ffn=128,
               rope_theta=1e6, rms_eps=1e-5)
    key = W.seed_key(2**31 + 12345)
    params = jax.jit(lambda k: W.make_params(k, d, jnp.float32))(key)
    one = jax.jit(lambda k: W.layer_params(k, 1, d))(key)
    assert all(np.array_equal(one[n], params["layers"][n][1]) for n in one)
    cfg = tf.TransformerConfig(vocab_size=256, d_model=64, n_layers=3, n_heads=4, n_kv_heads=2,
                               d_ff=128, rope_theta=1e6, max_seq_len=128, dtype=jnp.float32, remat=False)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 33)), jnp.int32)
    program = tf.forward(params, tokens[:, :-1], cfg)
    ref = R.stream_logits(key, tokens[:, :-1], d, jnp.float32)
    assert float(jnp.abs(program - ref).max()) < 1e-4
    low = R.stream_logits(key, tokens[:, :-1], d, jnp.float32, "int8")
    assert float(jnp.linalg.norm(low - ref) / jnp.linalg.norm(ref)) > 5e-3
    p_loss, p_grads = jax.value_and_grad(lambda p: tf.loss_fn(p, {"tokens": tokens}, cfg))(params)
    num = den = 0.0
    seen = []
    for kind, i, piece in R.stream_loss_and_grads(key, tokens, d, jnp.float32):
        if kind == "loss":
            assert abs(float(piece) - float(p_loss)) < 1e-5
            continue
        seen.append((kind, i))
        for name, g in piece.items():
            other = p_grads["layers"][name][i] if kind == "layer" else p_grads[name]
            num += float(jnp.sum((other - g) ** 2))
            den += float(jnp.sum(g ** 2))
    assert seen == [("top", None), ("layer", 2), ("layer", 1), ("layer", 0), ("top", None)]
    assert (num / den) ** 0.5 < 1e-4


# --- the tiny rehearsal of each driver --------------------------------------
@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("driver", ["train", "serve"])
def test_rehearsal_prints_a_line_the_validator_accepts(driver, trace, tmp_path):
    cell = first_cell(driver)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TMPDIR": str(tmp_path),
           "JAX_ENABLE_COMPILATION_CACHE": "false", "BENCH_RUN": "ignored"}
    run = subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload", cell, "--seed", str(2**31 + 3),
         "--seconds", "3", "--trace", str(trace), "--rehearse"],
        cwd=M.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-2000:]
    last = run.stdout.strip().splitlines()[-1]
    line = json.loads(last)
    assert line["device"]["platform"] == "cpu"  # and so never a measurement
    lacking = {m["name"] for m in M.metrics_for(MANIFEST, cell, "per_layer")
               if m["source"] == "device_trace"}
    assert validate.check_line(last, MANIFEST, cell, trace, platform="cpu", may_lack=lacking) == []
    assert any("platform" in w for w in validate.check_line(last, MANIFEST, cell, trace, may_lack=lacking))


def test_a_run_without_an_accelerator_fails_and_prints_no_line(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TMPDIR": str(tmp_path),
           "JAX_ENABLE_COMPILATION_CACHE": "false"}
    run = subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload", first_cell("train"), "--seed", "1",
         "--seconds", "2", "--trace", "0"],
        cwd=M.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert run.returncode != 0
    assert not [ln for ln in run.stdout.splitlines() if ln.startswith("{")]
    assert "found no accelerator" in run.stdout + run.stderr


# --- compile-only, for a described v5e --------------------------------------
@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps the TPU's compiler away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def tpu_lowering(monkeypatch):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setenv("RAY_TPU_FORCE_PALLAS", "1")  # the default backend here is the CPU
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def test_flash_forward_compiles_for_v5e_at_mistral_widths(one_chip, tpu_lowering):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import flash_attention

    q = jax.ShapeDtypeStruct((1, 32, 4096, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8, 4096, 128), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(lambda q, k, v: flash_attention(q, k, v, True, None)).lower(q, kv, kv).compile().as_text()
    assert text.count("tpu_custom_call") == 1


def test_paged_decode_step_compiles_for_v5e_at_mistral_widths(one_chip, tpu_lowering):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import transformer as tf
    from ray_tpu.models.paged import PagedConfig, init_paged_cache, paged_decode_step

    conf = json.load(open(os.path.join(M.HERE, "configs", MANIFEST["configs"][0]["file"].split("/")[-1])))
    dims = W.Dims.from_config({**conf, "num_hidden_layers": 2})
    cfg = tf.TransformerConfig(
        vocab_size=dims.vocab, d_model=dims.hidden, n_layers=2, n_heads=dims.heads,
        n_kv_heads=dims.kv_heads, d_ff=dims.ffn, rope_theta=dims.rope_theta, max_seq_len=1568,
        dtype=jnp.bfloat16, remat=False)
    p = PagedConfig(block_size=16, num_blocks=129, max_batch=32, max_blocks_per_seq=98)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        lambda k: W.make_params(k, dims, jnp.bfloat16), jax.ShapeDtypeStruct((2,), jnp.uint32)))
    cache = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(lambda: init_paged_cache(cfg, p)))
    compiled = jax.jit(lambda prm, t, c, tab, ln: paged_decode_step(prm, cfg, t, c, tab, ln)).lower(
        params, sds((32,), np.int32), cache, sds((32, 98), np.int32), sds((32,), np.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 * 2**30
