"""End-to-end serve & train telemetry.

Covers the serve-path SLO histograms (queue/TTFT/TPOT/e2e), span
propagation across a full proxy → handle → replica → engine hop, the
engine flight recorder, ``state.summarize_serve()``, the
``/api/serve/engine`` endpoint, and the Grafana factory's serve/train
rows. Reference test models: python/ray/serve/tests/test_metrics.py +
test_telemetry.py.
"""
import json
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.util import state as state_api


def _wait_until(cond, timeout=12.0, interval=0.2):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


def _hist_series(snap, name):
    """{tags_tuple: histogram_state} for one histogram metric."""
    if name not in snap:
        return {}
    return {tuple(map(tuple, k)): v for k, v in snap[name]["series"]}


@pytest.fixture
def traced_serve_cluster(monkeypatch):
    """A cluster with tracing ON everywhere (driver + spawned workers
    inherit RAY_TPU_TRACE) and serve torn down after the test."""
    from ray_tpu.util import tracing

    monkeypatch.setenv("RAY_TPU_TRACE", "1")
    ray_tpu.init(num_cpus=4, resources={"TPU": 4})
    tracing.maybe_enable_from_env()
    yield ray_tpu
    serve.shutdown()
    ray_tpu.shutdown()
    tracing.disable_tracing()


@serve.deployment(name="llm", max_ongoing_requests=8)
class _LLM:
    def __init__(self):
        from ray_tpu.models.paged import PagedConfig
        from ray_tpu.models.transformer import TransformerConfig, init_params
        from ray_tpu.serve.llm_engine import LLMEngine

        cfg = TransformerConfig.tiny(dtype=jnp.float32, remat=False)
        params = init_params(jax.random.PRNGKey(0), cfg)
        self.engine = LLMEngine(
            params, cfg,
            PagedConfig(block_size=8, num_blocks=17, max_batch=4,
                        max_blocks_per_seq=4),
        )
        self.engine.start()

    def __call__(self, prompt_ids):
        req = self.engine.add_request(
            [int(t) for t in prompt_ids], max_new_tokens=24
        )
        for tok in req.tokens(timeout=180):
            yield {"tok": int(tok)}


def _stream_tokens(port, prompt):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/llm",
        data=json.dumps(prompt).encode(),
        headers={"Accept": "application/x-ndjson",
                 "Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=240) as resp:
        return [json.loads(l)["tok"] for l in resp.read().decode().splitlines() if l]


def test_serve_slo_metrics_spans_and_engine_state(traced_serve_cluster):
    """THE acceptance path: a request through proxy → replica → LLMEngine
    yields (a) a connected span tree, (b) nonzero queue/TTFT/TPOT/e2e
    histograms tagged {deployment, replica}, (c) flight-recorder state
    via /api/serve/engine and summarize_serve()."""
    serve.run(_LLM.bind(), http_port=0)
    try:
        port = serve.api.get_proxy_port()
        toks = _stream_tokens(port, [2, 4, 6])
        assert len(toks) == 24
        toks2 = _stream_tokens(port, [1, 3, 5, 7])
        assert len(toks2) == 24

        # -- (b) SLO histograms reach the controller with tags ----------
        def _have_all():
            snap = state_api.metrics_snapshot()
            return all(
                _hist_series(snap, n)
                for n in ("serve_request_queue_ms", "serve_ttft_ms",
                          "serve_tpot_ms", "serve_e2e_ms")
            )

        assert _wait_until(_have_all), sorted(state_api.metrics_snapshot())
        snap = state_api.metrics_snapshot()
        for name in ("serve_request_queue_ms", "serve_ttft_ms",
                     "serve_tpot_ms", "serve_e2e_ms"):
            series = _hist_series(snap, name)
            tags, st = next(iter(series.items()))
            tagd = dict(tags)
            assert tagd["deployment"] == "llm", (name, tags)
            assert tagd.get("replica"), (name, tags)
            assert st["state"][-1] > 0, (name, st)  # count > 0
        # TTFT ≤ e2e by construction.
        ttft_sum = sum(v["state"][-2] for v in _hist_series(snap, "serve_ttft_ms").values())
        e2e_sum = sum(v["state"][-2] for v in _hist_series(snap, "serve_e2e_ms").values())
        assert 0 < ttft_sum <= e2e_sum

        # Prometheus exposition carries the tagged buckets.
        url = state_api.dashboard_url()
        with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
            text = r.read().decode()
        assert 'serve_ttft_ms_bucket{' in text
        assert 'deployment="llm"' in text

        # -- (c) engine flight recorder via state API + HTTP ------------
        # Engines push ~1/s; wait for a snapshot that includes both
        # finished requests, not just the first mid-stream heartbeat.
        assert _wait_until(
            lambda: any(
                s.get("stats", {}).get("tokens", 0) >= 48
                for s in state_api.serve_state().values()
            )
        )
        engines = state_api.serve_state()
        key, esnap = max(
            engines.items(), key=lambda kv: kv[1]["stats"].get("tokens", 0)
        )
        assert key.startswith("llm/")
        assert esnap["stats"]["tokens"] >= 48
        assert esnap["steps"], esnap.keys()  # step ring tail
        step = esnap["steps"][-1]
        for field in ("active", "waiting", "kv_blocks_free", "kv_utilization",
                      "tokens", "prefills", "admitted", "preemptions"):
            assert field in step, step
        assert esnap["recent_requests"], esnap["stats"]
        rec = esnap["recent_requests"][-1]
        assert rec["output_tokens"] == 24
        assert rec["ttft_ms"] is not None and rec["e2e_ms"] >= rec["ttft_ms"]

        summary = state_api.summarize_serve()
        assert summary["llm"]["engines"] >= 1
        assert summary["llm"]["finished_requests"] >= 2
        lat = summary["llm"]["latency_ms"]
        assert lat["e2e_ms"]["count"] >= 2
        assert 0 < lat["e2e_ms"]["p50"] <= lat["e2e_ms"]["p95"]

        with urllib.request.urlopen(url + "/api/serve/engine", timeout=30) as r:
            http_engines = json.loads(r.read())
        assert any(k.startswith("llm/") for k in http_engines)

        # -- (a) connected span tree ------------------------------------
        from ray_tpu.core import api
        from ray_tpu.util import tracing

        def _spans():
            return tracing.collect_spans(api._session_dir)

        def _tree_connected():
            events = _spans()
            by_name = {}
            for e in events:
                by_name.setdefault(e["name"], []).append(e)
            proxies = by_name.get("proxy:/llm", [])
            if not proxies:
                return False
            for p in proxies:
                tid = p["args"]["trace_id"]
                linked = [
                    e for e in events
                    if e["args"].get("trace_id") == tid and e is not p
                ]
                names = {e["name"] for e in linked}
                if (
                    "handle:llm.__call__" in names
                    and "replica:llm.__call__" in names
                    and "engine:request" in names
                ):
                    return True
            return False

        assert _wait_until(_tree_connected, timeout=15), sorted(
            {e["name"] for e in _spans()}
        )
    finally:
        serve.delete("llm")


def test_flight_recorder_rings_and_summary(tmp_path):
    """Unit: ring bounds, request records, percentile summary."""
    from ray_tpu.serve.llm_engine import FlightRecorder

    fr = FlightRecorder(step_capacity=4, request_capacity=3)
    for i in range(10):
        fr.record_step({"ts": float(i), "active": i, "waiting": 0,
                        "kv_blocks_free": 8, "kv_utilization": 0.5,
                        "tokens": 1, "prefills": 0, "preemptions": 0,
                        "admitted": 0})
    assert len(fr.steps) == 4  # fixed-size ring
    assert fr.steps[0]["ts"] == 6.0  # oldest evicted
    for i in range(5):
        fr.record_request({"rid": i, "ts": float(i), "prompt_tokens": 3,
                           "output_tokens": 8, "queue_ms": 1.0 + i,
                           "ttft_ms": 2.0 + i, "tpot_ms": 0.5,
                           "e2e_ms": 10.0 * (i + 1)})
    assert len(fr.requests) == 3
    snap = fr.snapshot()
    assert len(snap["steps"]) == 4 and len(snap["recent_requests"]) == 3
    lat = snap["latency_ms"]
    assert lat["e2e_ms"]["count"] == 3
    assert lat["e2e_ms"]["p50"] == 40.0  # of [30, 40, 50]
    assert lat["e2e_ms"]["p99"] == 50.0
    assert lat["tpot_ms"]["p50"] == 0.5


def test_engine_records_flight_data_standalone(ray_start_regular):
    """A standalone engine (no serve) fills the recorder and can push its
    snapshot to the controller for summarize_serve()."""
    from ray_tpu.models.paged import PagedConfig
    from ray_tpu.models.transformer import TransformerConfig, init_params
    from ray_tpu.serve.llm_engine import LLMEngine

    cfg = TransformerConfig.tiny(dtype=jnp.float32, remat=False)
    params = init_params(jax.random.PRNGKey(7), cfg)
    eng = LLMEngine(params, cfg,
                    PagedConfig(block_size=8, num_blocks=33, max_batch=4,
                                max_blocks_per_seq=8))
    prompts = [[5, 9, 2], [17, 1, 8, 4]]
    eng.generate_batch(prompts, max_new_tokens=12)
    assert len(eng.recorder.steps) >= 1
    assert len(eng.recorder.requests) == 2
    rec = list(eng.recorder.requests)[0]
    assert rec["output_tokens"] == 12
    assert rec["queue_ms"] is not None and rec["queue_ms"] >= 0
    assert rec["tpot_ms"] is not None and rec["tpot_ms"] > 0
    assert eng.stats["admitted"] == 2
    assert eng.stats["prompt_tokens"] == 7
    assert eng.stats["finished"] == 2

    snap = eng.report_state()
    assert snap["occupancy"]["active"] == 0
    dep = eng.metrics_tags["deployment"]
    assert _wait_until(lambda: dep in state_api.summarize_serve())
    summary = state_api.summarize_serve()[dep]
    assert summary["finished_requests"] == 2
    assert summary["latency_ms"]["ttft_ms"]["count"] == 2


def test_batch_metrics_recorded(ray_start_regular):
    """@serve.batch flushes feed serve_batch_size / serve_batch_wait_ms."""
    import threading

    from ray_tpu.util.metrics import flush

    @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.2)
    def double(items):
        return [2 * x for x in items]

    results = {}

    def call(i):
        results[i] = double(i)

    threads = [threading.Thread(target=call, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert results == {i: 2 * i for i in range(4)}
    flush()

    def _series():
        snap = state_api.metrics_snapshot()
        return _hist_series(snap, "serve_batch_size")

    assert _wait_until(lambda: _series())
    tags, st = next(iter(_series().items()))
    assert dict(tags)["fn"] == "double"
    assert st["state"][-1] >= 1  # at least one flush observed
    wait = _hist_series(state_api.metrics_snapshot(), "serve_batch_wait_ms")
    assert wait and next(iter(wait.values()))["state"][-1] >= 1


def test_grafana_serve_and_train_rows():
    """The dashboard factory groups serve/train metrics into rows with
    histogram-quantile panels (pure function: fake snapshot in)."""
    from ray_tpu.util.grafana import generate_dashboard

    snapshot = {
        "serve_ttft_ms": {"type": "histogram", "description": "ttft",
                          "series": []},
        "serve_engine_active_slots": {"type": "gauge", "description": "",
                                      "series": []},
        "train_step_wall_ms": {"type": "histogram", "description": "wall",
                               "series": []},
        "my_app_total": {"type": "counter", "description": "", "series": []},
    }
    dash = generate_dashboard(snapshot)
    rows = [p for p in dash["panels"] if p["type"] == "row"]
    row_titles = [r["title"] for r in rows]
    assert row_titles == ["Serve SLO", "Serve Engine", "Train", "Application"]
    by_title = {p["title"]: p for p in dash["panels"] if p["type"] != "row"}
    q = by_title["serve_ttft_ms (quantiles)"]["targets"]
    assert any("histogram_quantile(0.95" in t["expr"] for t in q)
    assert any("histogram_quantile(0.99" in t["expr"]
               for t in by_title["train_step_wall_ms (quantiles)"]["targets"])
    assert "my_app_total (rate)" in by_title
    # Rows precede their panels: Serve SLO row sits above the ttft panel.
    order = [p["title"] for p in dash["panels"]]
    assert order.index("Serve SLO") < order.index("serve_ttft_ms (quantiles)")
    assert order.index("Train") < order.index("train_step_wall_ms (quantiles)")
    # Importability invariants from the pre-row factory still hold.
    assert all(p["datasource"] == "${datasource}" for p in dash["panels"])


def test_proxy_request_metrics(traced_serve_cluster):
    """Proxy-level counters/latency, including 404s."""
    @serve.deployment(name="echo2")
    def echo(x):
        return {"echo": x}

    serve.run(echo.bind(), http_port=0)
    try:
        port = serve.api.get_proxy_port()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/echo2", data=json.dumps("hi").encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            assert json.loads(r.read()) == {"echo": "hi"}
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"http://127.0.0.1:{port}/nope", timeout=30)

        def _counts():
            snap = state_api.metrics_snapshot()
            if "serve_proxy_requests_total" not in snap:
                return {}
            return {tuple(map(tuple, k)): v
                    for k, v in snap["serve_proxy_requests_total"]["series"]}

        def _have_both():
            c = _counts()
            codes = {dict(k).get("code") for k in c}
            return {"200", "404"} <= codes

        assert _wait_until(_have_both), _counts()
        c = _counts()
        ok = next(v for k, v in c.items()
                  if dict(k) == {"route": "/echo2", "code": "200"})
        assert ok >= 1
    finally:
        serve.delete("echo2")


def test_engine_perf_suite_reported(ray_start_regular):
    """The perf-suite engine (prefix cache + overlap) reports its cache
    and overlap gauges through report_state -> controller ->
    summarize_serve: hit rate, resident blocks, speculated-window
    occupancy (backs the GET /api/serve/engine payload)."""
    from ray_tpu.models.paged import PagedConfig
    from ray_tpu.models.transformer import TransformerConfig, init_params
    from ray_tpu.serve.llm_engine import LLMEngine

    cfg = TransformerConfig.tiny(dtype=jnp.float32, remat=False)
    params = init_params(jax.random.PRNGKey(7), cfg)
    eng = LLMEngine(
        params, cfg,
        PagedConfig(block_size=8, num_blocks=33, max_batch=4,
                    max_blocks_per_seq=8),
        decode_window=2, overlap=True, enable_prefix_cache=True,
    )
    shared = list(range(1, 19))  # 18 tokens -> 2 full shared blocks
    for i in range(3):
        eng.generate_batch([shared + [40 + i]], max_new_tokens=6)

    snap = eng.report_state()
    pc = snap["prefix_cache"]
    assert pc["enabled"] and pc["resident_blocks"] >= 2
    assert pc["hit_tokens"] == 32 and pc["lookup_tokens"] == 57
    assert pc["hit_rate"] == pytest.approx(32 / 57)
    ov = snap["overlap"]
    assert ov["enabled"] and ov["spec_windows"] >= 1
    assert 0 < ov["occupancy"] <= 1
    assert ov["h2d_skips"] > 0  # dirty tracking skipped stable arrays

    dep = eng.metrics_tags["deployment"]
    assert _wait_until(lambda: dep in state_api.summarize_serve())
    summary = state_api.summarize_serve()[dep]
    assert summary["prefix_hit_tokens"] == 32
    assert summary["prefix_hit_rate"] == pytest.approx(32 / 57)
    assert summary["prefix_cached_blocks"] >= 2
    assert summary["overlap_windows"] >= 1
    assert 0 < summary["overlap_occupancy"] <= 1
    # what GET /api/serve/engine serves: every prefill step queued its window
    # behind the prefill, and none had to read the first token first
    pushed = next(v for v in state_api.serve_state().values()
                  if v["tags"]["deployment"] == dep)
    assert pushed["overlap"]["windows_behind_prefill"] == 3
    assert pushed["overlap"]["prefill_flushed_first"] == 0
    assert pushed["stats"]["windows_behind_prefill"] == 3
