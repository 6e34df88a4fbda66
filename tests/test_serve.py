"""Serve: deployments, handles, composition, autoscaling, HTTP proxy.

Reference test models: python/ray/serve/tests/test_deploy.py,
test_handle.py, test_autoscaling_policy.py, test_proxy.py.
"""
import json
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_cluster(ray_start_regular):
    yield ray_start_regular
    serve.shutdown()


def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(), headers={"Content-Type": "application/json"}
    )
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read())


def test_basic_deployment(serve_cluster):
    @serve.deployment
    class Echo:
        def __call__(self, x):
            return {"echo": x}

    h = serve.run(Echo.bind())
    assert h.remote("hi").result(timeout=30) == {"echo": "hi"}


def test_function_deployment(serve_cluster):
    @serve.deployment
    def double(x):
        return 2 * x

    h = serve.run(double.bind())
    assert h.remote(21).result(timeout=30) == 42


def test_method_calls_and_state(serve_cluster):
    @serve.deployment(num_replicas=1)
    class Counter:
        def __init__(self, start):
            self.v = start

        def incr(self, by):
            self.v += by
            return self.v

    h = serve.run(Counter.bind(10))
    assert h.incr.remote(5).result(timeout=30) == 15
    assert h.incr.remote(1).result(timeout=30) == 16


def test_multiple_replicas_spread_requests(serve_cluster):
    @serve.deployment(num_replicas=2)
    class WhoAmI:
        def __init__(self):
            import os

            self.pid = os.getpid()

        def __call__(self, _x):
            return self.pid

    h = serve.run(WhoAmI.bind())
    pids = {h.remote(i).result(timeout=30) for i in range(20)}
    assert len(pids) == 2


def test_composition(serve_cluster):
    @serve.deployment
    class Adder:
        def __call__(self, x):
            return x + 1

    @serve.deployment
    class Gateway:
        def __init__(self, adder):
            self.adder = adder

        def __call__(self, x):
            # Chained handle call: response passed through (worker-side).
            return self.adder.remote(x).result(timeout=30) * 10

    h = serve.run(Gateway.bind(Adder.bind()))
    assert h.remote(4).result(timeout=30) == 50


def test_status_and_delete(serve_cluster):
    @serve.deployment(num_replicas=2, name="thing")
    def noop():
        return 1

    serve.run(noop.bind())
    st = serve.status()
    assert st["thing"]["running_replicas"] == 2
    serve.delete("thing")
    assert "thing" not in serve.status()


def test_replica_recovery(serve_cluster):
    @serve.deployment(num_replicas=1)
    class Fragile:
        def __call__(self, x):
            return x

        def die(self):
            import os

            os._exit(1)

    h = serve.run(Fragile.bind())
    assert h.remote(1).result(timeout=30) == 1
    try:
        h.die.remote().result(timeout=5)
    except Exception:
        pass
    # Reconciler replaces the dead replica.
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        try:
            if h.remote(2).result(timeout=5) == 2:
                break
        except Exception:
            time.sleep(0.3)
    else:
        pytest.fail("replica never recovered")


def test_autoscaling_scales_up(serve_cluster):
    @serve.deployment(min_replicas=1, max_replicas=3, target_ongoing_requests=1.0)
    class Slow:
        def __call__(self, x):
            time.sleep(0.4)
            return x

    h = serve.run(Slow.bind())
    assert serve.status()["Slow"]["running_replicas"] == 1
    # Sustained concurrent load → scale toward max.
    resps = []
    deadline = time.monotonic() + 25
    scaled = False
    while time.monotonic() < deadline and not scaled:
        resps.extend(h.remote(i) for i in range(6))
        while len(resps) > 24:
            resps.pop(0).result(timeout=30)
        scaled = serve.status()["Slow"]["running_replicas"] >= 2
        time.sleep(0.2)
    assert scaled, "autoscaler never added replicas"
    for r in resps:
        r.result(timeout=30)


def test_http_proxy(serve_cluster):
    @serve.deployment(route_prefix="/calc")
    class Calc:
        def __call__(self, req):
            return {"sum": req["a"] + req["b"]}

    serve.run(Calc.bind(), http_port=0)
    port = serve.api.get_proxy_port()
    assert port
    base = f"http://127.0.0.1:{port}"
    with urllib.request.urlopen(base + "/-/healthz", timeout=10) as r:
        assert json.loads(r.read()) == "ok"
    with urllib.request.urlopen(base + "/-/routes", timeout=10) as r:
        assert json.loads(r.read()) == {"/calc": "Calc"}
    assert _post(base + "/calc", {"a": 2, "b": 3}) == {"sum": 5}


def test_llm_generation_deployment(serve_cluster):
    """End-to-end LLM serving: a deployment holding transformer params +
    the jitted KV-cache generate loop (the reference delegates this to
    vLLM-on-Ray; here the decode path is native — models/generate.py)."""

    @serve.deployment(num_replicas=1, num_cpus=1)
    class TinyLLM:
        def __init__(self):
            import jax
            import jax.numpy as jnp

            from ray_tpu.models import generate as gen
            from ray_tpu.models import transformer as tf

            self.cfg = tf.TransformerConfig.tiny(dtype=jnp.float32, remat=False)
            self.params = tf.init_params(jax.random.PRNGKey(0), self.cfg)
            self._gen = jax.jit(
                lambda p, t: gen.generate(p, self.cfg, t, max_new_tokens=8)
            )

        def __call__(self, prompt_tokens):
            import jax.numpy as jnp
            import numpy as np

            toks = jnp.asarray(np.asarray(prompt_tokens, dtype=np.int32)[None, :])
            out = self._gen(self.params, toks)
            return np.asarray(out)[0].tolist()

    handle = serve.run(TinyLLM.bind(), name="llm")
    out = handle.remote([1, 2, 3, 4]).result(timeout=120)
    assert len(out) == 8
    assert all(0 <= t < 256 for t in out)
    # Deterministic greedy decode: same prompt → same continuation.
    out2 = handle.remote([1, 2, 3, 4]).result(timeout=60)
    assert out == out2


def test_streaming_deployment_handle(serve_cluster):
    """Generator deployment streams items through handle.stream()
    (reference: serve streaming responses / DeploymentResponseGenerator)."""
    from ray_tpu import serve

    @serve.deployment(name="tok")
    class Tokens:
        def __call__(self, prompt):
            for i, word in enumerate(f"{prompt} a b c".split()):
                yield {"token": word, "index": i}

    handle = serve.run(Tokens.bind())
    try:
        items = list(handle.stream("hello"))
        assert [it["token"] for it in items] == ["hello", "a", "b", "c"]
        assert [it["index"] for it in items] == [0, 1, 2, 3]
    finally:
        serve.delete("tok")


@pytest.fixture
def bursts(serve_cluster):
    """A deployment that streams bursts, and says what its replica's process
    has shipped (``core/worker_main._StreamShipper``) and how many of its
    generators were closed."""
    from ray_tpu import serve

    @serve.deployment(name="bursts", max_ongoing_requests=8)
    class Bursts:
        def __init__(self):
            self.closed = self.yielded = 0

        def __call__(self, spec):
            try:
                for i in range(spec["n"]):
                    if i == spec.get("fail_at"):
                        raise ValueError(f"broke at {i}")
                    if i >= spec.get("slow_from", spec["n"]):
                        time.sleep(spec["gap_s"])
                    self.yielded += 1
                    yield {"i": i}
            finally:
                self.closed += 1

        def facts(self, _=None):
            from ray_tpu.core.api import _require_worker

            shipper = _require_worker().stream_shipper
            return {"closed": self.closed, "yielded": self.yielded,
                    "items": shipper.items if shipper else 0,
                    "shipments": shipper.shipments if shipper else 0}

    handle = serve.run(Bursts.bind())
    try:
        yield handle
    finally:
        serve.delete("bursts")


@pytest.mark.parametrize("n", [40, 400])
def test_a_burst_reaches_the_handle_in_order_in_far_fewer_shipments(bursts, n):
    """What the generator has yielded while a shipment was on its way leaves as
    ONE shipment: N items arrive in order, every one once, in far fewer than N."""
    before = bursts.facts.remote().result(timeout=60)
    assert list(bursts.stream({"n": n})) == [{"i": i} for i in range(n)]
    after = bursts.facts.remote().result(timeout=60)
    assert after["items"] - before["items"] == n
    assert 1 <= after["shipments"] - before["shipments"] <= n // 4, (before, after)
    assert after["closed"] - before["closed"] == 1


def test_a_lone_item_reaches_the_handle_without_waiting(bursts):
    """An idle stream ships one item at once: time to first token does not
    wait for company (the second item comes three seconds later)."""
    bursts.facts.remote().result(timeout=60)
    stream = bursts.stream({"n": 2, "slow_from": 1, "gap_s": 3.0})
    t0 = time.monotonic()
    assert next(stream) == {"i": 0}
    assert time.monotonic() - t0 < 2.0
    assert stream.in_hand() == []  # nothing else had been yielded
    assert list(stream) == [{"i": 1}]


def test_an_error_mid_burst_reaches_the_consumer_after_exactly_the_items_before_it(bursts):
    stream = bursts.stream({"n": 30, "fail_at": 7})
    got = []
    with pytest.raises(Exception, match="broke at 7"):
        for item in stream:
            got.append(item)
    assert got == [{"i": i} for i in range(7)]
    with pytest.raises(StopIteration):
        next(stream)
    assert all(v == 0 for v in bursts._router._inflight.values())


def test_closing_a_stream_mid_burst_cancels_the_producer_and_releases_the_router(bursts):
    """The consumer reads three items of a burst of five hundred (the rest come
    one every 20 ms) and closes: the replica's generator is closed long before
    its end, and the router's in-flight count is back to zero."""
    before = bursts.facts.remote().result(timeout=60)
    stream = bursts.stream({"n": 500, "slow_from": 20, "gap_s": 0.02})
    assert [next(stream) for _ in range(3)] == [{"i": 0}, {"i": 1}, {"i": 2}]
    assert sum(bursts._router._inflight.values()) == 1
    stream.close()
    assert all(v == 0 for v in bursts._router._inflight.values())
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        after = bursts.facts.remote().result(timeout=60)
        if after["closed"] - before["closed"] == 1:
            break
        time.sleep(0.1)
    assert after["closed"] - before["closed"] == 1, "the producer ran on"
    assert after["yielded"] - before["yielded"] < 400


def test_stream_of_non_generator_is_single_item(serve_cluster):
    """Plain methods through stream(): one item, even for list returns
    (containers are a single response, not element-wise streams)."""
    from ray_tpu import serve

    @serve.deployment(name="plain")
    class Plain:
        def as_dict(self, x):
            return {"v": x}

        def as_list(self, x):
            return [x, x + 1, x + 2]

    serve.run(Plain.bind())
    try:
        h = serve.get_deployment_handle("plain")
        assert list(h.as_dict.stream(1)) == [{"v": 1}]
        assert list(h.as_list.stream(5)) == [[5, 6, 7]]
    finally:
        serve.delete("plain")


def test_streaming_http_ndjson(serve_cluster):
    """The proxy streams NDJSON chunks for Accept: application/x-ndjson
    (reference: proxy streaming — LLM token streaming over HTTP)."""
    import json as _json
    import urllib.request

    from ray_tpu import serve

    @serve.deployment(name="gen")
    class Gen:
        def __call__(self, prompt):
            for tok in ("x", "y", "z"):
                yield {"tok": tok}

    serve.run(Gen.bind(), http_port=0)
    try:
        port = serve.api.get_proxy_port()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/gen",
            data=_json.dumps("p").encode(),
            headers={"Accept": "application/x-ndjson", "Content-Type": "application/json"},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert "x-ndjson" in resp.headers.get("Content-Type", "")
            lines = [l for l in resp.read().decode().strip().splitlines() if l]
        assert [_json.loads(l)["tok"] for l in lines] == ["x", "y", "z"]
        # a plain (non-streaming) call on a generator handler cannot be
        # serialized → clean 500, matching the reference's "streaming
        # deployments need stream=True" contract
        import urllib.error

        req2 = urllib.request.Request(
            f"http://127.0.0.1:{port}/gen",
            data=_json.dumps("p").encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(req2, timeout=30)
    finally:
        serve.delete("gen")


def test_streaming_http_sse(serve_cluster):
    """Accept: text/event-stream gets SSE framing (data: <json>\\n\\n) —
    the EventSource/LLM-client contract (reference: serve SSE responses)."""
    import json as _json
    import urllib.request

    from ray_tpu import serve

    @serve.deployment(name="ssegen")
    class Gen:
        def __call__(self, prompt):
            for tok in ("a", "b"):
                yield {"tok": tok}

    serve.run(Gen.bind(), http_port=0)
    try:
        port = serve.api.get_proxy_port()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/ssegen",
            data=_json.dumps("p").encode(),
            headers={"Accept": "text/event-stream", "Content-Type": "application/json"},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert "text/event-stream" in resp.headers.get("Content-Type", "")
            body = resp.read().decode()
        events = [e for e in body.split("\n\n") if e.strip()]
        toks = []
        for e in events:
            for line in e.splitlines():
                if line.startswith("data: "):
                    toks.append(_json.loads(line[len("data: "):])["tok"])
        assert toks == ["a", "b"], body
    finally:
        serve.delete("ssegen")


def test_per_node_proxies_and_local_routing():
    """proxy_location=EveryNode: a proxy runs on each node; the handle
    router prefers co-located replicas (reference: per-node ProxyActor +
    prefer-local replica scheduling)."""
    import json as _json
    import urllib.request

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core.cluster_utils import Cluster

    cluster = Cluster({"CPU": 2})
    cluster.add_node(num_cpus=2, resources={"n2": 10})
    cluster.connect()
    try:

        @serve.deployment(name="where", num_replicas=2)
        class Where:
            def __call__(self, _=None):
                from ray_tpu.runtime_context import get_runtime_context

                return get_runtime_context().get_node_id()

        serve.run(Where.bind(), http_port=0, proxy_location="EveryNode")
        ports = serve.api.get_proxy_ports()
        assert "head" in ports and len(ports) == 2, ports
        # every proxy serves the route
        for port in ports.values():
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/where",
                data=_json.dumps(None).encode(),
                headers={"Content-Type": "application/json"},
                method="POST",
            )
            with urllib.request.urlopen(req, timeout=30) as r:
                node = _json.loads(r.read())
            assert isinstance(node, str) and len(node) == 32
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()


def test_grpc_ingress(serve_cluster):
    """Generic gRPC ingress: unary Call + server-streaming Stream
    (reference: serve's gRPC proxy, proxy.py:545)."""
    from ray_tpu import serve
    from ray_tpu.serve.grpc_proxy import grpc_call, grpc_stream

    @serve.deployment(name="gsum")
    class Summer:
        def __call__(self, xs):
            return {"sum": sum(xs)}

        def toks(self, n):
            for i in range(n):
                yield {"tok": i}

    serve.run(Summer.bind(), grpc_port=0)
    try:
        port = serve.api.get_grpc_port()
        assert port
        target = f"127.0.0.1:{port}"
        assert grpc_call(target, "/gsum", [1, 2, 3]) == {"sum": 6}
        # unknown route → NOT_FOUND
        import grpc as _grpc

        with pytest.raises(_grpc.RpcError) as ei:
            grpc_call(target, "/nope", 1)
        assert ei.value.code() == _grpc.StatusCode.NOT_FOUND
    finally:
        serve.delete("gsum")


def test_grpc_ingress_streaming(serve_cluster):
    from ray_tpu import serve
    from ray_tpu.serve.grpc_proxy import grpc_stream

    @serve.deployment(name="gstream")
    class Gen:
        def __call__(self, n):
            for i in range(n):
                yield {"tok": i}

    serve.run(Gen.bind(), grpc_port=0)
    try:
        port = serve.api.get_grpc_port()
        items = list(grpc_stream(f"127.0.0.1:{port}", "/gstream", 3))
        assert items == [{"tok": 0}, {"tok": 1}, {"tok": 2}]
    finally:
        serve.delete("gstream")


def test_grpc_user_service_method_dispatch(serve_cluster):
    """User-defined gRPC service with METHOD dispatch (reference:
    proxy.py:545 serving user proto servicers): /test.Echo/Reverse and a
    server-streaming /test.Echo/Chunks hit the deployment's matching
    methods with raw request bytes — the replica does the (de)coding, so
    any wire format (protobuf included) flows through without ingress
    codegen."""
    import grpc as _grpc

    from ray_tpu import serve

    @serve.deployment(name="echo_svc")
    class EchoService:
        # "proto" here is plain bytes — stands in for any generated
        # message's SerializeToString()/FromString round trip
        def Reverse(self, req: bytes) -> bytes:
            return bytes(reversed(req))

        def Chunks(self, req: bytes):
            for b in req:
                yield bytes([b])

    serve.run(EchoService.bind(), grpc_port=0)
    serve.register_grpc_service(
        "test.Echo", "echo_svc", methods=["Reverse"], stream_methods=["Chunks"]
    )
    try:
        port = serve.api.get_grpc_port()
        with _grpc.insecure_channel(f"127.0.0.1:{port}") as channel:
            rev = channel.unary_unary(
                "/test.Echo/Reverse",
                request_serializer=bytes, response_deserializer=bytes,
            )
            assert rev(b"abcdef", timeout=60) == b"fedcba"
            chunks = channel.unary_stream(
                "/test.Echo/Chunks",
                request_serializer=bytes, response_deserializer=bytes,
            )
            assert list(chunks(b"xyz", timeout=60)) == [b"x", b"y", b"z"]
            # unregistered service → UNIMPLEMENTED (grpc's unknown-method)
            other = channel.unary_unary(
                "/test.Other/Nope",
                request_serializer=bytes, response_deserializer=bytes,
            )
            with pytest.raises(_grpc.RpcError) as ei:
                other(b"", timeout=30)
            assert ei.value.code() == _grpc.StatusCode.UNIMPLEMENTED
            # method NOT in the allowlist → UNIMPLEMENTED too (public
            # replica helpers stay unreachable from the ingress)
            hidden = channel.unary_unary(
                "/test.Echo/Chunks2",
                request_serializer=bytes, response_deserializer=bytes,
            )
            with pytest.raises(_grpc.RpcError) as ei:
                hidden(b"", timeout=30)
            assert ei.value.code() == _grpc.StatusCode.UNIMPLEMENTED
    finally:
        serve.unregister_grpc_service("test.Echo")
        serve.delete("echo_svc")


def test_multiplexed_models_lru_and_sticky_routing(serve_cluster):
    """3 model ids through 2 replicas: each replica holds <= 2 resident
    models (LRU eviction at max_num_models_per_replica), and repeat
    requests for a model route sticky to a replica that has it loaded
    (reference: serve.multiplexed + model-affine routing)."""

    @serve.deployment(num_replicas=2, max_ongoing_requests=4)
    class MuxModel:
        @serve.multiplexed(max_num_models_per_replica=2)
        def get_model(self, model_id: str):
            # a "model" is a callable tagging outputs with its id
            return lambda x, _mid=model_id: f"{_mid}:{x}"

        def __call__(self, x):
            import os

            mid = serve.get_multiplexed_model_id()
            model = self.get_model(mid)
            return {"out": model(x), "pid": os.getpid(), "resident": len(self._serve_mux_get_model.loaded_ids())}

    handle = serve.run(MuxModel.bind())
    # drive 3 model ids; each must produce its own model's output
    for mid in ("m1", "m2", "m3"):
        r = handle.options(multiplexed_model_id=mid).remote(7).result(timeout=60)
        assert r["out"] == f"{mid}:7", r
    # LRU cap: no replica ever holds more than 2
    for mid in ("m1", "m2", "m3", "m1", "m2", "m3"):
        r = handle.options(multiplexed_model_id=mid).remote(1).result(timeout=60)
        assert r["resident"] <= 2, r
    # sticky: a FRESH model id loads on exactly one replica; once the
    # routing table refreshes, every later request lands on that replica
    # (model-affine routing — never a second copy on the other replica)
    r0 = handle.options(multiplexed_model_id="m-sticky").remote(0).result(timeout=60)
    time.sleep(1.5)  # let report_models + router refresh settle
    pids = set()
    for _ in range(5):
        r = handle.options(multiplexed_model_id="m-sticky").remote(0).result(timeout=60)
        pids.add(r["pid"])
    assert pids == {r0["pid"]}, f"m-sticky bounced: {pids} vs loader {r0['pid']}"
    serve.delete("MuxModel")


def test_multiplexed_http_header_routing(serve_cluster):
    """The serve_multiplexed_model_id HTTP header reaches the replica."""

    @serve.deployment(num_replicas=1)
    class H:
        @serve.multiplexed(max_num_models_per_replica=2)
        def get_model(self, model_id: str):
            return model_id.upper()

        def __call__(self, payload):
            return {"model": self.get_model(serve.get_multiplexed_model_id())}

    serve.run(H.bind(), http_port=0)
    port = serve.api.get_proxy_port()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/H",
        data=json.dumps({"x": 1}).encode(),
        headers={"serve_multiplexed_model_id": "fancy"},
    )
    body = json.loads(urllib.request.urlopen(req, timeout=30).read())
    assert body == {"model": "FANCY"}, body
    serve.delete("H")


def test_run_config_declarative_deploy(serve_cluster, tmp_path):
    """YAML config deploy: import_path + per-deployment overrides
    (reference: the serve config-file deploy path)."""
    import textwrap

    mod = tmp_path / "serve_cfg_app.py"
    mod.write_text(textwrap.dedent("""
        from ray_tpu import serve

        @serve.deployment(num_replicas=1)
        class CfgModel:
            def __call__(self, x):
                return {"doubled": x * 2}

        app = CfgModel.bind()
    """))
    import sys

    sys.path.insert(0, str(tmp_path))
    try:
        cfg = f"""
applications:
  - name: cfgapp
    import_path: serve_cfg_app:app
    route_prefix: /cfg
    deployments:
      - name: CfgModel
        num_replicas: 2
"""
        handles = serve.run_config(cfg)
        assert "cfgapp" in handles
        out = handles["cfgapp"].remote(21).result(timeout=60)
        assert out == {"doubled": 42}, out
        st = serve.status()
        assert st["CfgModel"]["target_replicas"] == 2, st
        assert st["CfgModel"]["config"]["route_prefix"] == "/cfg", st
        serve.delete("CfgModel")
    finally:
        sys.path.remove(str(tmp_path))


def test_llm_deployment_two_clients_share_one_decode_batch(serve_cluster):
    """Native LLM serving (the reference delegates this to vLLM-on-Ray,
    SURVEY §2.9): two concurrent HTTP clients stream tokens from ONE
    continuously-batched engine — both requests occupy decode slots of
    the same jitted step (engine max_active >= 2)."""
    import threading

    @serve.deployment(name="llm", max_ongoing_requests=8)
    class LLM:
        def __init__(self):
            import jax
            import jax.numpy as jnp

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

        def stats(self):
            return dict(self.engine.stats)

    serve.run(LLM.bind(), http_port=0)
    try:
        port = serve.api.get_proxy_port()
        results = {}

        def client(name, prompt):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/llm",
                data=json.dumps(prompt).encode(),
                headers={"Accept": "application/x-ndjson",
                         "Content-Type": "application/json"},
                method="POST",
            )
            with urllib.request.urlopen(req, timeout=240) as resp:
                results[name] = [
                    json.loads(l)["tok"]
                    for l in resp.read().decode().splitlines() if l
                ]

        t1 = threading.Thread(target=client, args=("a", [2, 4, 6]))
        t2 = threading.Thread(target=client, args=("b", [1, 3, 5, 7]))
        t1.start(); t2.start()
        t1.join(300); t2.join(300)
        assert len(results["a"]) == 24, results
        assert len(results["b"]) == 24, results
        h = serve.get_deployment_handle("llm")
        stats = h.stats.remote().result(timeout=30)
        assert stats["max_active"] >= 2, stats  # shared one decode batch
    finally:
        serve.delete("llm")
