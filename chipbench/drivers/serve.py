"""A serving cell: ``serve.run`` -> HTTP proxy -> replica -> ``LLMEngine``,
with streaming NDJSON clients in the benchmark's own process.

The benchmark's process never touches JAX. The replica holds the chip: it
builds the engine on weights the benchmark makes from the seed, reads the
device's facts, takes and reduces the trace, and runs the reference, and
hands all of it back through its handle.
"""
from __future__ import annotations

import http.client
import json
import threading
import time

import numpy as np

from ray_tpu import serve

DEPLOYMENT = "llm"
MAX_ONGOING = 256


# ---------------------------------------------------------------------------
# In the replica: the process that holds the chip
# ---------------------------------------------------------------------------
@serve.deployment(name=DEPLOYMENT, max_ongoing_requests=MAX_ONGOING)
class LLM:
    def __init__(self, spec: dict):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import transformer as tf
        from ray_tpu.models.paged import PagedConfig
        from ray_tpu.serve.llm_engine import FlightRecorder, LLMEngine

        from chipbench import onchip
        from chipbench import weights as W

        t0 = time.time()
        self.compiles_at_start = onchip.compile_count()
        onchip.require_device(spec["rehearse"])
        conf = spec["config"]
        self.dims = dims = W.Dims.from_config(conf)
        if dims.head_dim * dims.heads != dims.hidden:
            raise ValueError("the program derives head_dim as hidden_size / heads")
        eng, paged = conf["engine"], conf["paged"]
        self.dtype = getattr(jnp, conf["dtype"])
        self.pcfg = PagedConfig(**paged)
        cfg = tf.TransformerConfig(
            vocab_size=dims.vocab, d_model=dims.hidden, n_layers=dims.layers,
            n_heads=dims.heads, n_kv_heads=dims.kv_heads, d_ff=dims.ffn,
            rope_theta=dims.rope_theta, max_seq_len=self.pcfg.max_seq_len,
            dtype=self.dtype, remat=False,
        )
        key = W.seed_key(spec["seed"])
        self.engine = LLMEngine(
            lambda: W.make_params(key, dims, self.dtype), cfg, self.pcfg,
            decode_window=eng["decode_window"], overlap=eng["overlap"],
            enable_prefix_cache=eng["enable_prefix_cache"],
            prefill_chunk=eng.get("prefill_chunk"), warmup_buckets=eng["warmup_buckets"],
        )
        # The rings are sized for a smoke (256); a window holds more.
        self.engine.recorder = FlightRecorder(step_capacity=200_000, request_capacity=50_000)
        self.window = eng["decode_window"]
        self.rids = {}
        self.base = None
        self.build_s = time.time() - t0
        self.engine.start()

    def __call__(self, request):
        req = self.engine.add_request(
            [int(t) for t in request["prompt"]], max_new_tokens=int(request["max_new_tokens"]))
        self.rids[req.rid] = request.get("cid")
        for tok in req.tokens(timeout=300):
            yield {"tok": int(tok)}

    def ready(self) -> dict:
        from chipbench import onchip

        return {"build_s": self.build_s, "stats": dict(self.engine.stats),
                "compiles": onchip.compile_count() - self.compiles_at_start,
                "decode_program": type(self.engine._decode).__name__, **onchip.device_facts()}

    def begin_window(self) -> float:
        from chipbench import onchip

        self.engine.recorder.steps.clear()
        self.engine.recorder.requests.clear()
        self.base = {"stats": dict(self.engine.stats), "compiles": onchip.compile_count(),
                     "compiled": onchip.compiled_functions()}
        return time.time()

    def trace_window(self, seconds: float, keep_to: str = "") -> dict:
        from chipbench import onchip

        trace = onchip.DeviceTrace()
        trace.start()
        time.sleep(seconds)
        return trace.stop(keep_to)

    def end_window(self) -> dict:
        from chipbench import onchip

        now = time.time()
        stats = dict(self.engine.stats)
        requests = [dict(r, cid=self.rids.get(r["rid"])) for r in list(self.engine.recorder.requests)]
        return {
            "t": now,
            "stats": {k: stats[k] - self.base["stats"].get(k, 0) for k in stats
                      if isinstance(stats[k], int) and not k.startswith("warmup")},
            "max_active": stats["max_active"],
            "compiles_in_window": onchip.compile_count() - self.base["compiles"],
            "compiled_in_window": {k: v - self.base["compiled"].get(k, 0)
                                   for k, v in onchip.compiled_functions().items()
                                   if v > self.base["compiled"].get(k, 0)},
            "requests": requests,
            "steps": list(self.engine.recorder.steps),
            "max_batch": self.pcfg.max_batch, "decode_window": self.window,
            "block_size": self.pcfg.block_size, "usable_blocks": self.pcfg.usable_blocks,
        }

    def device(self) -> dict:
        from chipbench import onchip

        return onchip.device_facts()

    def reseed(self, seed: int) -> None:
        """New weights in the place and layout of the old: the programs take
        them as arguments. Only ``--check-seeds`` calls this, on an idle engine."""
        import jax

        from chipbench import weights as W

        formats = jax.tree.map(lambda x: x.format, self.engine.params)
        jax.tree.map(lambda x: x.delete(), self.engine.params)
        dims, dtype = self.dims, self.dtype
        self.engine.params = jax.jit(
            lambda k: W.make_params(k, dims, dtype), out_shardings=formats)(W.seed_key(seed))

    def check(self, seed: int, sample: list, control: str = "") -> dict:
        """Each sampled request's served tokens, fed to the reference as a
        forced continuation. At every generated position: the reference's
        largest logit less its logit of the served token, over the spread of
        its logits there. With ``control``, the tokens the reference itself
        would have served in that lower precision are judged the same way."""
        import jax.numpy as jnp

        from chipbench import reference as R
        from chipbench import weights as W

        key = W.seed_key(seed)
        out = {"program": [], "control": []}
        # One shape for the whole sample, so the reference compiles once.
        n_max = max(len(s["served"]) for s in sample)
        t_max = max(len(s["prompt"]) + len(s["served"]) - 1 for s in sample)
        t_pad = -(-t_max // 256) * 256
        for at in range(0, len(sample), 4):  # a few sequences at a time: memory
            part = sample[at:at + 4]
            tokens = np.zeros((4, t_pad), np.int32)  # a short last group leaves rows unused
            where = np.zeros((4, n_max), np.int32)
            served = np.zeros((4, n_max), np.int32)
            mask = np.zeros((4, n_max), bool)
            for i, s in enumerate(part):
                seq, n, p = s["prompt"] + s["served"][:-1], len(s["served"]), len(s["prompt"])
                tokens[i, :len(seq)] = seq
                where[i, :n] = p - 1 + np.arange(n)
                served[i, :n] = s["served"]
                mask[i, :n] = True
            args = (key, jnp.asarray(tokens), self.dims, self.dtype)
            ref = R.stream_logits(*args, positions=jnp.asarray(where))
            out["program"].append(_deficits(ref, served, mask))
            if control:
                low = R.stream_logits(*args, quantize=control, positions=jnp.asarray(where))
                out["control"].append(_deficits(ref, np.asarray(jnp.argmax(low, axis=-1)), mask))
        return {k: _summary(np.concatenate(v)) for k, v in out.items() if v}


def _deficits(ref_logits, tokens, mask) -> np.ndarray:
    import jax.numpy as jnp

    chosen = jnp.take_along_axis(ref_logits, jnp.asarray(tokens)[..., None], axis=-1)[..., 0]
    deficit = (ref_logits.max(axis=-1) - chosen) / ref_logits.std(axis=-1)
    return np.asarray(deficit)[mask]


def _summary(deficit: np.ndarray) -> dict:
    return {"mean_deficit": float(deficit.mean()), "max_deficit": float(deficit.max()),
            "flip_share": float((deficit > 0).mean()), "positions": int(deficit.size)}


# ---------------------------------------------------------------------------
# In the benchmark's process: the clients
# ---------------------------------------------------------------------------
class Client:
    """One streamed request through the proxy, timed on this side's clock."""

    def __init__(self, port: int, cid, prompt: list, max_new_tokens: int, due: float):
        self.port, self.cid, self.prompt, self.asked, self.due = port, cid, prompt, max_new_tokens, due
        self.sent = None
        self.times: list = []
        self.tokens: list = []
        self.error = None

    def run(self):
        body = json.dumps({"cid": self.cid, "prompt": self.prompt,
                           "max_new_tokens": self.asked}).encode()
        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=300)
        try:
            self.sent = time.time()
            conn.request("POST", f"/{DEPLOYMENT}", body=body, headers={
                "Accept": "application/x-ndjson", "Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                raise RuntimeError(f"HTTP {resp.status}: {resp.read()[:200]!r}")
            while True:
                line = resp.readline()
                if not line:
                    break
                frame = json.loads(line)
                if "error" in frame:
                    raise RuntimeError(frame["error"])
                self.tokens.append(int(frame["tok"]))
                self.times.append(time.time())
        except (OSError, RuntimeError, ValueError, http.client.HTTPException) as e:
            self.error = f"{type(e).__name__}: {e}"
        finally:
            conn.close()

    @property
    def ok(self) -> bool:
        return self.error is None and len(self.tokens) == self.asked


class Load:
    """The cell's traffic, from one process: a thread for each request in
    flight, blocked on its socket nearly all of its life."""

    def __init__(self, port: int, plan: dict):
        self.port, self.plan = port, plan
        self.clients: list = []
        self.lock = threading.Lock()
        self.stop_at = None
        self.threads: list = []
        self.began = None

    def start(self):
        self.began = time.time()
        if self.plan["kind"] == "open":
            self.threads = [threading.Thread(target=self._schedule, daemon=True)]
        else:
            self.next_session = iter(range(10**9))
            self.threads = [threading.Thread(target=self._converse, daemon=True)
                            for _ in range(self.plan["clients"])]
        for t in self.threads:
            t.start()

    def _sending(self) -> bool:
        return self.stop_at is None or time.time() < self.stop_at

    def _add(self, client: Client):
        with self.lock:
            self.clients.append(client)

    def _schedule(self):
        workers = []
        for r in self.plan["requests"]:
            due = self.began + r["due_s"]
            while self._sending() and time.time() < due:
                time.sleep(min(0.005, max(0.0, due - time.time())))
            if not self._sending():
                break
            c = Client(self.port, r["cid"], r["prompt"], r["max_new_tokens"], due)
            self._add(c)
            t = threading.Thread(target=c.run, daemon=True)
            t.start()
            workers.append(t)
        for t in workers:
            t.join()

    def _converse(self):
        while self._sending():
            with self.lock:
                j = next(self.next_session)
            s = self.plan["session"](j)
            history = list(self.plan["systems"][s["system"]])
            for k, turn in enumerate(s["turns"]):
                if not self._sending():
                    return
                history = history + turn["user"]
                c = Client(self.port, f"{j}.{k}", history, turn["max_new_tokens"], time.time())
                self._add(c)
                c.run()
                if not c.ok:
                    return
                history = history + c.tokens

    def stop_sending(self, at: float):
        self.stop_at = at

    def drain(self, timeout: float) -> bool:
        end = time.time() + timeout
        for t in self.threads:
            t.join(max(0.0, end - time.time()))
        return not any(t.is_alive() for t in self.threads)


def warm_requests(warm: dict, paged: dict, vocab: int, margin: int) -> list:
    """One short request for each prefill bucket, and each chunk bucket, that
    the cell's traffic can reach: ``[(prompt, max_new_tokens)]``, to be played
    one after another before any load. The engine's own warm-up runs every
    program once on a fresh cache, but the first live use of a bucket after
    the decode program has run compiles it again (PERF.md, PR 24): so the
    benchmark walks the live path through each shape itself. A chunk program
    runs when a prompt's first blocks are cached: its requests share two."""
    bs, longest = paged["block_size"], paged["block_size"] * paged["max_blocks_per_seq"]
    sizes, b = [], bs
    while b < longest:
        sizes.append(b)
        b *= 2
    sizes.append(longest)
    rng = np.random.default_rng(0)
    base = rng.integers(0, vocab, 2 * bs).tolist()
    out = []
    lo, hi = warm.get("prefill_buckets") or (1, 0)
    for s in (s for s in sizes if lo <= s <= hi):
        out.append((rng.integers(0, vocab, min(s, longest - margin)).tolist(), 2))
    lo, hi = warm.get("chunk_buckets") or (1, 0)
    chunks = [s for s in sizes if lo <= s <= hi]
    if chunks:
        out.append((base, 2))
    for s in chunks:
        out.append((base + rng.integers(0, vocab, min(s, longest - margin - len(base))).tolist(), 2))
    return out


def client_facts(clients: list, t0: float, t1: float, gave_up: float) -> dict:
    """What the clients saw: one record per request sent, and the tokens
    that arrived inside the window."""
    records = []
    for c in clients:
        n = len(c.tokens)
        records.append({
            "cid": c.cid, "due": c.due, "sent": c.sent, "ok": c.ok, "asked": c.asked, "received": n,
            "prompt_tokens": len(c.prompt),
            "first": c.times[0] if n else None, "last": c.times[-1] if n else None,
            "tokens_in_window": sum(1 for t in c.times if t0 <= t < t1),
        })
    return {"t0": t0, "t1": t1, "gave_up": gave_up, "requests": records}


def run(cell, args, t_start: float) -> dict:
    import ray_tpu
    from ray_tpu.core.cluster_utils import wait_cluster_processes_gone

    conf, traffic = cell.config, cell.traffic["params"]
    if args.rehearse:
        conf = {**conf, **conf["rehearsal"]}
        traffic = {**traffic, **cell.traffic.get("rehearsal", {})}
    spec = {"config": conf, "seed": args.seed, "rehearse": args.rehearse}
    ray_tpu.init()
    try:
        serve.run(LLM.options(num_tpus=0 if args.rehearse else cell.chips).bind(spec), http_port=0)
        port = serve.api.get_proxy_port()
        handle = serve.get_deployment_handle(DEPLOYMENT)
        built = handle.ready.remote().result(timeout=1100)
        print(f"[chipbench] replica ready: build {built['build_s']:.1f}s, "
              f"{built['compiles']} compilations, {built['kind']} x{built['count']}", flush=True)
        if args.check_seeds:
            return _check_seeds(cell, args, handle, port, traffic, conf)
        if args.sweep:
            return _sweep(cell, args, handle, port, traffic, conf)
        _warm(port, conf, cell.traffic)
        plan = cell.generator().plan(traffic, args.seed, args.seconds, conf["vocab_size"])
        load = Load(port, plan)
        load.start()
        time.sleep(max(0.0, load.began + traffic["ramp_s"] - time.time()))
        handle.begin_window.remote().result(timeout=60)
        t0 = time.time()
        t1 = t0 + args.seconds
        load.stop_sending(t1)
        traced = None
        if args.trace:
            tr = cell.traffic["trace"]
            time.sleep(min(tr["after_s"], max(0.0, args.seconds - tr["seconds"] - 1)))
            traced = handle.trace_window.remote(
                min(tr["seconds"], max(0.5, args.seconds - 1)), args.keep_trace).result(timeout=300)
        time.sleep(max(0.0, t1 - time.time()))
        engine = handle.end_window.remote().result(timeout=60)
        device = handle.device.remote().result(timeout=60)
        drained = load.drain(traffic["drain_s"])
        clients = client_facts(list(load.clients), t0, t1, time.time())
        done = [c for c in load.clients if c.ok and c.sent is not None]
        rng = np.random.default_rng(args.seed)
        picks = rng.choice(len(done), size=min(traffic["check_requests"], len(done)), replace=False)
        sample = [{"prompt": done[i].prompt, "served": done[i].tokens} for i in sorted(picks)]
        check = handle.check.remote(args.seed, sample).result(timeout=300)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
        wait_cluster_processes_gone(timeout_s=60)
    finished = [r for r in clients["requests"] if r["sent"] is not None]
    failed = sum(1 for r in finished if not r["ok"])
    late = sorted((r["sent"] - r["due"]) * 1e3 for r in finished)
    if late:  # a starved generator must not be read as a fast server
        print(f"[chipbench] load: {len(late)} requests sent, late by {late[len(late) // 2]:.2f} ms "
              f"(median), {late[-1]:.2f} ms (most)", flush=True)
    limit = cell.limit("mean_deficit_limit")
    number = check["program"]["mean_deficit"]
    print(f"[chipbench] correct: mean deficit of served tokens under the reference "
          f"{number:.6g} (limit {limit}) over {check['program']['positions']} positions of "
          f"{len(sample)} requests; flipped {check['program']['flip_share']:.4f}, "
          f"largest {check['program']['max_deficit']:.4g}", flush=True)
    print(f"[chipbench] correct: requests failed {failed} (limit 0) of {len(finished)}; "
          f"drained {drained}; compilations inside the window "
          f"{engine['compiles_in_window']} (limit 0) {engine['compiled_in_window'] or ''}", flush=True)
    correct = (number <= limit and failed == 0 and drained
               and engine["compiles_in_window"] == 0 and len(sample) > 0)
    return {
        "correct": bool(correct), "attempted": len(finished), "failed": failed, "device": device,
        "facts": {"setup_s": t0 - t_start, "seconds": args.seconds, "client": clients,
                  "engine": engine, "trace": traced, "built": built, "check": check,
                  "dims": conf, "chips": cell.chips},
    }


def _warm(port: int, conf: dict, traffic_file: dict) -> None:
    eng = conf["engine"]
    margin = eng["decode_window"] * (2 if eng["overlap"] else 1) + 2
    t0 = time.time()
    todo = warm_requests(traffic_file.get("warm", {}), conf["paged"], conf["vocab_size"], margin)
    for i, (prompt, n) in enumerate(todo):
        c = Client(port, f"warm.{i}", prompt, n, time.time())
        c.run()
        if not c.ok:
            raise RuntimeError(f"warm-up request {i} ({len(prompt)} tokens) failed: {c.error}")
    print(f"[chipbench] warmed {len(todo)} shapes through the served path in "
          f"{time.time() - t0:.1f}s", flush=True)


def _check_seeds(cell, args, handle, port, traffic, conf) -> dict:
    """Many seeds after one set-up: for each, new weights, the first requests
    of that seed's traffic at once, and the reference's verdict on the program
    and on the control."""
    rows = []
    for seed in args.check_seeds:
        handle.reseed.remote(seed).result(timeout=300)
        plan = cell.generator().plan(traffic, seed, 60.0, conf["vocab_size"])
        n = traffic["check_requests"]
        if plan["kind"] == "open":
            todo = [(r["prompt"], r["max_new_tokens"]) for r in plan["requests"][:n]]
        else:
            todo = []
            for j in range(n):
                s = plan["session"](j)
                todo.append((plan["systems"][s["system"]] + s["turns"][0]["user"],
                             s["turns"][0]["max_new_tokens"]))
        clients = [Client(port, i, p, m, time.time()) for i, (p, m) in enumerate(todo)]
        threads = [threading.Thread(target=c.run, daemon=True) for c in clients]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        bad = [c.error or f"{len(c.tokens)} of {c.asked} tokens" for c in clients if not c.ok]
        if bad:
            raise RuntimeError(f"seed {seed}: requests failed: {bad[:3]}")
        sample = [{"prompt": c.prompt, "served": c.tokens} for c in clients]
        got = handle.check.remote(seed, sample, args.control).result(timeout=600)
        rows.append({"seed": seed, **got})
        print(f"[chipbench] check-seeds {json.dumps(rows[-1])}", flush=True)
    return {"check_seeds": rows}


def _sweep(cell, args, handle, port, traffic, conf) -> dict:
    """The knee, found once: the open loop at each rate in turn after one
    set-up, with what the clients saw and whether the queue kept growing."""
    from chipbench.end_to_end import serve_tokens_per_s, tpot_p95_ms
    from chipbench.layer_metrics import ttft_p95_ms_steady as ttft_p95_ms
    from chipbench.stats import percentile

    _warm(port, conf, cell.traffic)
    rows = []
    for k, rate in enumerate(args.sweep):
        plan = cell.generator().plan({**traffic, "rate_per_s": rate}, args.seed + k,
                                     args.seconds, conf["vocab_size"])
        load = Load(port, plan)
        load.start()
        time.sleep(traffic["ramp_s"])
        handle.begin_window.remote().result(timeout=60)
        t0 = time.time()
        load.stop_sending(t0 + args.seconds)
        time.sleep(args.seconds)
        engine = handle.end_window.remote().result(timeout=60)
        drained = load.drain(traffic["drain_s"])
        facts = {"client": client_facts(list(load.clients), t0, t0 + args.seconds, time.time())}
        ttft = ttft_p95_ms.sample(facts)
        waiting = [s["waiting"] for s in engine["steps"]]
        third = max(1, len(waiting) // 3)
        rows.append({
            "rate_per_s": rate, "requests": len(ttft),
            "tokens_per_s": serve_tokens_per_s.read(facts),
            "ttft_p50_ms": percentile(ttft, 50), "ttft_p95_ms": percentile(ttft, 95),
            "tpot_p95_ms": tpot_p95_ms.read(facts),
            "waiting_first_third": sum(waiting[:third]) / third,
            "waiting_last_third": sum(waiting[-third:]) / third,
            "preemptions": engine["stats"]["preemptions"], "drained": drained,
            "compiled_in_window": engine["compiled_in_window"],
        })
        print(f"[chipbench] sweep {json.dumps(rows[-1])}", flush=True)
    return {"sweep": rows}
