"""A serving cell of the multi-stream latent-attention expert decoder: the same path
as ``drivers/serve.py`` (``serve.run`` -> HTTP proxy -> replica -> ``LLMEngine``,
NDJSON clients in the benchmark's own process), with a replica that builds the
engine from ``weights_hyper_latent_moe`` and is judged by
``reference_hyper_latent_moe``.

``drivers/serve_latent_moe.py`` written again around this configuration's modules
(``diff`` of the two files; a fix to the window there belongs here too, and in
``drivers/serve.py``: ``serve.deployment`` returns a ``Deployment``, which cannot be
subclassed, and ``run`` names its module's own replica class): the weights and
reference modules' names; the program's module imported at the top; the warm-up
(``_warm``: every system prompt once before a tail longer than the engine's chunk, so
that the prefix cache holds each system prompt's block and every prompt of the traffic is
a suffix behind a hit: chunk calls, never the whole-prompt program); the positions a checked answer
is padded to, which the traffic fixes (one shape, compiled beside the set-up); and
the check's controls, several at once (``--control int8,plain-residual``): ``int8``
the reference in the nearest precision below, ``plain-residual`` a PLANTED FAULT OF
THE MECHANISM (``Hres`` the identity and ``Hpre = Hpost = 1``: the residual path every
other model has, ``reference_hyper_latent_moe.stream_logits(residual="plain")``).
"""
from __future__ import annotations

import threading
import time

import numpy as np

from ray_tpu import serve
# The program's side of this configuration: a tree without it cannot run the
# cell, and says so here, before any cluster starts.
from ray_tpu.models import hyper_latent_moe  # noqa: F401

from chipbench.drivers.serve import (DEPLOYMENT, MAX_ONGOING, Client, Load, _check_seeds, _deficits,
                                     _summary, _sweep, client_facts)
from chipbench.drivers.serve_latent_moe import CHECK_POSITIONS

FAULTS = {"plain-residual": "plain"}  # controls that are a fault of the mechanism, not a precision


def check_positions(traffic: dict) -> int:
    """Positions a checked answer is judged at, padded to (one shape): the longest answer's."""
    return -(-traffic["answer_tokens"]["max"] // CHECK_POSITIONS) * CHECK_POSITIONS


# ---------------------------------------------------------------------------
# In the replica: the process that holds the chip
# ---------------------------------------------------------------------------
@serve.deployment(name=DEPLOYMENT, max_ongoing_requests=MAX_ONGOING)
class LLM:
    def __init__(self, spec: dict):
        import jax.numpy as jnp

        from ray_tpu.models.paged import PagedConfig
        from ray_tpu.serve.llm_engine import FlightRecorder, LLMEngine

        from chipbench import onchip
        from chipbench import weights_hyper_latent_moe as W

        t0 = time.time()
        self.compiles_at_start = onchip.compile_count()
        onchip.require_device(spec["rehearse"])
        conf = spec["config"]
        self.dims = dims = W.Dims.from_config(conf)
        eng = conf["engine"]
        self.dtype = getattr(jnp, conf["dtype"])
        self.pcfg = PagedConfig(**conf["paged"])
        self.check_positions = spec["check_positions"]
        # The reference's programs compile beside the engine's build and the
        # warm-up (a minute of the host's, nothing of the device's), not after
        # the window; ``begin_window`` waits for them.
        self.reference_ready = threading.Thread(target=self._compile_reference, daemon=True)
        self.reference_ready.start()
        key = W.seed_key(spec["seed"])
        self.engine = LLMEngine(
            lambda: W.make_params(key, dims, self.dtype), W.program_config(dims, self.dtype),
            self.pcfg, decode_window=eng["decode_window"], overlap=eng["overlap"],
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

    def _compile_reference(self) -> None:
        from chipbench import reference_hyper_latent_moe as R

        t0 = time.time()
        R.precompile(self.dims, self.dtype, self.pcfg.max_seq_len, self.check_positions)
        self.reference_s = time.time() - t0

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
                "compiled": onchip.compiled_functions(),
                "decode_program": type(self.engine._decode).__name__, **onchip.device_facts()}

    def begin_window(self) -> float:
        from chipbench import onchip

        self.reference_ready.join()
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
        pc = self.engine.prefix_cache
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
            "resident_blocks": pc.resident_blocks if pc else 0,
            "reference_compile_s": self.reference_s,
        }

    def device(self) -> dict:
        from chipbench import onchip

        return onchip.device_facts()

    def reseed(self, seed: int) -> None:
        """New weights in the place and layout of the old: the programs take
        them as arguments. Only ``--check-seeds`` calls this, on an idle engine."""
        import jax

        from chipbench import weights_hyper_latent_moe as W

        formats = jax.tree.map(lambda x: x.format, self.engine.params)
        jax.tree.map(lambda x: x.delete(), self.engine.params)
        dims, dtype = self.dims, self.dtype
        self.engine.params = jax.jit(
            lambda k: W.make_params(k, dims, dtype), out_shardings=formats)(W.seed_key(seed))

    def check(self, seed: int, sample: list, control: str = "") -> dict:
        """Each sampled request's served tokens, fed to the reference as a
        forced continuation, and judged as ``drivers/serve.py`` judges them: at
        every generated position the reference's largest logit less its logit
        of the served token, over the spread of its logits there. For each of
        ``control`` (comma-separated), the tokens the reference itself would
        have served in that lower precision (``int8``) or with that fault
        planted in its residual path (``FAULTS``) are judged the same way. A
        sequence at a time, all padded to the table's whole length: the
        reference compiles once (causal attention keeps the padding out of the
        judged positions)."""
        import jax.numpy as jnp

        from chipbench import reference_hyper_latent_moe as R
        from chipbench import weights_hyper_latent_moe as W

        key = W.seed_key(seed)
        controls = [c for c in control.split(",") if c]
        out = {"program": [], **{c: [] for c in controls}}
        seconds = []
        n_max, t_pad = self.check_positions, self.pcfg.max_seq_len
        for s in sample:
            seq, n, p = s["prompt"] + s["served"][:-1], len(s["served"]), len(s["prompt"])
            # Padding of DISTINCT ids: one id repeated would send every padded place to
            # the same four experts, the fullest expert's gather would outgrow the
            # capacity ``precompile`` made, and the check would compile (30-45 s).
            tokens = (np.arange(t_pad, dtype=np.int32) % self.dims.vocab)[None].copy()
            where = np.zeros((1, n_max), np.int32)
            served = np.zeros((1, n_max), np.int32)
            mask = np.zeros((1, n_max), bool)
            tokens[0, :len(seq)] = seq
            where[0, :n] = p - 1 + np.arange(n)
            served[0, :n] = s["served"]
            mask[0, :n] = True
            args = (key, jnp.asarray(tokens), self.dims, self.dtype)
            t0 = time.time()
            ref = R.stream_logits(*args, positions=jnp.asarray(where))
            out["program"].append(_deficits(ref, served, mask))
            seconds.append(round(time.time() - t0, 2))
            for c in controls:
                how = {"residual": FAULTS[c]} if c in FAULTS else {"quantize": c}
                low = R.stream_logits(*args, **how, positions=jnp.asarray(where))
                out[c].append(_deficits(ref, np.asarray(jnp.argmax(low, axis=-1)), mask))
        return {**{k: _summary(np.concatenate(v)) for k, v in out.items() if v},
                "reference_s": seconds}


# ---------------------------------------------------------------------------
# In the benchmark's process
# ---------------------------------------------------------------------------
def _warm(port: int, plan: dict, conf: dict) -> None:
    """Every system prompt once, one after another, each with a tail as long as the
    engine's chunk behind it and two tokens asked. Longer than the chunk, such a prompt
    enters the chunked queue: it runs the chunk program at its widest and at a narrower
    width and the decode window on the live path, and leaves its system prompt's block in
    the prefix cache. Every prompt of the traffic then hits that block and is a suffix:
    chunk calls, whose widths the engine compiled at its build. (A prompt with NO hit
    runs the whole-prompt program of its bucket, which nothing has compiled: inside the
    window that would be a compilation.)"""
    t0 = time.time()
    rng = np.random.default_rng(0)
    tail = conf["engine"]["prefill_chunk"]
    for i, system in enumerate(plan["systems"]):
        c = Client(port, f"warm.{i}", list(system) + rng.integers(0, conf["vocab_size"], tail).tolist(),
                   2, time.time())
        c.run()
        if not c.ok:
            raise RuntimeError(f"warm-up request {i} ({len(c.prompt)} tokens) failed: "
                               f"{c.error or f'{len(c.tokens)} of {c.asked} tokens'}")
    print(f"[chipbench] warmed {len(plan['systems'])} system prompts of {len(plan['systems'][0])} tokens, "
          f"each before {tail} more, through the served path in {time.time() - t0:.1f}s", flush=True)


def run(cell, args, t_start: float) -> dict:
    import ray_tpu
    from ray_tpu.core.cluster_utils import wait_cluster_processes_gone

    conf, traffic = cell.config, cell.traffic["params"]
    if args.rehearse:
        conf = {**conf, **conf["rehearsal"]}
        traffic = {**traffic, **cell.traffic.get("rehearsal", {})}
    spec = {"config": conf, "seed": args.seed, "rehearse": args.rehearse,
            "check_positions": check_positions(traffic)}
    print(f"[chipbench] set-up: driver started {time.time() - t_start:.1f}s after the process", flush=True)
    ray_tpu.init()
    try:
        t_cluster = time.time()
        serve.run(LLM.options(num_tpus=0 if args.rehearse else cell.chips).bind(spec), http_port=0)
        port = serve.api.get_proxy_port()
        handle = serve.get_deployment_handle(DEPLOYMENT)
        built = handle.ready.remote().result(timeout=1100)
        print(f"[chipbench] set-up: cluster up {t_cluster - t_start:.1f}s after the process; replica "
              f"ready {time.time() - t_cluster:.1f}s later: build {built['build_s']:.1f}s, "
              f"{built['compiles']} compilations, {built['kind']} x{built['count']}", flush=True)
        if args.check_seeds:
            return _check_seeds(cell, args, handle, port, traffic, conf)
        if args.sweep:
            return _sweep(cell, args, handle, port, traffic, conf)
        plan = cell.generator().plan(traffic, args.seed, args.seconds, conf["vocab_size"])
        _warm(port, plan, conf)
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
        t_drained = time.time()
        clients = client_facts(list(load.clients), t0, t1, time.time())
        done = [c for c in load.clients if c.ok and c.sent is not None]
        rng = np.random.default_rng(args.seed)
        picks = rng.choice(len(done), size=min(traffic["check_requests"], len(done)), replace=False)
        sample = [{"prompt": done[i].prompt, "served": done[i].tokens} for i in sorted(picks)]
        check = handle.check.remote(args.seed, sample).result(timeout=300)
        print(f"[chipbench] after the window: drained in {t_drained - t1:.1f}s, the reference's "
              f"check of {len(sample)} requests {time.time() - t_drained:.1f}s (its programs "
              f"compiled beside the set-up in {engine['reference_compile_s']:.1f}s; a sequence "
              f"{min(check['reference_s'])}-{max(check['reference_s'])}s)", flush=True)
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
