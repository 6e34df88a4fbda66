"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

    python3 chip_smoke.py

Drives the two main paths once, through the entry points a user calls, at
the full width of the models the repo supports (depth cut, random weights
from a seed), and checks what comes out:

- ``train``: ``ray_tpu.init()`` → ``JaxTrainer(ScalingConfig(num_workers=1,
  use_tpu=True))`` → ``make_train_state``/``make_train_step`` on the 758M
  flagship (d_model 2304, 10 layers, seq 2048, batch 12, bf16, remat) for
  6 steps on one fixed batch. Losses finite and falling, the step holds the
  Pallas kernel, the worker is on a TPU.
- ``serve``: ``serve.run`` of a ``@serve.deployment(num_tpus=1)`` 7B
  ``LLMEngine`` (paged KV, window 10, overlap, prefix cache, warmed
  buckets) → concurrent streamed HTTP requests through the proxy. Every
  request returns exactly the tokens asked for, in range, ≥ 2 share a
  decode batch, the decode program is the AOT-layout ``Compiled``.
- ``multichip`` (only where a child sees ≥ 4 chips): one worker on four
  chips under ``fsdp=4``; every plan kind against one chip at tiny shapes
  (``__graft_entry__.multichip_parity``); four one-chip actors; the
  four-process ``use_jax_distributed`` gang, which trains or says at once
  why it cannot.

One process per chip: this parent never imports jax. Every phase is a
fresh child process, and the next starts as soon as it ends: its
``ray_tpu.shutdown()`` returned, so no process of its cluster is left to
hold a chip. The multichip phases run twice over, back to back, to prove
that. No phase's exception is caught:
any failure, timeout or non-TPU backend is a non-zero exit with no result
line. On success the last two lines of stdout are ``CHIP_SMOKE_FACTS {...}``
(everything the run found: versions, per-phase compile and run times,
compile cache, peak HBM, the checks above) and then, last, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as jax reports it — the line the driver parses; it takes
no other key.
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
RESULT_TAG = "CHIP_SMOKE_RESULT "  # child → parent, one per phase
FACTS_TAG = "CHIP_SMOKE_FACTS "  # parent → reader, the line before the last
# Cold, compilation included; the whole run must fit 1200 s.
PHASE_TIMEOUT_S = {
    "probe": 120, "train": 300, "serve": 540,
    "multichip_fsdp": 300, "multichip_parity": 300,
    "multichip_actors": 120, "multichip_gang": 180,
}
MULTICHIP = tuple(name for name in PHASE_TIMEOUT_S if name.startswith("multichip_"))

# 758M flagship: the largest llama-shaped config whose fp32
# master weights + Adam moments + grads fit one v5e chip with remat.
FLAGSHIP = dict(
    vocab_size=32000, d_model=2304, n_layers=10, n_heads=18, n_kv_heads=18,
    d_ff=5760, max_seq_len=2048,
)
SEQ, BATCH_PER_CHIP, STEPS = 2048, 12, 6


# ---------------------------------------------------------------------------
# Parent: no jax here
# ---------------------------------------------------------------------------
def run_phase(name: str) -> dict:
    """Run one phase in a fresh child; its last RESULT line is the result."""
    t0 = time.monotonic()
    print(f"[chip_smoke] phase {name}: start", flush=True)
    child = subprocess.Popen(
        [sys.executable, "-c", f"import chip_smoke; chip_smoke.PHASES[{name!r}]()"],
        cwd=ROOT, stdout=subprocess.PIPE, text=True, start_new_session=True,
    )
    result = None
    try:
        killer = _kill_after(child, PHASE_TIMEOUT_S[name])
        for line in child.stdout:
            if line.startswith(RESULT_TAG):
                result = json.loads(line[len(RESULT_TAG):])
            else:
                sys.stdout.write(line)
                sys.stdout.flush()
        rc = child.wait()
        killer.cancel()
    finally:
        # Whatever the child started (controller, workers) dies with its
        # process group — on success they are already gone.
        _kill_group(child)
    if rc != 0 or result is None:
        raise SystemExit(
            f"chip_smoke: phase {name} failed (exit code {rc}, "
            f"{'no result' if result is None else 'result printed'}, "
            f"{time.monotonic() - t0:.0f}s of {PHASE_TIMEOUT_S[name]}s allowed)"
        )
    result["wall_s"] = round(time.monotonic() - t0, 1)
    print(f"[chip_smoke] phase {name}: ok in {result['wall_s']}s", flush=True)
    return result


def _kill_group(child: subprocess.Popen) -> None:
    try:
        os.killpg(child.pid, signal.SIGKILL)  # start_new_session: pgid == pid
    except ProcessLookupError:
        pass


def _kill_after(child: subprocess.Popen, seconds: float):
    import threading

    def kill():
        print(f"[chip_smoke] timeout after {seconds}s: killing the phase", flush=True)
        _kill_group(child)

    t = threading.Timer(seconds, kill)
    t.daemon = True
    t.start()
    return t


def main(phases=("train", "serve", "multichip")) -> None:
    from ray_tpu.native import build as native_build

    t0 = time.monotonic()
    found = run_phase("probe")
    device = {"platform": found["platform"], "kind": found["device_kind"],
              "count": found["n_devices"]}
    out = {**found, "phases": {}}
    for phase in phases:
        if phase == "multichip":
            n = device["count"]
            if n < 4:
                out["phases"]["multichip"] = f"not run, {n} chip(s)"
                print(f"[chip_smoke] multichip: not run, {n} chip(s)", flush=True)
                continue
            names = MULTICHIP * 2  # the second pass reports; both must pass
        else:
            names = (phase,)
        for name in names:
            out["phases"][name] = run_phase(name)
    ran = [r for r in out["phases"].values() if isinstance(r, dict)]
    out["compile_cache"] = {
        "dir": sorted({r["compile_cache_dir"] for r in ran if "compile_cache_dir" in r}),
        "hits": sum(r.get("compile_cache_hits", 0) for r in ran),
        "misses": sum(r.get("compile_cache_misses", 0) for r in ran),
    }
    native_build.load()  # falls back to Python silently: say which it was
    out["native_library"] = native_build.build_error() or "loaded"
    out["wall_s"] = round(time.monotonic() - t0, 1)
    assert "jax" not in sys.modules, "the smoke's parent must never import jax"
    out["parent_imported_jax"] = False
    print(FACTS_TAG + json.dumps(out), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


# ---------------------------------------------------------------------------
# Children
# ---------------------------------------------------------------------------
def _emit(result: dict) -> None:
    print(RESULT_TAG + json.dumps(result), flush=True)


def _require_tpu(error=RuntimeError):
    """jax's first device, or ``error`` naming why this is not a chip run."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise error(
            f"chip_smoke: JAX found no accelerator: platform {dev.platform!r} "
            f"({dev.device_kind}), JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}. "
            "This script proves the chip path and does not fall back to the CPU."
        )
    return dev


def _device_facts() -> dict:
    """What the process that owns the chip can say about it."""
    import jax

    from ray_tpu.core.node_telemetry import sample_devices
    from ray_tpu.util import compile_tracker

    dev = jax.devices()[0]
    snap = compile_tracker.snapshot()
    hbm = sample_devices()
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_devices": len(jax.devices()),
        "peak_hbm_bytes_per_device": [r["peak_bytes_in_use"] for r in hbm],
        "hbm_limit_bytes": [r["bytes_limit"] for r in hbm],
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "compiles": snap["compiles"],
        "compile_seconds": snap["compile_seconds"],
        "compile_cache_hits": snap["cache_hits"],
        "compile_cache_misses": snap["cache_misses"],
        "tpu_env": {k: os.environ[k] for k in (
            "TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_BOUNDS",
        ) if k in os.environ},
    }


def _phase_probe():
    import importlib.metadata as md

    import jax

    dev = _require_tpu(SystemExit)  # the message alone, no traceback
    _emit({
        "platform": dev.platform, "device_kind": dev.device_kind,
        "n_devices": len(jax.devices()),
        "local_device_count": jax.local_device_count(),
        "versions": {p: md.version(p) for p in ("jax", "jaxlib", "libtpu")},
    })


def _flagship_train_fn(config):
    """Runs inside the TrainWorker: the process that owns the chip(s)."""
    import time as _t

    import jax
    import jax.numpy as jnp

    from ray_tpu import train
    from ray_tpu.models import transformer as tf
    from ray_tpu.parallel import MeshPlan, build_mesh, make_train_state, make_train_step
    from ray_tpu.parallel import mesh as mesh_lib
    from ray_tpu.parallel.train_step import make_optimizer
    from ray_tpu.util import compile_tracker

    import chip_smoke

    compile_tracker.install()
    chip_smoke._require_tpu()
    cfg = tf.TransformerConfig(dtype=jnp.bfloat16, remat=True, **chip_smoke.FLAGSHIP)
    plan = MeshPlan(**config["plan"])
    assert plan.num_devices == jax.device_count(), (plan, jax.devices())
    mesh = build_mesh(plan)
    opt = make_optimizer(lr=3e-4, warmup=10)
    batch_size = chip_smoke.BATCH_PER_CHIP * plan.num_devices
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch_size, chip_smoke.SEQ + 1), 0, cfg.vocab_size
    )
    batch = {"tokens": jax.device_put(tokens, mesh_lib.batch_sharding(mesh, plan))}
    t0 = _t.perf_counter()
    params, opt_state, _ = make_train_state(cfg, plan, mesh, opt)
    step = make_train_step(cfg, plan, mesh, opt)
    pallas = "tpu_custom_call" in step.lower(params, opt_state, batch).as_text()
    layer_device_sets = sorted(
        {len(x.sharding.device_set) for x in jax.tree.leaves(params["layers"])}
    )
    compiles_at, losses = [], []
    for i in range(chip_smoke.STEPS):
        params, opt_state, m = step(params, opt_state, batch)
        # jax.block_until_ready is a sync point on this backend, also on
        # this worker thread; float() then only copies a scalar.
        t_b = _t.perf_counter()
        jax.block_until_ready(m["loss"])
        t_f = _t.perf_counter()
        losses.append(float(m["loss"]))
        compiles_at.append(compile_tracker.snapshot()["compiles"])
        if i == 0:
            first_step_s = _t.perf_counter() - t0
            t1 = _t.perf_counter()
        train.report({"step": i, "loss": losses[-1]})
    run_s = _t.perf_counter() - t1
    in_use = [d.memory_stats()["bytes_in_use"] for d in jax.local_devices()]
    train.report({
        "step": chip_smoke.STEPS, "loss": losses[-1], "losses": losses,
        "pallas_custom_call": pallas,
        "mesh_devices": sorted(d.id for d in mesh.devices.flat),
        "layer_weight_device_set_sizes": layer_device_sets,
        "bytes_in_use_per_device": in_use,
        "setup_and_first_step_s": round(first_step_s, 1),
        "post_compile_steps": chip_smoke.STEPS - 1,
        "post_compile_run_s": round(run_s, 2),
        "compiles_after_each_step": compiles_at,
        "last_sync_ms": {"block_until_ready": round((t_f - t_b) * 1e3, 2),
                         "float_after": round((_t.perf_counter() - t_f) * 1e3, 2)},
        **chip_smoke._device_facts(),
    })


def _run_flagship_trainer(name: str, tpus_per_worker: int, plan: dict) -> dict:
    import tempfile

    # A driver that imports jax before init() — as most user scripts do —
    # must leave the chip to its worker.
    import jax  # noqa: F401

    import ray_tpu
    from ray_tpu.accelerators.tpu import jax_backend_initialized
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    info = ray_tpu.init()
    try:
        trainer = JaxTrainer(
            _flagship_train_fn,
            train_loop_config={"plan": plan},
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=True,
                resources_per_worker={"CPU": 1, "TPU": tpus_per_worker},
            ),
            run_config=RunConfig(name=name, storage_path=tempfile.mkdtemp(prefix="chip_smoke_")),
        )
        result = trainer.fit()
        if result.error is not None:
            raise result.error
        cluster_tpus = ray_tpu.cluster_resources().get("TPU", 0)
    finally:
        ray_tpu.shutdown()
    final = result.metrics
    losses = final["losses"]
    reported = [m["loss"] for m in result.metrics_history[:STEPS]]
    assert reported == losses, (reported, losses)  # train.report carried every step
    assert all(x == x and abs(x) != float("inf") for x in losses), losses
    assert losses[-1] < losses[0], losses
    assert final["platform"] == "tpu", final
    assert final["pallas_custom_call"], "the lowered step holds no tpu_custom_call"
    assert final["n_devices"] == tpus_per_worker, final
    assert not jax_backend_initialized(), "the driver opened a jax backend"
    final.pop("step"), final.pop("loss")
    final.update(cluster_tpus=cluster_tpus, session_dir=info["session_dir"],
                 driver_backend_initialized=False)
    return final


def _phase_train():
    _emit(_run_flagship_trainer("chip_smoke_train", 1, {"dp": 1}))


def _phase_multichip_fsdp():
    final = _run_flagship_trainer("chip_smoke_fsdp4", 4, {"fsdp": 4})
    assert len(set(final["mesh_devices"])) == 4, final["mesh_devices"]
    assert final["layer_weight_device_set_sizes"] == [4], final
    used = final["bytes_in_use_per_device"]
    assert max(used) <= 1.25 * min(used), f"memory piled on one device: {used}"
    _emit(final)


def _phase_serve():
    import threading
    import urllib.request

    import numpy as np

    import ray_tpu
    from ray_tpu import serve

    n_requests, prompt_len, max_new = 8, 16, 36

    @serve.deployment(name="llm", num_tpus=1, max_ongoing_requests=16)
    class LLM:
        def __init__(self):
            import time as _t

            import jax
            import jax.numpy as jnp

            from ray_tpu.models import transformer as tf
            from ray_tpu.models.paged import PagedConfig
            from ray_tpu.serve.llm_engine import LLMEngine
            from ray_tpu.util import compile_tracker

            import chip_smoke

            compile_tracker.install()
            chip_smoke._require_tpu()
            cfg = tf.TransformerConfig.llama7b(
                max_seq_len=2048, dtype=jnp.bfloat16, remat=False
            )

            def init_bf16():
                return jax.tree.map(
                    lambda x: x.astype(jnp.bfloat16),
                    tf.init_params(jax.random.PRNGKey(0), cfg),
                )

            t0 = _t.perf_counter()
            # Sized to this phase's requests: 16+36+19 (overlap
            # overshoot) = 71 tokens → 9 blocks a slot, 16 slots = the pool.
            self.engine = LLMEngine(
                init_bf16, cfg,
                PagedConfig(block_size=8, num_blocks=145, max_batch=16,
                            max_blocks_per_seq=9),
                decode_window=10, overlap=True, enable_prefix_cache=True,
                warmup_buckets=True,
            )
            self.build_s = _t.perf_counter() - t0
            self.vocab = cfg.vocab_size
            self.window_ms = chip_smoke._time_decode_windows(self.engine)
            self.engine.start()

        def __call__(self, request):
            req = self.engine.add_request(
                [int(t) for t in request["prompt"]],
                max_new_tokens=int(request["max_new_tokens"]),
            )
            for tok in req.tokens(timeout=120):
                yield {"tok": int(tok)}
            if req.error:
                raise RuntimeError(req.error)

        def facts(self):
            import chip_smoke

            return {
                "engine_build_s": round(self.build_s, 1),
                "decode_program": type(self.engine._decode).__name__,
                "decode_window_ms": self.window_ms,
                "vocab": self.vocab,
                "stats": dict(self.engine.stats),
                **chip_smoke._device_facts(),
            }

    t0 = time.perf_counter()
    ray_tpu.init()
    try:
        serve.run(LLM.bind(), http_port=0)
        port = serve.api.get_proxy_port()
        handle = serve.get_deployment_handle("llm")
        # Returns when the replica's __init__ has built and warmed the engine.
        built = handle.facts.remote().result(timeout=PHASE_TIMEOUT_S["serve"])
        setup_s = time.perf_counter() - t0
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, built["vocab"], prompt_len).tolist()
                   for _ in range(n_requests)]
        outputs = [None] * n_requests

        def client(i):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/llm",
                data=json.dumps({"prompt": prompts[i], "max_new_tokens": max_new}).encode(),
                headers={"Accept": "application/x-ndjson",
                         "Content-Type": "application/json"},
                method="POST",
            )
            with urllib.request.urlopen(req, timeout=180) as resp:
                outputs[i] = [json.loads(l) for l in resp.read().decode().splitlines() if l]

        t1 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,)) for i in range(n_requests)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(240)
        run_s = time.perf_counter() - t1
        final = handle.facts.remote().result(timeout=60)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    for i, frames in enumerate(outputs):
        assert frames is not None, f"request {i} did not return"
        toks = [f.get("tok") for f in frames]
        assert len(toks) == max_new, f"request {i}: {len(toks)} frames, want {max_new}: {frames[-2:]}"
        assert all(isinstance(t, int) and 0 <= t < built["vocab"] for t in toks), frames
    stats = final["stats"]
    assert stats["max_active"] >= 2, stats
    assert stats["finished"] >= n_requests, stats
    assert final["decode_program"] == "Compiled", final["decode_program"]
    assert final["platform"] == "tpu", final
    final.update(requests=n_requests, tokens_per_request=max_new,
                 setup_s=round(setup_s, 1), requests_run_s=round(run_s, 2))
    _emit(final)


def _time_decode_windows(eng, n: int = 8) -> dict:
    """Wall time of one decode window (all ``max_batch`` lanes compute,
    occupied or not) with a host sync after every window against windows
    chained on device outputs with one sync at the end. Runs on the idle
    engine's own buffers; every write lands in the trash block."""
    import jax.numpy as jnp
    import numpy as np

    tables, lens = jnp.asarray(eng.tables), jnp.asarray(eng.lens)
    temps, cur = jnp.asarray(eng.temps), jnp.asarray(eng.cur)
    out = {"window": eng.window, "batch": eng.pcfg.max_batch, "windows_timed": n}
    for mode in ("synced", "chained"):
        t0 = time.perf_counter()
        for _ in range(n):
            seq, cur, _lens, eng.cache = eng._decode(
                eng.params, cur, eng.cache, tables, lens, temps, eng.key
            )
            if mode == "synced":
                np.asarray(seq)
        np.asarray(seq)
        out[f"{mode}_ms"] = round((time.perf_counter() - t0) * 1e3 / n, 2)
    return out


def _phase_multichip_parity():
    """Every plan kind on the four real chips against one chip, with
    __graft_entry__'s tolerances. A bare process: it is the one owner."""
    import jax

    import __graft_entry__ as graft

    _require_tpu()
    t0 = time.perf_counter()
    # The tolerances (3e-5 loss, 3e-4 grad norm) are reduction-order sized:
    # a TPU's default fp32 matmul rounds to bf16 passes and differs between
    # the flash kernel and ring attention's einsums by 1.5e-4.
    jax.config.update("jax_default_matmul_precision", "highest")
    # seq 128: the flash backward's q block is a multiple of 128 on a TPU.
    graft.multichip_parity(4, seq=128)
    _emit({
        "plans": [p.sizes() for p in graft._pick_plans(4)]
        + ["MPMD", "MPMD+tp", "MPMD-gang"],
        "device_order": [[d.id, list(d.coords)] for d in jax.devices()],
        "run_s": round(time.perf_counter() - t0, 1),
    })


def _phase_multichip_actors():
    """Four TPU actors: each sees exactly one chip, all four at once."""
    import ray_tpu

    @ray_tpu.remote(num_tpus=1, num_cpus=0)
    class OneChip:
        def look(self):
            import jax
            import jax.numpy as jnp

            import chip_smoke

            chip_smoke._require_tpu()
            x = jnp.ones((1024, 1024), jnp.bfloat16)
            return {
                "pid": os.getpid(), "n_devices": len(jax.devices()),
                "visible": os.environ.get("TPU_VISIBLE_CHIPS"),
                "checksum": float((x @ x).sum()),
            }

    ray_tpu.init()
    try:
        actors = [OneChip.remote() for _ in range(4)]
        # All four hold their chip at the same time: a chip has one owner,
        # so four live owners are four different chips.
        seen = ray_tpu.get([a.look.remote() for a in actors], timeout=100)
    finally:
        ray_tpu.shutdown()
    assert [s["n_devices"] for s in seen] == [1] * 4, seen
    assert sorted(s["visible"] for s in seen) == ["0", "1", "2", "3"], seen
    assert len({s["pid"] for s in seen}) == 4, seen
    _emit({"actors": seen})


def _phase_multichip_gang():
    """Four one-chip workers asked to be ONE jax runtime. Under libtpu
    0.0.34 sub-host processes each come up as their own 1-chip slice
    (CHANGES.md, PR 21), so the gang must say so at once — not hang in
    rendezvous, and not train four unrelated models."""
    import tempfile

    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    def gang_fn():
        import jax

        from ray_tpu import train

        train.report({"global_devices": jax.device_count()})

    t0 = time.perf_counter()
    ray_tpu.init()
    try:
        result = JaxTrainer(
            gang_fn,
            scaling_config=ScalingConfig(num_workers=4, use_tpu=True,
                                         use_jax_distributed=True),
            run_config=RunConfig(name="chip_smoke_gang",
                                 storage_path=tempfile.mkdtemp(prefix="chip_smoke_")),
        ).fit()
    finally:
        ray_tpu.shutdown()
    seconds = time.perf_counter() - t0
    if result.error is None:
        assert result.metrics["global_devices"] == 4, result.metrics
        _emit({"outcome": "one runtime of 4 devices", "seconds": round(seconds, 1)})
        return
    reason = str(result.error)
    assert "do not form one runtime" in reason, reason
    _emit({"outcome": "fails fast with the stated reason",
           "reason": reason[reason.index("use_jax_distributed:"):][:330],
           "seconds": round(seconds, 1)})


PHASES = {
    "probe": _phase_probe,
    "train": _phase_train,
    "serve": _phase_serve,
    "multichip_fsdp": _phase_multichip_fsdp,
    "multichip_parity": _phase_multichip_parity,
    "multichip_actors": _phase_multichip_actors,
    "multichip_gang": _phase_multichip_gang,
}

if __name__ == "__main__":
    main()
