"""Host-side regression gate: the tiny CPU benches (``python bench.py --cpu``).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "tokens/s/chip", "vs_baseline": N, ...}

North star (BASELINE.json): framework throughput >= 90% of single-process
JAX on the same hardware. ``vs_baseline`` is measured directly on a tiny
model: framework train step (ray_tpu.parallel.make_train_step — the same
compiled path the JaxTrainer drives) vs a plain hand-rolled jax.jit train
step written inline below with no framework imports in the loop. The
serving, ingest and RL arms report host-side figures beside it.

Nothing here measures a chip: that is ``python3 -m chipbench``
(BENCHMARK.json; the driver's readings are in PERF_LEDGER.jsonl), which
owns the one count of work (``chipbench/work.py``) and the one table of
peaks (``chipbench/peaks.py``). Run without ``--cpu`` this exits saying so.

Diagnostics go to stderr; stdout stays one JSON line.
"""
from __future__ import annotations

import functools
import json
import sys
import time


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def main():
    if "--cpu" not in sys.argv:
        raise SystemExit(
            "bench.py is the host-side regression gate: run `python bench.py "
            "--cpu`. The chip is measured by `python3 -m chipbench` "
            "(BENCHMARK.json, PERF_LEDGER.jsonl)."
        )
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import transformer as tf
    from ray_tpu.parallel import MeshPlan, build_mesh, make_train_state, make_train_step
    from ray_tpu.parallel import mesh as mesh_lib
    from ray_tpu.parallel.train_step import make_optimizer

    n_dev = jax.device_count()
    log(f"devices: {n_dev} x {jax.devices()[0].platform}")
    cfg = tf.TransformerConfig.tiny(dtype=jnp.float32)
    batch_size, seq, steps, warmup = 4, 64, 20, 3

    plan = MeshPlan(dp=n_dev)
    mesh = build_mesh(plan)
    opt = make_optimizer(lr=3e-4, warmup=10)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch_size, seq + 1), 0, cfg.vocab_size)
    batch = {"tokens": jax.device_put(tokens, mesh_lib.batch_sharding(mesh, plan))}

    # ---- framework path -------------------------------------------------
    params, opt_state, _ = make_train_state(cfg, plan, mesh, opt)
    step = make_train_step(cfg, plan, mesh, opt)

    # ---- plain JAX baseline (no framework in the loop) ------------------
    def plain_loss(params, batch):
        return tf.loss_fn(params, batch, cfg)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def plain_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(plain_loss)(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, {"loss": loss}

    # Same placement a plain-JAX user would pick on this mesh: replicated
    # params, batch-sharded data (single-device this is a no-op).
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep = NamedSharding(mesh, P())
    params2 = jax.jit(lambda k: tf.init_params(k, cfg), out_shardings=rep)(jax.random.PRNGKey(0))
    opt_state2 = jax.jit(opt.init, out_shardings=rep)(params2)

    # Interleaved medians: alternating measurement blocks cancel the
    # thermal/cache drift that biases whichever path is timed first on
    # CPU. Holds both states — fine at tiny scale.
    fw_time, pj_time = _time_interleaved(
        [(step, params, opt_state), (plain_step, params2, opt_state2)],
        batch,
        steps,
        warmup,
        log,
        ("framework", "plain-jax"),
    )

    value = batch_size * seq / fw_time / n_dev
    vs_baseline = pj_time / fw_time  # >1 → framework faster than plain JAX
    log(f"step: framework {fw_time*1e3:.1f}ms, plain-jax {pj_time*1e3:.1f}ms")
    log(f"tokens/s/chip {value:.0f} (host CPU: no device peak, no MFU)")

    extra = {}
    try:
        tiny_serve = _bench_serving_tiny_cpu(log, cfg)
        extra["serve_tiny_cpu"] = tiny_serve
        extra["serve_prefix_hit_rate"] = tiny_serve["prefix_hit_rate"]
    except Exception as e:  # noqa: BLE001 — smoke bench must not kill the metric
        log(f"cpu serve bench failed: {e!r}")
    try:
        extra["ingest_cpu"] = _bench_ingest_cpu(log)
        extra["profiling_overhead_pct"] = extra["ingest_cpu"][
            "profiling_overhead_pct"
        ]
    except Exception as e:  # noqa: BLE001 — ingest bench must not kill the metric
        log(f"cpu ingest bench failed: {e!r}")
    try:
        extra["rl_ppo_cpu"] = _bench_rl_ppo_cpu(log)
        extra["rl_ppo_env_steps_per_sec"] = extra["rl_ppo_cpu"][
            "podracer_env_steps_per_s"
        ]
    except Exception as e:  # noqa: BLE001 — RL bench must not kill the metric
        log(f"cpu rl ppo bench failed: {e!r}")

    record = {
        "metric": "train_tokens_per_sec_per_chip_tiny_cpu",
        "value": round(value, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(vs_baseline, 4),
    }
    record.update(extra)
    print(json.dumps(record))


def rng_prompt(cfg, n, _state=[0]):
    import numpy as np

    _state[0] += 1
    return np.random.default_rng(_state[0]).integers(0, cfg.vocab_size, n).tolist()


def _serve_prefix_scenario(eng, cfg, log, *, tag, n_req=8, shared_len=32,
                           uniq_len=8, gen_tokens=12):
    """Shared-prefix serving: ``n_req`` requests sharing a ``shared_len``
    system prompt with distinct tails, submitted twice. The second (warm)
    pass must serve the shared blocks from the prefix cache — reported as
    hit-rate over the scenario plus cold/warm TTFT."""
    import statistics

    shared = rng_prompt(cfg, shared_len)
    prompts = [shared + rng_prompt(cfg, uniq_len) for _ in range(n_req)]
    h0 = eng.stats["prefix_hit_tokens"]
    l0 = eng.stats["prefix_lookup_tokens"]
    ttft = {}
    for phase in ("cold", "warm"):
        reqs = [eng.add_request(p, gen_tokens) for p in prompts]
        if eng._thread is None:
            while eng.active_count() or eng.waiting:
                eng.step()
        for r in reqs:
            list(r.tokens(timeout=300.0))
        samples = [(r.first_token_ts - r.submit_ts) * 1000.0 for r in reqs]
        # Only the first request of the first pass is guaranteed a full
        # cold prefill — later cold-pass admissions may already map
        # blocks an earlier request of the SAME pass registered (that
        # concurrent sharing is part of the feature, but it must not
        # masquerade as the cold baseline). Warm pass: median.
        ttft[phase] = samples[0] if phase == "cold" else statistics.median(samples)
    hit = eng.stats["prefix_hit_tokens"] - h0
    lookup = eng.stats["prefix_lookup_tokens"] - l0
    rate = hit / max(1, lookup)
    log(
        f"{tag}: shared-prefix hit rate {rate:.2f} ({hit}/{lookup} tokens, "
        f"incl. within-pass sharing), TTFT cold(first) {ttft['cold']:.1f} ms "
        f"-> warm p50 {ttft['warm']:.1f} ms"
    )
    return {
        "prefix_hit_rate": round(rate, 3),
        "prefix_ttft_cold_ms": round(ttft["cold"], 1),
        "prefix_ttft_warm_ms": round(ttft["warm"], 1),
    }


def _bench_serving_tiny_cpu(log, cfg):
    """CPU smoke of the serving perf suite (tiny model): engine with
    prefix cache + chunked prefill + overlap, shared-prefix hit rate and
    TTFT, plus a small aggregate-throughput number."""
    import jax

    from ray_tpu.models import transformer as tf
    from ray_tpu.models.paged import PagedConfig
    from ray_tpu.serve.llm_engine import LLMEngine

    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    pcfg = PagedConfig(block_size=8, num_blocks=65, max_batch=8,
                       max_blocks_per_seq=12)
    eng = LLMEngine(params, cfg, pcfg, decode_window=4, overlap=True,
                    enable_prefix_cache=True, prefill_chunk=16,
                    warmup_buckets=True)
    res = {"warmup_s": eng.stats.get("warmup_s")}
    prompts = [rng_prompt(cfg, 16) for _ in range(8)]
    t0 = time.perf_counter()
    outs = eng.generate_batch(prompts, 24)
    dt = time.perf_counter() - t0
    res["c8_tok_s"] = round(sum(len(o) for o in outs) / dt, 1)
    log(f"tiny cpu serve: c8 {res['c8_tok_s']} tok/s aggregate")
    res.update(_serve_prefix_scenario(eng, cfg, log, tag="tiny cpu serve"))
    res["overlap_occupancy"] = round(
        eng.stats["spec_windows"] / max(1, eng.stats["steps"]), 3
    )
    log(f"tiny cpu serve engine stats: {eng.stats}")
    return res


def _bench_ingest_cpu(log):
    """Ingest-bound A/B for the pipelined data→device path (ISSUE 5):
    materialized columnar blocks → iter_jax_batches, consumed by a
    simulated device step sized to the measured host batch-prep cost —
    the regime where fetch/rebatch/H2D either serialize with the step
    (pipeline off) or hide behind it (pipeline on). Reports batches/s
    off vs on, the speedup, and the zero-copy hit count."""
    import numpy as np

    import ray_tpu
    from ray_tpu.data.metrics import data_metrics

    ray_tpu.init(num_cpus=4)
    try:
        # 24 blocks x ~2MB (8192 rows x 64 f32) — shm-tier, zero-copy eligible
        arr = np.arange(24 * 8192 * 64, dtype=np.float32).reshape(-1, 64)
        ds = ray_tpu.data.from_numpy({"x": arr}, parallelism=24).materialize()
        m = data_metrics()

        def run(prefetch_blocks, prefetch_to_device, step_s):
            it = ds.iterator().iter_jax_batches(
                batch_size=4096,
                dtypes={"x": np.float32},
                prefetch_blocks=prefetch_blocks,
                prefetch_to_device=prefetch_to_device,
            )
            n = 0
            t0 = time.perf_counter()
            for _ in it:
                if step_s:
                    time.sleep(step_s)
                n += 1
            return n / (time.perf_counter() - t0)

        hits0 = m.counts.get("zero_copy_hits", 0)
        run(0, 0, 0.0)  # warm: page-fault the mappings, first transfers
        base = run(0, 0, 0.0)  # calibrate host prep cost per batch
        step_s = 1.0 / base
        # Interleaved best-of-2 per arm (scheduler-noise control, same
        # practice as the CPU train A/B above): off/on alternate so load
        # drift biases neither arm.
        off = on = 0.0
        for _ in range(2):
            off = max(off, run(0, 0, step_s))
            on = max(on, run(2, 2, step_s))
        hits = m.counts.get("zero_copy_hits", 0) - hits0
        # Continuous-profiler overhead A/B (ISSUE 9): the same pipelined
        # ingest arm UNPACED (pure host throughput — no device-step sleep
        # to hide the sampler behind), interleaved with the incident-ring
        # sampler on at 19 Hz vs off. Budget: < 3%.
        from ray_tpu.util import profiling

        prof_off = prof_on = 0.0
        for _ in range(3):
            prof_off = max(prof_off, run(2, 2, 0.0))
            sampler = profiling.ContinuousSampler(hz=19.0).start()
            try:
                prof_on = max(prof_on, run(2, 2, 0.0))
            finally:
                sampler.stop()
        overhead_pct = round(max(0.0, (prof_off - prof_on) / prof_off) * 100.0, 2)
        res = {
            "batches_per_s_off": round(off, 1),
            "batches_per_s_on": round(on, 1),
            "pipeline_speedup": round(on / off, 2),
            "data_zero_copy_hits": hits,
            "profiling_overhead_pct": overhead_pct,
            "profiling_overhead_ok": overhead_pct < 3.0,
        }
        log(
            f"cpu ingest: {off:.1f} -> {on:.1f} batches/s "
            f"({res['pipeline_speedup']}x, step {step_s*1e3:.2f}ms, "
            f"zero-copy hits {hits})"
        )
        log(
            f"continuous-profiler overhead (19 Hz, unpaced ingest): "
            f"{prof_off:.1f} -> {prof_on:.1f} batches/s = {overhead_pct}% "
            f"({'OK' if overhead_pct < 3.0 else 'OVER'} vs 3% budget)"
        )
        return res
    finally:
        ray_tpu.shutdown()


def _bench_rl_ppo_cpu(log):
    """RLlib PPO CartPole env-steps/sec (the BASELINE.json north-star
    metric): synchronous driver loop vs the podracer async pipeline
    (ISSUE 8, ray_tpu.rllib.podracer), 4 CPU env-runner actors in both
    arms. Mid-run one podracer runner is KILLED to prove the bench
    completes through an actor restart (queue keeps flowing, restart
    recorded in the control-plane lifecycle events)."""
    import ray_tpu
    from ray_tpu.rllib import PPOConfig
    from ray_tpu.util import state

    def base():
        # kl_target high = KL early-stop off, so BOTH arms do the exact
        # same learner work per batch (a clean A/B: the podracer win is
        # sampling/update overlap, not a shorter epoch cycle).
        return (
            PPOConfig()
            .environment("CartPole-v1")
            .training(train_batch_size=2048, minibatch_size=256,
                      num_epochs=4, lr=1e-3, kl_target=10.0)
            .debugging(seed=0)
        )

    iters = 6
    ray_tpu.init(num_cpus=8)
    try:
        # -- arm 1: synchronous driver loop (sample -> update -> sync) ----
        cfg = base().env_runners(
            num_env_runners=4, num_envs_per_env_runner=2,
            rollout_fragment_length=256,
        )
        algo = cfg.build()
        algo.train()  # warmup: jit compiles on every runner + the learner
        t0 = time.perf_counter()
        steps = 0
        for _ in range(iters):
            r = algo.train()
            steps += r["env_steps_this_iter"]
        sync_rate = steps / (time.perf_counter() - t0)
        log(f"rl ppo: sync {sync_rate:.0f} env-steps/s "
            f"(return {r['episode_return_mean']:.1f})")
        algo.stop()

        # -- arm 2: podracer async pipeline -------------------------------
        cfg = base().env_runners(
            num_envs_per_env_runner=2, rollout_fragment_length=256
        ).podracer(num_async_runners=4, sample_queue_size=16)
        algo = cfg.build()
        algo.train()  # warmup
        t0 = time.perf_counter()
        steps = 0
        for i in range(iters):
            if i == iters // 2:
                # kill a runner mid-run: the bench must complete anyway
                ray_tpu.kill(algo._podracer.manager.actors[0])
                log("rl ppo: killed runner 0 mid-run")
            r = algo.train()
            steps += r["env_steps_this_iter"]
        pod_rate = steps / (time.perf_counter() - t0)
        # The measured window can end within the ~0.5s crash-detection
        # latency; give the pipeline a bounded beat to register the
        # restart so it is visible in the report and lifecycle events.
        deadline = time.time() + 15
        while algo._podracer.num_restarts == 0 and time.time() < deadline:
            algo._podracer.check_runners()
            time.sleep(0.25)
        restarts = algo._podracer.num_restarts
        death_events = sum(
            1 for e in state.list_lifecycle_events(limit=100000)
            if e.get("kind") == "actor" and e.get("state") in ("DEAD", "FAILED")
        )
        algo.stop()
        res = {
            "sync_env_steps_per_s": round(sync_rate, 1),
            "podracer_env_steps_per_s": round(pod_rate, 1),
            "podracer_speedup": round(pod_rate / sync_rate, 2),
            "runner_restarts": restarts,
            "lifecycle_runner_death_events": death_events,
            "num_runners": 4,
        }
        log(
            f"rl ppo: podracer {pod_rate:.0f} env-steps/s "
            f"({res['podracer_speedup']}x sync, {restarts} runner "
            f"restart(s) mid-run, {death_events} lifecycle death event(s))"
        )
        return res
    finally:
        ray_tpu.shutdown()


def _warmup(step, params, opt_state, batch, warmup, log, tag):
    import jax

    for i in range(warmup):
        t0 = time.perf_counter()
        params, opt_state, m = step(params, opt_state, batch)
        jax.block_until_ready(m["loss"])
        log(f"{tag} warmup[{i}] {time.perf_counter()-t0:.2f}s loss={float(m['loss']):.3f}")
    return params, opt_state


def _time_interleaved(entries, batch, steps, warmup, log, tags, blocks: int = 4):
    """Median per-step time for each entry, measured in alternating blocks."""
    import statistics

    import jax

    states = []
    for (step, params, opt_state), tag in zip(entries, tags):
        params, opt_state = _warmup(step, params, opt_state, batch, warmup, log, tag)
        states.append((step, params, opt_state))
    samples = [[] for _ in entries]
    per_block = max(1, steps // blocks)
    for _ in range(blocks):
        for i, (step, params, opt_state) in enumerate(states):
            t0 = time.perf_counter()
            for _ in range(per_block):
                params, opt_state, m = step(params, opt_state, batch)
            jax.block_until_ready(m["loss"])
            samples[i].append((time.perf_counter() - t0) / per_block)
            states[i] = (step, params, opt_state)
    return [statistics.median(s) for s in samples]


if __name__ == "__main__":
    main()
