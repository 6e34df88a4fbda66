"""A training cell: ``JaxTrainer`` with the benchmark's loop function, one
worker that holds every chip of the cell.

The loop is the user's: ``make_train_state`` and ``make_train_step`` under the
cell's ``MeshPlan``, the weights replaced by the benchmark's own from the
seed, each step's batch made on the host and put on the device inside the
loop, every step ended by ``block_until_ready``. The worker reads the
device's facts, takes and reduces the trace, and runs the reference.
"""
from __future__ import annotations

import json
import tempfile
import time

import numpy as np


def _build(conf: dict, rehearse: bool):
    """Everything the loop and the check share, built in the worker."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import transformer as tf
    from ray_tpu.parallel import MeshPlan, build_mesh
    from ray_tpu.parallel.train_step import make_optimizer

    from chipbench import weights as W

    dims = W.Dims.from_config(conf)
    if dims.head_dim * dims.heads != dims.hidden:
        raise ValueError("the program derives head_dim as hidden_size / heads")
    job = conf["train"]
    cfg = tf.TransformerConfig(
        vocab_size=dims.vocab, d_model=dims.hidden, n_layers=dims.layers, n_heads=dims.heads,
        n_kv_heads=dims.kv_heads, d_ff=dims.ffn, rope_theta=dims.rope_theta,
        max_seq_len=job["seq_len"], dtype=getattr(jnp, conf["dtype"]), remat=job["remat"],
        logits_chunk=job["logits_chunk"],
    )
    plan = MeshPlan(**job["plan"])
    if plan.num_devices != jax.device_count():
        raise RuntimeError(f"the plan {job['plan']} needs {plan.num_devices} devices, "
                           f"this worker holds {jax.device_count()}")
    mesh = build_mesh(plan)
    opt = make_optimizer(**job["optimizer"])
    return dims, cfg, plan, mesh, opt, job


def train_loop(config: dict):
    """Runs inside the ``TrainWorker``: the process that holds the chip(s)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import train
    from ray_tpu.parallel import make_train_state, make_train_step
    from ray_tpu.parallel import mesh as mesh_lib

    from chipbench import onchip
    from chipbench import weights as W
    from chipbench.generators import token_batches

    onchip.compile_count()
    onchip.require_device(config["rehearse"])
    conf, seed, seconds = config["config"], config["seed"], config["seconds"]
    distinct = config["traffic"]["distinct_batches"]
    dims, cfg, plan, mesh, opt, job = _build(conf, config["rehearse"])
    sequences = job["batch_per_chip"] * plan.num_devices
    tokens_per_step = sequences * job["seq_len"]
    batch_sharding = mesh_lib.batch_sharding(mesh, plan)

    def make_weights(s):
        return jax.jit(lambda k: W.make_params(k, dims, jnp.float32),
                       out_shardings=shardings["params"])(W.seed_key(s))

    def put_batch(s, i):
        host = token_batches.batch(s, i, sequences, job["seq_len"], dims.vocab)
        return {"tokens": jax.device_put(host, batch_sharding)}

    if config["check_seeds"]:
        shardings = {"params": mesh_lib.param_shardings(mesh, cfg, plan)}
        for s in config["check_seeds"]:
            row = check(s, make_weights(s), put_batch(s, 0), conf, control=config["control"])
            train.report({"check_seed": s, **row})
        return

    params, opt_state, shardings = make_train_state(cfg, plan, mesh, opt)
    jax.tree.map(lambda x: x.delete(), params)
    params = make_weights(seed)  # the benchmark's weights in the program's place
    step = make_train_step(cfg, plan, mesh, opt)

    losses, i = [], 0
    for _ in range(job["warmup_steps"]):  # compiles, then runs once more warm
        params, opt_state, m = step(params, opt_state, put_batch(seed, i % distinct))
        losses.append(float(jax.block_until_ready(m["loss"])))
        i += 1
    compiles_before = onchip.compile_count()
    trace, traced, trace_steps = None, None, []
    step_s = []
    t0 = time.time()
    while time.time() - t0 < seconds:
        if config["trace"] and trace is None and len(step_s) == job["trace"]["after_steps"]:
            trace = onchip.DeviceTrace()
            trace.start()
        ts = time.time()
        # Spans of the benchmark's own loop, on the trace's clock: they say
        # what the host was in while the device waited.
        with jax.profiler.TraceAnnotation("chipbench.make_batch"):
            batch = put_batch(seed, i % distinct)
        with jax.profiler.TraceAnnotation("chipbench.dispatch_step"):
            params, opt_state, m = step(params, opt_state, batch)
        with jax.profiler.TraceAnnotation("chipbench.wait_for_step"):
            jax.block_until_ready(m["loss"])
        step_s.append(time.time() - ts)
        losses.append(m["loss"])
        i += 1
        if trace is not None and traced is None:
            trace_steps.append(step_s[-1])
            if len(trace_steps) == job["trace"]["steps"]:
                traced = trace.stop(config["keep_trace"])
                traced["steps"], traced["step_wall_s"] = len(trace_steps), sum(trace_steps)
    t1 = time.time()
    if trace is not None and traced is None:
        raise RuntimeError(f"the window of {seconds}s ended before the trace's "
                           f"{job['trace']['steps']} steps had run")
    compiles_in_window = onchip.compile_count() - compiles_before
    losses = [float(x) for x in losses]
    device = onchip.device_facts()
    for tree in (params, opt_state):
        jax.tree.map(lambda x: x.delete(), tree)
    row = check(seed, make_weights(seed), put_batch(seed, 0), conf)
    train.report({
        "t0": t0, "t1": t1, "steps": len(step_s), "step_s": step_s, "losses": losses,
        "tokens_per_step": tokens_per_step, "compiles_in_window": compiles_in_window,
        "trace": traced, "device": device, "check": row,
        "seq_len": job["seq_len"], "sequences": sequences,
    })


def check(seed: int, weights: dict, batch: dict, conf: dict, control: str = "") -> dict:
    """The program's loss and gradients on the first sequences of the first
    batch, at the seed's weights, against the reference's. The number
    compared is the distance between the two gradients over the length of the
    reference's. With ``control`` the reference in that lower precision stands
    in the program's place too. The reference works on one chip, a layer at a
    time; the program's gradients come to it piece by piece."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.parallel.train_step import build_loss_fn

    from chipbench import reference as R
    from chipbench import weights as W

    dims, cfg, plan, mesh, _opt, job = _build(conf, True)
    n = job["check_sequences"]
    sub = {"tokens": batch["tokens"][:n]}
    like_weights = (None, jax.tree.map(lambda x: x.sharding, weights))
    loss, grads = jax.jit(jax.value_and_grad(build_loss_fn(cfg, plan, mesh)),
                          out_shardings=like_weights)(weights, sub)
    jax.tree.map(lambda x: x.delete(), weights)
    here = jax.devices()[0]
    tokens = jax.device_put(np.asarray(sub["tokens"]), here)
    key = W.seed_key(seed)

    @jax.jit
    def squares(a, b):
        return (sum(jnp.sum(jnp.square(a[k] - b[k])) for k in b),
                sum(jnp.sum(jnp.square(b[k])) for k in b))

    def distance(other, stream):
        """|other - reference| / |reference| over every piece of ``stream``;
        ``other(kind, i, names)`` gives the matching piece of the other side."""
        num = den = 0.0
        ref_loss = None
        for kind, i, piece in stream:
            if kind == "loss":
                ref_loss = float(piece)
                continue
            a, b = squares(other(kind, i, list(piece)), piece)
            num, den = num + float(a), den + float(b)
        return (num / den) ** 0.5, den ** 0.5, ref_loss

    def program_piece(kind, i, names):
        src = grads["layers"] if kind == "layer" else grads
        return {k: jax.device_put(src[k][i] if kind == "layer" else src[k], here) for k in names}

    err, ref_norm, ref_loss = distance(
        program_piece, R.stream_loss_and_grads(key, tokens, dims, jnp.float32))
    out = {"program": {"grad_rel_err": err, "loss_abs_err": abs(float(loss) - ref_loss)},
           "ref_loss": ref_loss, "ref_grad_norm": ref_norm, "sequences": n}
    jax.tree.map(lambda x: x.delete(), grads)
    if control:
        low = R.stream_loss_and_grads(key, tokens, dims, jnp.float32, control)
        low_loss = float(next(low)[2])
        err, _, _ = distance(lambda kind, i, names: next(low)[2],
                             R.stream_loss_and_grads(key, tokens, dims, jnp.float32))
        out["control"] = {"grad_rel_err": err, "loss_abs_err": abs(low_loss - ref_loss)}
    return out


def run(cell, args, t_start: float) -> dict:
    import ray_tpu
    from ray_tpu.core.cluster_utils import wait_cluster_processes_gone
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    conf = cell.config
    if args.rehearse:
        conf = {**conf, **conf["rehearsal"]}
    config = {"config": conf, "traffic": cell.traffic["params"], "seed": args.seed,
              "seconds": args.seconds, "trace": args.trace, "rehearse": args.rehearse,
              "keep_trace": args.keep_trace, "check_seeds": args.check_seeds,
              "control": args.control}
    resources = {"CPU": 1} if args.rehearse else {"CPU": 1, "TPU": cell.chips}
    ray_tpu.init()
    try:
        result = JaxTrainer(
            train_loop, train_loop_config=config,
            scaling_config=ScalingConfig(num_workers=1, use_tpu=not args.rehearse,
                                         resources_per_worker=resources),
            run_config=RunConfig(name="chipbench", storage_path=tempfile.mkdtemp(prefix="chipbench_")),
        ).fit()
    finally:
        ray_tpu.shutdown()
        wait_cluster_processes_gone(timeout_s=60)
    if result.error is not None:
        raise result.error
    if args.check_seeds:
        rows = [m for m in result.metrics_history if "check_seed" in m]
        for r in rows:
            print(f"[chipbench] check-seeds {json.dumps(r)}", flush=True)
        return {"check_seeds": rows}
    w = result.metrics
    losses, chk = w["losses"], w["check"]
    finite = all(np.isfinite(losses))
    limit = cell.limit("grad_rel_err_limit")
    number = chk["program"]["grad_rel_err"]
    print(f"[chipbench] correct: gradient's distance from the reference's over its length "
          f"{number:.6g} (limit {limit}) on {chk['sequences']} sequences; loss differs by "
          f"{chk['program']['loss_abs_err']:.3g} of {chk['ref_loss']:.6g} (not compared: "
          f"does not tell precisions apart)", flush=True)
    print(f"[chipbench] correct: loss {losses[0]:.5f} -> {losses[-1]:.5f} (last below first, "
          f"all finite: {finite}); compilations inside the window {w['compiles_in_window']} "
          f"(limit 0)", flush=True)
    correct = (number <= limit and finite and losses[-1] < losses[0]
               and w["compiles_in_window"] == 0)
    return {
        "correct": bool(correct), "attempted": w["steps"], "failed": 0, "device": w["device"],
        "facts": {"setup_s": w["t0"] - t_start, "seconds": args.seconds, "train": w,
                  "trace": w["trace"], "dims": conf, "chips": cell.chips},
    }
