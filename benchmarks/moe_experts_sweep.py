"""``routed_experts`` alone on the chip, grouped form against kernel (PR 51, step 0).

The layer's held experts at two published widths, jitted, in a ``lax.scan``
over the stack's layers as the served programs run it, over the call's token
count ``T``. For each: microseconds a layer of the sorted ``ragged_dot`` form
and of ``moe_decode_experts``, the touched experts, and the touched weights'
bytes over the time against the memory's peak. It says where the two forms
cross (``ops/moe.RIDGE_TOKENS``).

    chiprun -- python3 benchmarks/moe_experts_sweep.py --out chiprun_out/moe_sweep.json

Routing is ``latent_moe.route`` over a random router on normal inputs: 8 of 512
in 4 of 8 groups with experts 0-127 held (Ling-3.0-flash on one chip of four),
8 of 256 with 0-15 held (openPangu-Ultra-MoE on one of sixteen): ~128 pairs
over 128 experts at 64 tokens, as the served cells count them.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # run from a checkout

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import latent_moe as lm
from ray_tpu.ops import moe

HBM_BYTES_PER_S = 819e9  # chipbench/peaks.py: TPU v5e

WIDTHS = {
    "ling": dict(layers=3, cfg=lm.LatentMoEConfig(
        hidden_size=2560, moe_intermediate_size=768, n_routed_experts=512, num_experts_per_tok=8,
        n_group=8, topk_group=4, held_first=0, held_count=128)),
    "pangu": dict(layers=2, cfg=lm.LatentMoEConfig(
        hidden_size=7680, moe_intermediate_size=2048, n_routed_experts=256, num_experts_per_tok=8,
        held_first=0, held_count=16)),
}


def build(cfg, layers, key):
    D, F, E = cfg.hidden_size, cfg.moe_intermediate_size, cfg.held
    ks = jax.random.split(key, 4)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.bfloat16) * fan_in ** -0.5).astype(jnp.bfloat16)

    make = jax.jit(lambda: {
        "e_gate": normal(ks[0], (layers, E, D, F), D), "e_up": normal(ks[1], (layers, E, D, F), D),
        "e_down": normal(ks[2], (layers, E, F, D), F)})
    held = make()
    routers = normal(ks[3], (layers, D, cfg.n_routed_experts), D)  # logits of unit variance
    return held, routers


def program(cfg, kernel: bool):
    def run(y, held, routers):
        # The choice is static (a shape's); the sweep forces each side.
        was = moe.fused
        moe.fused = lambda T, held: kernel
        try:
            def layer(carry, at):
                i, router = at
                m, counts = lm.routed_experts(y, {"router": router}, cfg, held, i)
                return carry + m.astype(jnp.float32), counts
            total, counts = jax.lax.scan(
                layer, jnp.zeros(y.shape, jnp.float32), (jnp.arange(routers.shape[0]), routers))
        finally:
            moe.fused = was
        return total, counts
    return jax.jit(run)


def kernel_alone(cfg, y, held, routers):
    """The kernel's own call for layer 0's routing, without the router, the
    counts and the gates around it: (jitted function, its arguments)."""
    def inputs(y, router):
        experts, gates = lm.route(y, {"router": router}, cfg)
        chose = jnp.where(experts < cfg.held, experts, cfg.held)[..., None] == jnp.arange(cfg.held)
        return (jnp.sum(jnp.where(chose, gates[..., None], 0.0), axis=1),
                jnp.sum(chose, axis=(0, 1)).astype(jnp.int32))
    w, sizes = jax.jit(inputs)(y, routers[0])
    return jax.jit(lambda y, w, sizes, held: moe.moe_decode_experts(y, w, sizes, held, 0)), (y, w, sizes, held)


def timed(fn, args, reps=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps, out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/moe_sweep.json")
    ap.add_argument("--tokens", default="16,32,64,128,256,512,1024")
    ap.add_argument("--widths", default="ling,pangu")
    ap.add_argument("--seed", type=int, default=51)
    args = ap.parse_args(argv)
    assert jax.default_backend() == "tpu", jax.default_backend()
    rows = []
    for name in args.widths.split(","):
        cfg, layers = WIDTHS[name]["cfg"], WIDTHS[name]["layers"]
        held, routers = build(cfg, layers, jax.random.PRNGKey(args.seed))
        matrix = cfg.hidden_size * cfg.moe_intermediate_size * 2
        plain, kernel = program(cfg, False), program(cfg, True)
        for T in (int(t) for t in args.tokens.split(",")):
            y = jax.random.normal(jax.random.PRNGKey(T), (T, cfg.hidden_size), jnp.bfloat16)
            row = {"widths": name, "T": T}
            s_plain, (want, counts) = timed(plain, (y, held, routers))
            touched = float(np.asarray(counts)[:, 1].mean())
            row.update(plain_us_layer=1e6 * s_plain / layers, touched=touched,
                       pairs=float(np.asarray(counts)[:, 0].mean()))
            try:
                s_kernel, (got, _) = timed(kernel, (y, held, routers))
                apart = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
                s_alone, _ = timed(*kernel_alone(cfg, y, held, routers))
                row.update(kernel_us_layer=1e6 * s_kernel / layers, apart=apart,
                           kernel_alone_us=1e6 * s_alone,
                           touched_layer0=float(np.asarray(counts)[0, 1]))
            except Exception as e:  # a T the kernel's VMEM cannot hold
                row.update(kernel_error=f"{type(e).__name__}: {str(e)[:200]}")
            floor_us = 1e6 * 3 * touched * matrix / HBM_BYTES_PER_S
            if "kernel_alone_us" in row:
                row["kernel_alone_pct_of_hbm"] = (
                    100 * 1e6 * 3 * row["touched_layer0"] * matrix / HBM_BYTES_PER_S / row["kernel_alone_us"])
            row.update(bytes_floor_us=floor_us,
                       plain_pct_of_hbm=100 * floor_us / row["plain_us_layer"],
                       kernel_pct_of_hbm=100 * floor_us / row.get("kernel_us_layer", float("inf")))
            rows.append(row)
            print(json.dumps(row), flush=True)
        del held, routers
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
