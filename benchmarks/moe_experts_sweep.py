"""``routed_experts`` alone on the chip, its three forms (PR 51 and PR 52, step 0).

The layer's held experts at two published widths, jitted, in a ``lax.scan``
over the stack's layers as the served programs run it, over the call's token
count ``T``. For each: microseconds a layer of the sorted ``ragged_dot`` form,
of ``moe_decode_experts`` and of the sorted form through ``moe_grouped_experts``,
the touched experts, and the touched weights' bytes over the time against the
memory's peak. It says where the forms cross (``ops/moe.RIDGE_TOKENS``).

The sorted form is also timed in its parts, each jitted alone on one layer's
routing (PR 52): the router, keys and sizes; the sort and the gather of the
pairs' tokens; the products (three ``ragged_dot``, the stock megablox ``gmm``
three times at a few tilings, ``moe_grouped_experts`` at a few row tiles); the
way back to (token, choice) order and the weighted sum.

    chiprun -- python3 benchmarks/moe_experts_sweep.py --out chiprun_out/moe_sweep.json

Routing is ``latent_moe.route`` over a random router on normal inputs: 8 of 512
in 4 of 8 groups with experts 0-127 held (Ling-3.0-flash on one chip of four),
8 of 256 with 0-15 held (openPangu-Ultra-MoE on one of sixteen): ~128 pairs
over 128 experts at 64 tokens, as the served cells count them.
"""
from __future__ import annotations

import argparse
import functools
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


def program(cfg, form: str):
    """``routed_experts`` over the stack's layers in the form named: "ragged",
    "decode" or "grouped"."""
    def run(y, held, routers):
        # The choice is static (a shape's); the sweep forces each side.
        was = moe.fused, moe.grouped
        moe.fused, moe.grouped = (lambda T, held: form == "decode"), (lambda T, held: form == "grouped")
        try:
            def layer(carry, at):
                i, router = at
                m, counts = lm.routed_experts(y, {"router": router}, cfg, held, i)
                return carry + m.astype(jnp.float32), counts
            total, counts = jax.lax.scan(
                layer, jnp.zeros(y.shape, jnp.float32), (jnp.arange(routers.shape[0]), routers))
        finally:
            moe.fused, moe.grouped = was
        return total, counts
    return jax.jit(run)


GMM_TILINGS = {  # (rows, in, out) of the stock grouped product, inside its 16 MB of VMEM
    "ling": [(128, 2560, 768), (128, 1280, 768), (256, 1280, 768)],
    "pangu": [(128, 1920, 1024), (128, 1536, 512), (256, 1920, 512)],
}


def gmm_products(tiling):
    """The three grouped products by ``megablox.gmm``: the stack as ``layers x
    held`` groups, of which only the layer's hold rows, as ``ragged_dot`` is
    handed it."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    def products(x, sizes, held, layer):
        n_layers, E = held["e_gate"].shape[:2]
        groups = jax.lax.dynamic_update_slice(jnp.zeros((n_layers * E,), jnp.int32), sizes, (layer * E,))

        def dot(a, w):
            tm, tk, tn = tiling
            w = w.reshape((-1,) + w.shape[2:])
            return gmm(a, w, groups, preferred_element_type=a.dtype,
                       tiling=(tm, min(tk, w.shape[1]), min(tn, w.shape[2])))

        return dot(jax.nn.silu(dot(x, held["e_gate"])) * dot(x, held["e_up"]), held["e_down"])
    return products


def parts(name, cfg, y, held, router, layer=1):
    """The sorted form's parts, each a jitted function of its own on layer
    ``layer``'s routing: {part: microseconds}, and the products' distance from
    the ``ragged_dot`` form's."""
    k, E = cfg.num_experts_per_tok, cfg.held
    T = y.shape[0]

    def keys(y, router):
        experts, gates = lm.route(y, {"router": router}, cfg)
        local = experts - cfg.held_first
        here = (local >= 0) & (local < E)
        key = jnp.where(here, local, E)
        sizes = jnp.sum(key[..., None] == jnp.arange(E), axis=(0, 1)).astype(jnp.int32)
        return key, jnp.where(here, gates, 0.0), sizes

    def sort_gather(y, key):
        order = jnp.argsort(key.reshape(T * k), stable=True)
        return order, y[order // k]

    def way_back(out, order, weight):
        out, weight = out[jnp.argsort(order)].reshape(T, k, -1), weight[..., None]
        return jnp.sum(jnp.where(weight != 0, out.astype(jnp.float32) * weight, 0), axis=1).astype(out.dtype)

    us = {}
    s, (key, weight, sizes) = timed(jax.jit(keys), (y, router))
    us["route_keys_sizes"] = 1e6 * s
    s, (order, x) = timed(jax.jit(sort_gather), (y, key))
    us["sort_gather"] = 1e6 * s
    at = jnp.int32(layer)
    forms = {"ragged": lm._ragged_products}
    forms.update({"gmm_%dx%dx%d" % t: gmm_products(t) for t in GMM_TILINGS[name]})
    forms.update({"grouped_rows%d" % r: functools.partial(moe.moe_grouped_experts, row_tile=r) for r in (64, 128, 256)})
    want, apart = None, {}
    for form, fn in forms.items():
        try:
            s, out = timed(jax.jit(fn), (x, sizes, held, at))
        except Exception as e:  # a tiling the compiler refuses
            us["products_" + form] = f"{type(e).__name__}: {str(e)[:160]}"
            continue
        us["products_" + form] = 1e6 * s
        out = jnp.where((jnp.arange(x.shape[0]) < jnp.sum(sizes))[:, None], out.astype(jnp.float32), 0)
        if want is None:
            want, first = out, out.astype(x.dtype)
        else:
            apart[form] = float(jnp.max(jnp.abs(out - want)) / jnp.max(jnp.abs(want)))
    s, _ = timed(jax.jit(way_back), (first, order, weight))
    us["way_back"] = 1e6 * s
    return us, apart


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
    ap.add_argument("--parts-from", type=int, default=64, help="time the sorted form's parts from this T up")
    args = ap.parse_args(argv)
    assert jax.default_backend() == "tpu", jax.default_backend()
    rows = []
    for name in args.widths.split(","):
        cfg, layers = WIDTHS[name]["cfg"], WIDTHS[name]["layers"]
        held, routers = build(cfg, layers, jax.random.PRNGKey(args.seed))
        matrix = cfg.hidden_size * cfg.moe_intermediate_size * 2
        plain, kernel, grouped = (program(cfg, form) for form in ("ragged", "decode", "grouped"))
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
            s_grouped, (got, counts_grouped) = timed(grouped, (y, held, routers))
            row.update(grouped_us_layer=1e6 * s_grouped / layers,
                       grouped_apart=float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))),
                       grouped_counts=np.asarray(counts_grouped)[0].tolist())
            if T >= args.parts_from:
                row["parts_us"], row["products_apart"] = parts(name, cfg, y, held, routers[1])
            floor_us = 1e6 * 3 * touched * matrix / HBM_BYTES_PER_S
            if "kernel_alone_us" in row:
                row["kernel_alone_pct_of_hbm"] = (
                    100 * 1e6 * 3 * row["touched_layer0"] * matrix / HBM_BYTES_PER_S / row["kernel_alone_us"])
            row.update(bytes_floor_us=floor_us,
                       plain_pct_of_hbm=100 * floor_us / row["plain_us_layer"],
                       kernel_pct_of_hbm=100 * floor_us / row.get("kernel_us_layer", float("inf")),
                       grouped_pct_of_hbm=100 * floor_us / row["grouped_us_layer"])
            rows.append(row)
            print(json.dumps(row), flush=True)
        del held, routers
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
