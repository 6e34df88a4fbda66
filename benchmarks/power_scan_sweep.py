"""``power_chunk_scan`` alone on the chip, its two forms (PR 55, step 0).

The chunk scan of one power retention layer at the served shapes (8 tiles of 128
tokens, 8 states of 136 x 8,320, five queries a state), jitted, in a ``lax.scan``
over a flat pool of 8 layers x 16 slots (4.63 GB) as the chunk program runs it.
For each layout of a call's tiles: microseconds a tile a layer of the plain form
(``reference_power_chunk_scan``) and of the kernel (``_power_chunk_scan``), how
far apart their reads and their pools are, and the kernel again at ONE bfloat16
pass a product where the configuration says six: a timing that says how much of
the kernel the matrix unit's passes bound, never a result.

    chiprun -- python3 benchmarks/power_scan_sweep.py --out chiprun_out/power_scan.json

Layouts: one fresh prompt of 8 full tiles; the same 8 tiles carried from a stored
row; two segments, one carried and one fresh, each ending mid-tile (the served
cell's mean: a call holds a prompt's first 1,024 tokens or its last 40-940);
nobody at all.
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

from ray_tpu.models.hybrid_ssm import _segments
from ray_tpu.ops import power_retention as ops

LAYERS, SLOTS, H, G, D, C = 8, 16, 8, 5, 128, 128
REPS = 5  # calls timed a form a layout, behind one that compiles
SEED = 55

# A tile is (slot or None for nobody's, its first position, its real tokens).
LAYOUTS = {
    "fresh_8_tiles": [(3, 128 * t, 128) for t in range(8)],
    "carried_8_tiles": [(3, 1024 + 128 * t, 128) for t in range(8)],
    "carried_and_fresh_mid_tile": [(5, 1024, 128), (5, 1152, 128), (5, 1280, 100),
                                   (9, 0, 128), (9, 128, 128), (9, 256, 128), (9, 384, 128), (9, 512, 50)],
    "nobody": [(None, 0, 0)] * 8,
}


def tiles_of(spec):
    slot_of = jnp.asarray([SLOTS if s is None else s for s, _, _ in spec], jnp.int32)
    starts = jnp.asarray([a for _, a, _ in spec], jnp.int32)
    live = jnp.asarray([ln for _, _, ln in spec], jnp.int32)
    fresh, cont, last = _segments(starts[:, None], slot_of, SLOTS)
    return slot_of, fresh, cont, last, live


def program(form):
    """The scan of ``form`` over the layers, the pool the carry: layer ``i``'s
    rows are ``i * SLOTS + slot``."""
    def run(pool, slot_of, fresh, cont, last, live, log_g, q, k, v):
        def layer(pool, i):
            row = jnp.where(slot_of < SLOTS, i * SLOTS + slot_of, pool.shape[0])
            pool, y = form(pool, row, fresh, cont, last, live, log_g, q, k, v)
            return pool, y
        return jax.lax.scan(layer, pool, jnp.arange(LAYERS))
    return jax.jit(run, donate_argnums=(0,))


def timed(fn, pool, args):
    pool, y = fn(pool, *args)
    jax.block_until_ready(pool)
    first = (pool[SLOTS:2 * SLOTS], y[1])  # the second layer's rows and reads, after ONE call
    t0 = time.perf_counter()
    for _ in range(REPS):
        pool, y = fn(pool, *args)
    jax.block_until_ready(pool)
    return (time.perf_counter() - t0) / REPS, first


def apart(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.maximum(jnp.max(jnp.abs(b)), 1e-30))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/power_scan.json")
    args = ap.parse_args(argv)
    assert jax.default_backend() == "tpu", jax.default_backend()
    ks = jax.random.split(jax.random.PRNGKey(SEED), 5)
    V, P = ops.values_rows(D), ops.phi_width(D)

    # A pool a past of a few tokens leaves, made a layer at a time (phi of all rows at once is 4.6 GB more).
    @jax.jit
    def past(key):
        k, v = (jax.random.normal(kk, (SLOTS, H, 6, D)) for kk in jax.random.split(key))
        return jnp.einsum("rhtv,rhtp->rhvp", ops.with_one(v), ops.expand(k), precision="highest")

    def fresh_pool():
        return jnp.concatenate([past(jax.random.fold_in(ks[0], i)) for i in range(LAYERS)])

    n = 8
    log_g = jnp.log1p(-jnp.exp(jax.random.uniform(ks[1], (n, C, H), jnp.float32, np.log(5e-4), np.log(0.1))))
    k, v = jax.random.normal(ks[2], (n, C, H, D)), jax.random.normal(ks[3], (n, C, H, D))
    q = jax.random.normal(ks[4], (n, C, H, G, D))
    assert ops._scan_tiles(jax.ShapeDtypeStruct((LAYERS * SLOTS, H, V, P), jnp.float32), q)
    rows = []
    for name, spec in LAYOUTS.items():
        operands = tiles_of(spec) + (log_g, q, k, v)
        row = {"layout": name, "tiles": n, "layers": LAYERS, "group": ops._SCAN_GROUP}
        s, want = timed(program(ops.reference_power_chunk_scan), fresh_pool(), operands)
        row["plain_us_tile_layer"] = 1e6 * s / (n * LAYERS)
        s, got = timed(program(ops._power_chunk_scan), fresh_pool(), operands)
        row.update(kernel_us_tile_layer=1e6 * s / (n * LAYERS),
                   pool_apart=apart(got[0], want[0]), y_apart=apart(got[1], want[1]))
        # NOT a result: the same kernel with every product at ONE bfloat16 pass where the configuration
        # says six. What is left of the time is what the matrix unit's passes do not bound.
        exact, ops._EXACT = ops._EXACT, jax.lax.Precision.DEFAULT
        jax.clear_caches()  # the kernel's wrapper is a jit of its own, traced at the module's precision
        try:
            s, _ = timed(program(ops._power_chunk_scan), fresh_pool(), operands)
        finally:
            ops._EXACT = exact
            jax.clear_caches()
        row["kernel_one_pass_us_tile_layer"] = 1e6 * s / (n * LAYERS)
        rows.append(row)
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
