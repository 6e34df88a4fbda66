"""The four parts of a selecting full layer's attention alone on the chip (PR 59),
at the served widths: one decode step's (32 slots, a table of 544 blocks of 64,
64 index heads of 128, 2,048 of up to 34,816 cached tokens, 128 heads on rows of
640) and one group of a chunk call's (64 queries of one slot).

For each part microseconds a call, jitted alone, and what its bytes would take at
the memory's peak: the index score, the selection (``sparse.select``: one sort that
carries each key's place in the pool), the gather of the chosen rows, the attention
over them, and the four together; the sliding layers' window read beside them.
Beside the selection as served, the forms it was chosen among (PR 60):
``jax.lax.top_k`` alone (PR 59's, ``select_top_k_us``), the lookup of its positions
in the table that PR 59 needed after it (``lookup_us``, and PR 59's gather with it
inside, ``gather_through_table_us``), and the stable sort with the place as a
payload (``select_stable_us``: what ``select`` falls back to where position and
block do not fit one 31-bit key).

    chiprun -- python3 benchmarks/sparse_parts_sweep.py --out chiprun_out/sparse_parts.json
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

from ray_tpu.ops import sparse_latent_attention as sparse

HBM_BYTES_PER_S = 819e9  # chipbench/peaks.py: TPU v5e
B, W, BS, HI, DI, H, R, RANK, TOPK = 32, 544, 64, 64, 128, 128, 640, 512, 2048
BLOCKS = 2 * 6145  # the two full layers' pools, flat (as the issue sized them; the cell's hold 4,865)


def timed(fn, *args, reps: int = 20) -> float:
    """Microseconds a call, after one call that compiles."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--out", default="")
    p.add_argument("--context", type=int, default=33300, help="cached tokens a slot")
    args = p.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("sparse_parts_sweep: a measurement needs the chip", file=sys.stderr)
        return 2
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)
    rows = jax.random.normal(ks[0], (BLOCKS, BS, R), jnp.bfloat16)
    keys = jax.random.normal(ks[1], (BLOCKS, BS, DI), jnp.bfloat16)
    tables = jnp.asarray(np.random.default_rng(0).permutation(np.arange(1, 6145))[:B * 170]
                         .reshape(B, 170).repeat(4, axis=1)[:, :W].astype(np.int32))
    lens = jnp.full((B,), args.context, jnp.int32)
    q = jax.random.normal(ks[2], (B, H, R), jnp.bfloat16)
    qi = jax.random.normal(ks[3], (B, HI, DI), jnp.bfloat16)
    w = jax.random.normal(ks[4], (B, HI), jnp.float32)
    window_pool = jax.random.normal(ks[5], (6145, BS, 1152), jnp.bfloat16)
    qw = jax.random.normal(ks[6], (B, 64, 1152), jnp.bfloat16)

    # The pools are ARGUMENTS of every jitted part: closed over, each would be
    # a constant of its executable (1.0 GB of rows: the host ran out of memory).
    score = jax.jit(lambda keys: sparse.index_scores(qi, w, keys, tables, lens))
    scores = score(keys)
    select = jax.jit(lambda s, t: sparse.select(s, t, BS, BLOCKS, TOPK))
    stable = jax.jit(lambda s, t: sparse.select(s, t, BS, 2 ** 27, TOPK))  # too many blocks to pack
    top_k = jax.jit(lambda s: jax.lax.top_k(s, TOPK)[1])

    def through_table(t, pos):  # PR 59: positions -> (block, offset) by a gather from the table
        t = jnp.broadcast_to(t, pos.shape[:1] + t.shape[-1:])
        return jnp.take_along_axis(t, pos // BS, axis=1), pos % BS

    def place(t, pos):
        block, offset = through_table(t, pos)
        return block * BS + offset

    lookup = jax.jit(place)
    gather_through_table = jax.jit(lambda rows, pos: rows[through_table(tables, pos)])
    ids, valid = select(scores, tables)
    pos = top_k(scores)
    assert bool(jnp.array_equal(ids, lookup(tables, pos))) and bool(jnp.array_equal(ids, stable(scores, tables)[0]))
    gather = jax.jit(sparse.gather_rows)
    got = gather(rows, ids)
    attend = jax.jit(lambda got, valid: sparse.attend_rows(q, got, valid, 192 ** -0.5, RANK))
    whole = jax.jit(lambda rows, keys: sparse.sparse_attention(q, qi, w, rows, keys, tables, tables, lens,
                                                               192 ** -0.5, RANK, TOPK))
    window = jax.jit(lambda pool: sparse.window_attention(qw, pool, tables, lens, 256 ** -0.5, 1024, 513))
    group = jax.random.normal(ks[7], (64, W * BS), jnp.float32)  # a chunk group: 64 lists, ONE table
    out = {
        "context": args.context, "device": jax.devices()[0].device_kind,
        "score_us": timed(score, keys), "select_us": timed(select, scores, tables),
        "gather_us": timed(gather, rows, ids), "attend_us": timed(attend, got, valid),
        "whole_us": timed(whole, rows, keys), "window_us": timed(window, window_pool),
        "chunk_group_select_us": timed(select, group, tables[0]),
        "select_top_k_us": timed(top_k, scores), "chunk_group_select_top_k_us": timed(top_k, group),
        "lookup_us": timed(lookup, tables, pos), "chunk_group_lookup_us": timed(lookup, tables[0], top_k(group)),
        "gather_through_table_us": timed(gather_through_table, rows, pos),
        "select_stable_us": timed(stable, scores, tables),
        "chunk_group_select_stable_us": timed(stable, group, tables[0]),
        "score_least_us": B * args.context * DI * 2 / HBM_BYTES_PER_S * 1e6,
        "gather_least_us": B * TOPK * R * 2 / HBM_BYTES_PER_S * 1e6,
    }
    print("SPARSE_PARTS " + json.dumps(out), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
