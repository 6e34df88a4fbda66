"""What the readers of the selecting decoder's metrics share: the engine's counters of
cached tokens live and selected, and the selection's parts in the device trace.

The four parts of a full layer's attention are plain XLA operations with no name of
their own but the sort's (``sort.N``; the others are ``fusion.N``, and ``read_xplane``
cannot read their scopes yet), so each is found by the TYPES of what it writes, which
the configuration fixes (``shapes``): with ``b`` slots, ``m`` positions a table
(``max_blocks_per_seq x block_size``), ``k = index_topk`` and rows of ``r`` numbers, in
the decode program

- select: the sort's ``(f32[b,m], s32[b,m])`` and whatever writes ``[b,k]``;
- gather: the rows ``bf16[b*k,r]`` and the block ids ``s32[b*k]`` the positions become;
- attend: the attention's scores ``f32[b,H,k]`` and sums ``[b,H,rank]``;
- score: the gathered index keys ``bf16[b,m,Di]``, the products ``f32[b,Hi,m]`` and
  the scores ``f32[b,m]`` (tried last: the sort writes the scores again);

and in a chunk call the same with a group's 64 queries where the slots stood, the score
a step of 512 keys at a time (``f32[256,512]``). Operations that hold others (``while``,
``conditional``, ``call``) are no part: only their own time counts anywhere. Read from
the traced run of PR 59 (``chiprun_out/new/facts_traced.json``, seed 3000000021: ``sort``
/ ``sort.1`` 188 ms each over 85 groups, ``fusion.5`` / ``.7`` ``bf16[131072,640]`` 187 ms,
``fusion.4`` / ``.6`` ``s32[131072]`` 64 ms, ``fusion.848`` / ``.852`` ``f32[256,512]`` 27 ms,
``sort.111`` and ``fusion.196`` ``bf16[65536,640]`` 13.4 ms a 12 windows in the decode
program; PERF.md section 5). A compiler that fuses otherwise moves time between parts.
The two tile sizes are the program's own (``ops/sparse_latent_attention._KV_ROWS`` and
``._QUERIES_PER_STEP``), read from it.
"""
import math
import re

from chipbench.layer_metrics import _latent_moe as L
from chipbench.layer_metrics.decode_step_ms import PROGRAM
from chipbench.trace_reduce import seconds_matching

CONTAINERS = ("while", "conditional", "call")
_OUTPUT = re.compile(r"^(.*?) ([a-z][\w\-]*)\(")


def counters(facts: dict):
    """(cached tokens the full layers' queries could have read, cached tokens they
    selected) over the window, or None where the program keeps no such counters or
    counted nothing."""
    s = facts["engine"]["stats"]
    if not s.get("sparse_keys_live"):
        return None
    return s["sparse_keys_live"], s["sparse_keys_selected"]


def is_mine(facts: dict) -> bool:
    """The configuration is this family's (a cell of another has no such key)."""
    return "index_topk" in facts["dims"]


def shapes(facts: dict) -> dict:
    """program -> [(part, what its operations write, as the trace spells a type:
    ``f32[32,34816]``), ..] in the order an operation is tried against them: the
    first part one of whose types stands among the operation's outputs takes it
    (the sort writes the scores again beside their positions: it is tried first)."""
    from ray_tpu.ops.sparse_latent_attention import _KV_ROWS, _QUERIES_PER_STEP

    d, p = facts["dims"], facts["dims"]["paged"]
    b, W, bs = p["max_batch"], p["max_blocks_per_seq"], p["block_size"]
    m, k = W * bs, min(int(d["index_topk"]), W * bs)
    H, Hi, Di = int(d["num_attention_heads"]), int(d["index_n_heads"]), int(d["index_head_dim"])
    rank = int(d["kv_lora_rank"])
    r = -(-(rank + int(d["qk_rope_head_dim"])) // 128) * 128
    per = max(1, min(_KV_ROWS // bs, W))  # blocks a step of a chunk call's scores
    kv, mp = per * bs, -(-W // per) * per * bs
    tile = min(4 * bs, int(d["engine"]["prefill_chunk"]))  # ``paged.chunk_tile`` of the ladder's widths
    g = math.gcd(tile, _QUERIES_PER_STEP)  # queries a group of a chunk call's selection

    def t(dtype, *dims_):
        return dtype + "[" + ",".join(str(x) for x in dims_) + "]"

    def program(q, keys):  # q: the queries a selection holds; keys: the positions it is over
        return [
            ("select", [t("s32", q, keys), t("s32", q, k), t("f32", q, k), t("pred", q, k), t("s32", q, k, 2)]),
            # the rows, and the block ids the positions become through the table (a gather of its own)
            ("gather", [t("bf16", q, k, r), t("bf16", q * k, r), t("s32", q * k)]),
            # (not the softmax's ``[q,H]`` sums: the gate a head writes that type too)
            ("attend", [t("f32", q, H, k), t("bf16", q, H, k), t("f32", q, H, rank), t("bf16", q, H, rank),
                        t("bf16", 1, q, H, rank)]),
        ]

    return {
        "decode": program(b, m) + [("score", [
            t("bf16", b, m, Di), t("bf16", b, W, bs, Di), t("bf16", b * W, bs, Di), t("f32", b, Hi, m),
            t("f32", b, 1, Hi, m), t("f32", b, m), t("f32", b, 1, m)])],
        "chunk": program(g, mp) + [("score", [
            t("bf16", kv, Di), t("bf16", per, bs, Di), t("f32", tile, Hi, kv), t("f32", tile, kv),
            t("f32", tile, mp)])],
    }


def part_seconds(facts: dict, program: str = "") -> dict:
    """part -> the own seconds, in the traced window, of the operations that write one
    of the part's types: of the decode program, of a chunk call, or (``program`` "")
    of both. {} with no trace, or for another family's configuration."""
    if not facts.get("trace") or not is_mine(facts):
        return {}
    wanted = shapes(facts)
    rules = [rule for which in ([program] if program else list(wanted)) for rule in wanted[which]]
    out = {}
    for row in facts["trace"]["ops"].values():
        found = _OUTPUT.match(row.get("detail", ""))
        if not found or found.group(2) in CONTAINERS:
            continue
        written = found.group(1)
        for part, types in rules:
            if any(x in written for x in types):
                out[part] = out.get(part, 0.0) + row["self_seconds"]
                break
    return out


def decode_part(facts: dict, part: str):
    """(own seconds of ``part`` a full layer a decode step of the traced window, mean
    occupied slots, cached tokens over them, the configuration's ``Dims``), or None where
    the trace holds no such part or nothing finished in the window. Cached tokens as
    ``_latent_moe.cached_tokens`` has them; the traced steps are the decode program's
    runs times the window."""
    from chipbench import work_sparse_latent_moe as work
    from chipbench.weights_sparse_latent_moe import Dims

    seconds = part_seconds(facts, "decode").get(part)
    if not seconds:
        return None
    cached = L.cached_tokens(facts)
    _, runs = seconds_matching(facts["trace"]["modules"], PROGRAM)
    if cached is None or not runs:
        return None
    dims = Dims.from_config(facts["dims"])
    steps = runs * facts["engine"]["decode_window"] * work.layers_of(dims, work.FULL)
    return seconds / steps, L.mean_active(facts), cached, dims
