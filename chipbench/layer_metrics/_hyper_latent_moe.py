"""What the readers of the multi-stream decoder's metrics share: the engine's counter of
token places mixed, and the mixes' parts in the device trace.

The maps and the two mixes are plain XLA operations with no name of their own
(``fusion.N``, ``convolution.N``, ``copy.N``, ``while.N``; ``read_xplane`` cannot read
their scopes ``hc.maps`` / ``hc.in`` / ``hc.out``), so each is found by the TYPES of what it
writes, which the configuration fixes (``types``): with ``n`` streams of ``C`` numbers,
``k = 2n + n^2`` map logits and ``q`` the token axes of a program (``b,1`` in the decode
program, ``1,T`` in a chunk call ``T`` wide; ``Q`` the same as one axis)

- maps: the normed streams ``f32[Q,n,C]``, ``phi`` laid out for its product ``f32[n,C,k]``,
  the logits ``f32[q,k]`` / ``f32[Q,k,1]``, the ``n^2`` of ``Hres`` ``f32[q,n^2]``, one entry
  of it a token ``f32[q]`` (the Sinkhorn rounds' whole state: nothing else in either
  program writes that type; their ``while`` counts by its OWN time, the steps' turnaround),
  the maps ``f32[q,n,n]`` and ``f32[q,1,n]``;
- out: the streams ``bf16[q,n,C]`` (whatever the configuration's type is called),
  ``Hpost`` spread for them ``f32[q,n,1]`` and the sublayer's output in float32 ``f32[q,1,C]``.

``hc.in`` is NOT found: the weighted sum of the streams is fused into the norm that reads
it (two operations that write ``f32[Q]`` and ``[q,C]``, types that every norm writes), so
its reads of ``X`` count as that norm's and the share is a floor. Read from the programs
compiled for ``v5e:2x2`` (PR 61; PERF.md section 7). A compiler that fuses otherwise moves
time between the parts and what is around them.
"""
from chipbench.layer_metrics._sparse_latent_moe import _OUTPUT, CONTAINERS
from chipbench.layer_metrics.chunk_call_ms import PROGRAM as CHUNK
from chipbench.layer_metrics.decode_step_ms import PROGRAM as DECODE
from chipbench.trace_reduce import seconds_matching

_DTYPES = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}


def is_mine(facts: dict) -> bool:
    """The configuration is this family's (a cell of another has no such key)."""
    return "hc_mult" in facts["dims"]


def places_mixed(facts: dict):
    """Token places x sublayers mixed over the window, or None where the program keeps
    no such counter or counted nothing."""
    return facts["engine"]["stats"].get("hc_places_mixed") or None


def calls_traced(facts: dict) -> float:
    """Program calls that mix (decode STEPS and chunk calls) in the traced window."""
    _, windows = seconds_matching(facts["trace"]["modules"], DECODE)
    _, chunks = seconds_matching(facts["trace"]["modules"], CHUNK)
    return windows * facts["engine"]["decode_window"] + chunks


def types(facts: dict) -> list:
    """[(part, what its operations write, as the trace spells a type: ``f32[32,1,24]``,
    whether an operation that holds others may be the part), ..] in the order an
    operation is tried against them."""
    from ray_tpu.serve.llm_engine import _chunk_ladder

    d, p = facts["dims"], facts["dims"]["paged"]
    n, C, b = int(d["hc_mult"]), int(d["hidden_size"]), p["max_batch"]
    k, stream = 2 * n + n * n, _DTYPES[d["dtype"]]
    widths = _chunk_ladder(int(d["engine"]["prefill_chunk"]), p["block_size"])
    axes = [(f"{b},1", f"{b}")] + [(f"1,{T}", f"{T}") for T in widths]
    loop = [f"f32[{q}]" for q, _ in axes]
    maps = [t for q, Q in axes for t in (
        f"f32[{Q},{n},{C}]", f"f32[{q},{n},{C}]", f"f32[{q},{k}]", f"f32[{Q},{k},1]", f"f32[{q},{n * n}]",
        f"f32[{q},{n},{n}]", f"f32[{q},1,{n}]")] + [f"f32[{n},{C},{k}]"]
    out = [t for q, _ in axes for t in (f"{stream}[{q},{n},{C}]", f"f32[{q},{n},1]", f"f32[{q},1,{C}]")]
    return [("maps", loop, True), ("maps", maps, False), ("out", out, False)]


def part_seconds(facts: dict) -> dict:
    """part -> the own seconds, in the traced window, of the operations that write one of
    the part's types, in the decode program and the chunk calls together. {} with no
    trace, or for another family's configuration."""
    if not facts.get("trace") or not is_mine(facts):
        return {}
    rules, out = types(facts), {}
    for row in facts["trace"]["ops"].values():
        found = _OUTPUT.match(row.get("detail", ""))
        if not found:
            continue
        written, holds_others = found.group(1), found.group(2) in CONTAINERS
        for part, wanted, containers_too in rules:
            if (containers_too or not holds_others) and any(x in written for x in wanted):
                out[part] = out.get(part, 0.0) + row["self_seconds"]
                break
    return out
