"""Operations and bytes the decoder with selecting and sliding latent attention
needs, from its shapes.

What the algorithm needs, never what a program happens to move (an index key is
its 128 numbers of the tokens really cached, whatever the table's width; a
latent row is its 576 numbers, whatever the pool pads them to; an expert nobody
routed to is not read). ``dims`` is ``weights_sparse_latent_moe.Dims`` (or
anything with its fields).
"""
from __future__ import annotations

FULL, SLIDING = "full_attention", "sliding_attention"


def layers_of(dims, kind: str) -> int:
    return list(dims.layer_types).count(kind)


def mixer_params(dims, kind: str) -> int:
    """A layer's attention matrices: W_dq, W_uq, W_dkv, W_ukv, the gate, W_o,
    and in a full layer the indexer's W_iq, W_ik, W_iw."""
    d, a = dims.hidden, dims.attn(kind)
    n = (d * a.q_rank + a.q_rank * a.heads * (a.nope + a.rope) + d * (a.kv_rank + a.rope)
         + a.kv_rank * a.heads * (a.nope + a.v_dim) + d * a.heads + a.heads * a.v_dim * d)
    if kind == FULL:
        n += a.q_rank * dims.index_heads * dims.index_dim + d * dims.index_dim + d * dims.index_heads
    return n


def dense_ffn_params(dims) -> int:
    return 3 * dims.hidden * dims.ffn


def expert_params(dims) -> int:
    """One routed expert (the shared expert is ``dims.shared`` of them)."""
    return 3 * dims.hidden * dims.expert_ffn


def expert_layers(dims) -> int:
    return dims.layers - dims.lead


def fixed_matmul_params(dims) -> int:
    """Parameters every token multiplies: every mixer, the leading layers'
    feed-forward, each expert layer's router and shared expert, the head."""
    mixers = sum(mixer_params(dims, kind) for kind in dims.layer_types)
    return (mixers + dims.lead * dense_ffn_params(dims)
            + expert_layers(dims) * (dims.hidden * dims.experts + dims.shared * expert_params(dims))
            + dims.hidden * dims.vocab)


def stored_params(dims) -> int:
    """Every parameter this share holds: embedding, norms, biases and held experts too."""
    norms = sum(2 * dims.hidden + dims.attn(kind).q_rank + dims.attn(kind).kv_rank
                + (dims.index_dim if kind == FULL else 0) for kind in dims.layer_types) + dims.hidden
    return (fixed_matmul_params(dims) + expert_layers(dims) * (dims.held * expert_params(dims) + dims.experts)
            + dims.vocab * dims.hidden + norms)


def row_numbers(dims, kind: str) -> int:
    a = dims.attn(kind)
    return a.kv_rank + a.rope


def score_work(dims, queries: float, cached_tokens: float, itemsize: int = 2) -> tuple:
    """(operations, bytes) of the index score of ONE full layer: every index
    head's product with every cached key a query may read (``cached_tokens``
    summed over the queries), and each cached key read once a SLOT (a decode
    step: one query a slot, so ``cached_tokens`` keys), with the queries and
    their head weights."""
    flops = 2.0 * dims.index_heads * dims.index_dim * cached_tokens
    bytes_ = (cached_tokens * dims.index_dim * itemsize
              + queries * dims.index_heads * (dims.index_dim * itemsize + 4))
    return flops, bytes_


def gather_bytes(dims, selected: float, itemsize: int = 2) -> float:
    """Bytes of the selected latent rows of ONE full layer, each read once."""
    return selected * row_numbers(dims, FULL) * itemsize


def attend_flops(dims, selected: float) -> float:
    """Operations of the absorbed attention over the selected rows of ONE full
    layer: every head scores a row's 576 numbers and sums its 512."""
    a = dims.full
    return 2.0 * a.heads * (row_numbers(dims, FULL) + a.kv_rank) * selected


def window_bytes(dims, rows: float, itemsize: int = 2) -> float:
    """Bytes of ``rows`` cached rows of ONE sliding layer."""
    return rows * row_numbers(dims, SLIDING) * itemsize


def decode_step_bytes(dims, slots: float, cached_tokens: float, experts_touched: float,
                      itemsize: int = 2) -> float:
    """Bytes one decode step must read: every matrix every token multiplies
    once, each TOUCHED held expert once (their number summed over the expert
    layers), and of the cache what selection and the window leave: every full
    layer's index keys of the tokens really cached, its ``topk`` (or fewer)
    selected rows a slot, every sliding layer's window a slot."""
    context = cached_tokens / max(slots, 1e-9)
    full, sliding = layers_of(dims, FULL), layers_of(dims, SLIDING)
    cache = (full * (score_work(dims, slots, cached_tokens, itemsize)[1]
                     + gather_bytes(dims, slots * min(context, dims.topk), itemsize))
             + sliding * window_bytes(dims, slots * min(context, dims.window), itemsize))
    return (fixed_matmul_params(dims) + experts_touched * expert_params(dims)) * itemsize + cache
