"""Operations and bytes the latent-attention expert decoder needs, from its shapes.

What the algorithm needs, never what a program happens to move (a cache row is
its 576 numbers, whatever the pool pads them to; an expert nobody routed to is
not read). ``dims`` is ``weights_latent_moe.Dims`` (or anything with its fields).
"""
from __future__ import annotations


def attention_params(dims) -> int:
    """A layer's attention matrices: W_dq, W_uq, W_dkv, W_ukv, W_o."""
    d, h = dims.hidden, dims.heads
    return (d * dims.q_rank + dims.q_rank * h * (dims.nope + dims.rope)
            + d * (dims.kv_rank + dims.rope) + dims.kv_rank * h * (dims.nope + dims.v_dim)
            + h * dims.v_dim * d)


def dense_ffn_params(dims) -> int:
    return 3 * dims.hidden * dims.ffn


def expert_params(dims) -> int:
    """One routed expert (the shared expert is ``dims.shared`` of them)."""
    return 3 * dims.hidden * dims.expert_ffn


def router_params(dims) -> int:
    return dims.hidden * dims.experts


def expert_layers(dims) -> int:
    return dims.layers - dims.lead


def fixed_matmul_params(dims) -> int:
    """Parameters every token multiplies: attention everywhere, the leading
    layers' feed-forward, each expert layer's router and shared expert, the head."""
    return (dims.layers * attention_params(dims) + dims.lead * dense_ffn_params(dims)
            + expert_layers(dims) * (router_params(dims) + dims.shared * expert_params(dims))
            + dims.hidden * dims.vocab)


def stored_params(dims) -> int:
    """Every parameter this share holds: embedding, norms and held experts too."""
    norms = dims.layers * (4 * dims.hidden + dims.q_rank + dims.kv_rank) + dims.hidden
    return (fixed_matmul_params(dims) + expert_layers(dims) * dims.held * expert_params(dims)
            + dims.vocab * dims.hidden + norms)


def active_params_per_token(dims) -> int:
    """Parameters one token multiplies where every expert is held."""
    return fixed_matmul_params(dims) + expert_layers(dims) * dims.per_token * expert_params(dims)


def latent_row_numbers(dims) -> int:
    return dims.kv_rank + dims.rope


def latent_bytes_per_token(dims, itemsize: int = 2) -> int:
    """One cached token over all layers."""
    return dims.layers * latent_row_numbers(dims) * itemsize


def decode_step_bytes(dims, cached_tokens: float, experts_touched: float, itemsize: int = 2) -> float:
    """Bytes one decode step must read: every matrix every token multiplies
    once, each TOUCHED held expert once (``experts_touched``: their number
    summed over the expert layers), and the latent rows of the tokens really
    cached over the batch."""
    return ((fixed_matmul_params(dims) + experts_touched * expert_params(dims)) * itemsize
            + cached_tokens * latent_bytes_per_token(dims, itemsize))


def latent_attend_work(dims, slots: int, cached_tokens: float, itemsize: int = 2) -> tuple:
    """(operations, bytes) of ONE call of the decode attention kernel (one
    layer, one token a slot) in the absorbed form: every head scores a row's
    576 numbers and sums its 512; each cached row read once, the absorbed
    queries and the per-head sums once."""
    per_row = dims.heads * (latent_row_numbers(dims) + dims.kv_rank)
    flops = 2.0 * per_row * cached_tokens
    bytes_ = cached_tokens * latent_row_numbers(dims) * itemsize + slots * per_row * itemsize
    return flops, bytes_
