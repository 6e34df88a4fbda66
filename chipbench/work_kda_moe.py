"""Operations and bytes the KDA / latent-attention expert decoder needs, from its
shapes.

What the algorithm needs, never what a program happens to move (a latent row is
its 576 numbers, whatever the pool pads them to; an expert nobody routed to is
not read; an idle slot's state is not touched). ``dims`` is
``weights_kda_moe.Dims`` (or anything with its fields).
"""
from __future__ import annotations


def kinds(dims) -> list:
    """The kind of every layer: latent attention ends each group, the rest are KDA."""
    return ["latent" if (i + 1) % dims.group == 0 else "kda" for i in range(dims.layers)]


def layers_of(dims, kind: str) -> int:
    return kinds(dims).count(kind)


def kda_mixer_params(dims) -> int:
    """A KDA mixer's matrices: W_qkv, W_g, W_z, W_b, W_o."""
    d, hk = dims.hidden, dims.heads * dims.head
    return d * 3 * hk + 2 * d * hk + d * dims.heads + hk * d


def latent_mixer_params(dims) -> int:
    """A latent attention mixer's matrices: W_q, W_dkv, W_ukv, W_hg, W_o."""
    d, h = dims.hidden, dims.heads
    return (d * h * (dims.nope + dims.rope) + d * (dims.kv_rank + dims.rope)
            + dims.kv_rank * h * (dims.nope + dims.v_dim) + d * h + h * dims.v_dim * d)


def expert_params(dims) -> int:
    """One routed expert."""
    return 3 * dims.hidden * dims.expert_ffn


def expert_layers(dims) -> int:
    return dims.layers - dims.lead


def fixed_matmul_params(dims) -> int:
    """Parameters every token multiplies: every mixer, the leading layers'
    feed-forward, each expert layer's router and shared expert, the head."""
    mixers = (layers_of(dims, "kda") * kda_mixer_params(dims)
              + layers_of(dims, "latent") * latent_mixer_params(dims))
    return (mixers + dims.lead * 3 * dims.hidden * dims.ffn
            + expert_layers(dims) * (dims.hidden * dims.experts + 3 * dims.hidden * dims.shared_ffn)
            + dims.hidden * dims.vocab)


def stored_params(dims) -> int:
    """Every parameter this share holds: embedding, norms, the convolution, the
    decay's bias and rate, the selection bias and the held experts too."""
    hk = dims.heads * dims.head
    small_kda = 2 * dims.hidden + 3 * hk * dims.taps + hk + dims.heads + dims.head
    small_latent = 2 * dims.hidden + dims.nope + dims.rope + dims.kv_rank
    return (fixed_matmul_params(dims) + expert_layers(dims) * dims.held * expert_params(dims)
            + expert_layers(dims) * dims.experts + dims.vocab * dims.hidden + dims.hidden
            + layers_of(dims, "kda") * small_kda + layers_of(dims, "latent") * small_latent)


def state_bytes_per_slot(dims, itemsize: int = 4) -> int:
    """One slot's state over all KDA layers: heads x keys x values numbers a layer."""
    return layers_of(dims, "kda") * dims.heads * dims.head * dims.head * itemsize


def latent_bytes_per_token(dims, itemsize: int = 2) -> int:
    """One cached token over the latent attention layers."""
    return layers_of(dims, "latent") * (dims.kv_rank + dims.rope) * itemsize


def decode_step_bytes(dims, live_slots: float, cached_tokens: float, experts_touched: float,
                      itemsize: int = 2) -> float:
    """Bytes one decode step must move: every matrix that every token
    multiplies once, each TOUCHED held expert once (``experts_touched``: their
    number summed over the expert layers), each LIVE slot's state read and
    written (float32), the latent rows of the tokens really cached (the
    convolution's three inputs a slot, 74 KB a layer, are left out)."""
    return ((fixed_matmul_params(dims) + experts_touched * expert_params(dims)) * itemsize
            + 2 * live_slots * state_bytes_per_slot(dims)
            + cached_tokens * latent_bytes_per_token(dims, itemsize))


def kda_update_work(dims, live_slots: float) -> tuple:
    """(operations, bytes) of ONE call of the state update (one layer, one
    token a slot): a live slot's state read and written once in float32; per
    state entry a multiply by the decay, a multiply-add into ``S^T k``, a
    multiply-add of ``beta k u^T`` and a multiply-add into ``o``: seven
    operations. The per-slot vectors (decay, key, query: heads x keys each;
    value, output: heads x values each; beta) are counted too."""
    entries = dims.heads * dims.head * dims.head
    vectors = 5 * dims.heads * dims.head + dims.heads
    return 7.0 * entries * live_slots, (2 * entries + vectors) * 4.0 * live_slots
