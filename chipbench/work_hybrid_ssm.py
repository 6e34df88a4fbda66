"""Operations and bytes the hybrid state-space decoder needs, from its shapes.

What the algorithm needs, never what a program happens to move. ``dims`` is
``weights_hybrid_ssm.Dims`` (or anything with its fields).
"""
from __future__ import annotations


def mlp_params(dims) -> int:
    """Every layer's feed-forward: W_i (gate and up) and W_o."""
    return 3 * dims.hidden * dims.ffn


def mamba_matmul_params(dims) -> int:
    """A state-space layer's matrices: the in- and the out-projection, the MLP."""
    inner = dims.expand * dims.hidden
    width = 2 * inner + 2 * dims.groups * dims.state + dims.ssm_heads
    return dims.hidden * width + inner * dims.hidden + mlp_params(dims)


def attention_matmul_params(dims) -> int:
    q, kv = dims.heads * (dims.hidden // dims.heads), dims.kv_heads * (dims.hidden // dims.heads)
    return dims.hidden * (2 * q + 2 * kv) + mlp_params(dims)


def layers_of(dims, kind: str) -> int:
    return sum(1 for k in dims.kinds if k == kind)


def matmul_params(dims) -> int:
    """Parameters every token multiplies: the layers' matrices and the head
    (the embedding, used as the head: counted once, its lookup is rows)."""
    return (layers_of(dims, "mamba") * mamba_matmul_params(dims)
            + layers_of(dims, "attention") * attention_matmul_params(dims)
            + dims.hidden * dims.vocab)


def stored_params(dims) -> int:
    """Every parameter held: norms, the convolution, dt_bias, A_log and D too."""
    inner = dims.expand * dims.hidden
    conv_dim = inner + 2 * dims.groups * dims.state
    small = conv_dim * (dims.conv + 1) + 3 * dims.ssm_heads + inner + 2 * dims.hidden
    return (matmul_params(dims) + layers_of(dims, "mamba") * small
            + layers_of(dims, "attention") * 2 * dims.hidden + dims.hidden)


def state_bytes_per_slot(dims, itemsize: int = 4) -> int:
    """One slot's state over all state-space layers: heads x p x n numbers a layer."""
    return layers_of(dims, "mamba") * dims.ssm_heads * dims.ssm_head * dims.state * itemsize


def kv_bytes_per_token(dims, itemsize: int = 2) -> int:
    return 2 * layers_of(dims, "attention") * dims.kv_heads * (dims.hidden // dims.heads) * itemsize


def decode_step_bytes(dims, live_slots: float, cached_tokens: float, itemsize: int = 2) -> float:
    """Bytes one decode step must move: every matrix once, each LIVE slot's
    state read and written (float32), K and V of the tokens really cached
    (the convolution's three inputs a slot, 26 KB a layer, are left out)."""
    return (matmul_params(dims) * itemsize + 2 * live_slots * state_bytes_per_slot(dims)
            + cached_tokens * kv_bytes_per_token(dims, itemsize))


def ssm_update_work(dims, live_slots: float) -> tuple:
    """(operations, bytes) of ONE call of the state update (one layer, one
    token a slot): a live slot's state read and written once in float32; per
    state entry a multiply by the decay, a multiply-add of ``d x B`` and a
    multiply-add into ``y``: five operations. The per-slot vectors (decay, d x:
    heads x p each; B, C: n each; y out) are counted too."""
    entries = dims.ssm_heads * dims.ssm_head * dims.state
    vectors = 3 * dims.ssm_heads * dims.ssm_head + 2 * dims.state
    return 5.0 * entries * live_slots, (2 * entries + vectors) * 4.0 * live_slots
