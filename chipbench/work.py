"""Operations and bytes the algorithms need, from their shapes.

What the algorithm needs, never what a program happens to move: a roofline
share built on these cannot pass 100% unless the time leaves work out.
``dims`` is ``weights.Dims`` (or anything with the same fields).
"""
from __future__ import annotations


def layer_matmul_params(dims) -> int:
    """Parameters of one layer that multiply an activation."""
    q, kv = dims.heads * dims.head_dim, dims.kv_heads * dims.head_dim
    return dims.hidden * (2 * q + 2 * kv) + 3 * dims.hidden * dims.ffn


def matmul_params(dims) -> int:
    """Parameters that multiply: the layers and the head, not the embedding
    (a lookup) and not the norms (elementwise)."""
    return dims.layers * layer_matmul_params(dims) + dims.hidden * dims.vocab


def stored_params(dims) -> int:
    """Every parameter held: embedding, layers with their norms, final norm, head."""
    return (dims.layers * (layer_matmul_params(dims) + 2 * dims.hidden)
            + 2 * dims.vocab * dims.hidden + dims.hidden)


def causal_attention_flops(dims, seq: int) -> float:
    """Forward QK^T and PV of one sequence, all layers, the causal half only:
    position t attends to t + 1 keys."""
    per_layer = 2 * 2 * dims.heads * dims.head_dim * seq * (seq + 1) / 2
    return dims.layers * per_layer


def train_flops_per_token(dims, seq: int) -> float:
    """Forward and backward of one token in a sequence of ``seq``: 6 for each
    parameter that multiplies, 3 times the causal forward attention.
    Recomputation is not counted."""
    return 6.0 * matmul_params(dims) + 3.0 * causal_attention_flops(dims, seq) / seq


def flash_fwd_work(dims, batch: int, seq: int, itemsize: int = 2) -> tuple:
    """(operations, bytes) of one call of the forward flash kernel over
    [batch, heads, seq, head_dim]: the causal half of QK^T and PV; q and o
    once, each K/V head once, and the float32 logsumexp."""
    flops = 2 * 2 * batch * dims.heads * dims.head_dim * seq * (seq + 1) / 2
    qo = 2 * batch * dims.heads * seq * dims.head_dim * itemsize
    kv = 2 * batch * dims.kv_heads * seq * dims.head_dim * itemsize
    lse = batch * dims.heads * seq * 4
    return flops, qo + kv + lse


def kv_bytes_per_token(dims, itemsize: int = 2) -> int:
    return 2 * dims.layers * dims.kv_heads * dims.head_dim * itemsize


def decode_step_bytes(dims, cached_tokens: float, itemsize: int = 2) -> float:
    """Bytes one decode step must read: every weight that multiplies once,
    the embedding rows aside, and K and V of the tokens really cached over
    the batch (not of the padded block table)."""
    return matmul_params(dims) * itemsize + cached_tokens * kv_bytes_per_token(dims, itemsize)
