"""Operations and bytes the power retention decoder needs, from its shapes.

What the algorithm needs, never what a program happens to move: a head's state
is the LEAST map's ``head_dim * (head_dim + 1) / 2`` rows (8,256 at 128) of
``head_dim`` values and one normaliser, whatever a layout pads them to (the
program's has 8,320 x 136); an idle slot's state is not touched; every matrix is
read once a step. ``dims`` is ``weights_power_retention.Dims`` (or anything with
its fields).
"""
from __future__ import annotations


def mixer_params(dims) -> int:
    """A power retention mixer's matrices: W_q, W_o, W_k, W_v, W_g."""
    d, q, kv = dims.hidden, dims.heads * dims.head_dim, dims.kv_heads * dims.head_dim
    return 2 * d * q + 2 * d * kv + d * dims.kv_heads


def layer_params(dims) -> int:
    """A layer's matrices: the mixer and the SwiGLU."""
    return mixer_params(dims) + 3 * dims.hidden * dims.ffn


def matmul_params(dims) -> int:
    """Parameters every token multiplies: every layer's matrices and the head."""
    return dims.layers * layer_params(dims) + dims.hidden * dims.vocab


def stored_params(dims) -> int:
    """Every parameter held: the embedding, the norms and the gate's bias too."""
    small = 2 * dims.hidden + 2 * dims.head_dim + dims.kv_heads
    return matmul_params(dims) + dims.vocab * dims.hidden + dims.hidden + dims.layers * small


def phi_entries(dims) -> int:
    """Entries of the least map with phi(a) . phi(b) = (a . b) ** 2 / head_dim."""
    return dims.head_dim * (dims.head_dim + 1) // 2


def state_entries(dims) -> int:
    """Numbers of one slot's state in one layer: S and z of every key/value head."""
    return dims.kv_heads * phi_entries(dims) * (dims.head_dim + 1)


def state_bytes_per_slot(dims, itemsize: int = 4) -> int:
    """One slot's state over all layers."""
    return dims.layers * state_entries(dims) * itemsize


def decode_step_bytes(dims, live_slots: float, itemsize: int = 2) -> float:
    """Bytes one decode step must move: every matrix once (the head too), each
    LIVE slot's float32 state read and written. There is no cache by token."""
    return matmul_params(dims) * itemsize + 2 * live_slots * state_bytes_per_slot(dims)


def power_update_work(dims, live_slots: float) -> tuple:
    """(operations, bytes) of ONE call of the state update (one layer, one token
    a slot): a live slot's state read and written once in float32; per state
    entry a multiply by the decay, a multiply-add of ``phi(k) v^T`` and one
    multiply-add into each of the ``heads / kv_heads`` queries' reads: 3 + 2 x 5
    = 13 operations at the published grouping. The per-slot vectors (queries and
    reads: heads x head_dim each; key, value: kv_heads x head_dim each; the
    decay) are counted too."""
    group = dims.heads // dims.kv_heads
    vectors = 2 * dims.heads * dims.head_dim + 2 * dims.kv_heads * dims.head_dim + dims.kv_heads
    entries = state_entries(dims)
    return (3.0 + 2 * group) * entries * live_slots, (2 * entries + vectors) * 4.0 * live_slots
