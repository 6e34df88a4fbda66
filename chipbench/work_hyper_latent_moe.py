"""Operations and bytes the multi-stream latent-attention expert decoder needs, from
its shapes: ``work_latent_moe``'s (the attention, the experts, the rows are that
model's) and what the residual streams add.

What the algorithm needs, never what a program happens to move. ``dims`` is
``weights_hyper_latent_moe.Dims`` (or anything with its fields). The maps' parameters
are float32 (4 bytes) whatever the streams' type.
"""
from __future__ import annotations

from chipbench import work_latent_moe as L

SUBLAYERS = 2  # mixes a layer: around the attention, around the feed-forward
MAP_ITEMSIZE = 4


def map_params(dims) -> int:
    """One sublayer's maps' parameters: phi, the three gains, b_pre, b_post, b_res."""
    n = dims.streams
    return n * dims.hidden * (2 * n + n * n) + 3 + 2 * n + n * n


def mixes_a_call(dims) -> int:
    """Sublayers one program call (a decode step, a chunk call) mixes around."""
    return SUBLAYERS * dims.layers


def mix_bytes_a_place(dims, itemsize: int = 2) -> int:
    """The least one sublayer's mixes move for ONE token place: the streams ``X`` read
    twice (once for the maps and ``u``, which one pass can make together; once for
    ``Hres X``) and written once, ``u`` written and ``y`` read. The maps themselves
    (24 numbers a place) stay on the chip."""
    return (3 * dims.streams + 2) * dims.hidden * itemsize


def mix_bytes(dims, places_mixed: float, calls: float, itemsize: int = 2) -> float:
    """The least the mixes of ``places_mixed`` (token places x sublayers: the engine's
    ``hc_places_mixed``) move over ``calls`` program calls: the places' streams, and each
    sublayer's ``phi`` once a call."""
    return (places_mixed * mix_bytes_a_place(dims, itemsize)
            + calls * mixes_a_call(dims) * map_params(dims) * MAP_ITEMSIZE)


def stored_bytes(dims, itemsize: int = 2) -> int:
    """Every parameter this share holds (``work_latent_moe.stored_params`` counts two
    sandwich norms a layer that this model has not) and the maps' in float32."""
    params = L.stored_params(dims) - dims.layers * 2 * dims.hidden
    return params * itemsize + mixes_a_call(dims) * map_params(dims) * MAP_ITEMSIZE


def decode_step_bytes(dims, slots: float, cached_tokens: float, experts_touched: float,
                      itemsize: int = 2) -> float:
    """Bytes one decode step must move: ``work_latent_moe.decode_step_bytes`` (every
    matrix every token multiplies once, each TOUCHED held expert once, the latent rows of
    the tokens really cached) and the mixes of ``slots`` places in every sublayer."""
    return (L.decode_step_bytes(dims, cached_tokens, experts_touched, itemsize)
            + mix_bytes(dims, slots * mixes_a_call(dims), 1, itemsize))
