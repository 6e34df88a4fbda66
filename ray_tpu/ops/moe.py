"""A few tokens through the held experts of one layer: ``moe_decode_experts``.

For the call's tokens ``y`` ``[T, D]``, their gates ``w`` ``[T, held]`` (float32,
0 where a token did not choose the expert) and the layer's stacked experts::

    m[T, D] = sum over touched e of  w[:, e] * (silu(y W_gate[e]) * (y W_up[e])) W_down[e]

Dense over the tokens, sparse over the experts: every touched expert multiplies
ALL ``T`` rows and a row that did not choose it weighs 0. No sort, no gather,
no inverse permutation; under the chip's ridge (``RIDGE_TOKENS``) the ``T``
multiply-adds a weight read are free, the weights' bytes are the cost, and
those are read once, where they lie.

``models/latent_moe.routed_experts`` is the only caller and decides by
``fused(T, held)``: the call's STATIC token count under the ridge, on a TPU
where the widths tile. Over it the sorted ``ragged_dot`` form stays.

The Pallas kernel, named ``moe_decode_experts``:

- grid ``(held, F // f)``: the first axis walks the list of touched experts,
  the second an expert's width ``F`` in tiles of ``f`` (``_expert_tile``: the
  largest multiple of 128 that divides ``F`` with two buffers of the three
  weight blocks inside ``_WEIGHT_VMEM``: 768, whole, at ``D`` 2,560 / ``F`` 768;
  512 at ``D`` 7,680 / ``F`` 2,048). Both axes are sequential: the result is one
  float32 accumulator ``[T, D]`` in VMEM, cast once by the last step.
- scalar prefetch: ``ids`` ``[held]``, the touched experts in index order, the
  places past their count filled with the last one; ``meta`` = (count, layer).
  The weights' blocks are ``[1, 1, D, f]`` / ``[1, 1, f, D]`` of the stacks
  ``[layers, held, ...]`` at ``(layer, ids[step], tile)``: read in place, no
  reshape, no transposed copy. A step past the count names the block that is
  resident (the last expert's last tile) and does nothing, so an untouched
  expert costs no DMA.
- a step computes ``g, u = y W_gate[:, tile], y W_up[:, tile]`` in float32,
  rounds ``silu(g) * u`` to ``y``'s precision as the plain form does, multiplies
  by ``W_down[tile, :]`` in float32 and adds that times the expert's column of
  ``w``. ``y`` and ``w`` are resident; the column is picked by a mask over the
  lanes (a few vector registers).
- the products are the natural ``y @ W`` (tokens streamed through the matrix
  unit, a 128 x 128 piece of the weights latched): at bfloat16 a piece latches
  in the time 64-128 rows stream, and on the chip this form read the touched
  weights at 83-89% of the memory's pace at 16-256 tokens on both served
  widths; the transposed product (weights streamed, tokens latched) read 1-10%
  slower there, and the tile made no difference (PERF.md section 5, PR 51).

On a CPU, and at widths that are not lane tiles, ``fused`` says no and the
sorted form runs; the interpreter runs the kernel in the tests
(``tests/test_latent_moe.py``, ``tests/test_kda_moe.py``), the chip runs it
against the sorted form in ``tests/test_chip_bringup.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import _use_pallas

_LANES = 128
# The chip's ridge in tokens. A bfloat16 weight is 2 bytes and does 2 T
# operations (a multiply and an add a token), so a product of T rows is bound
# by memory while 2 T / 2 bytes < peak operations / peak bytes:
# 197e12 / 819e9 = 240 operations a byte on a TPU v5e (``chipbench/peaks.py``'s
# two numbers), 240 tokens. Borne out by one sweep on the chip (PERF.md
# section 5, PR 51).
RIDGE_TOKENS = 240
# Two buffers of an expert tile's three weight blocks may take this much VMEM
# (of 128 MiB on a v5e: 47.2 MB at 7,680 x 512, 23.6 MB at 2,560 x 768); the
# accumulator, ``y``, the result and a step's float32 intermediates (30 MB at
# 240 tokens of 7,680) come on top, under ``_VMEM_LIMIT``, which leaves the
# compiler a quarter of the chip's VMEM for its own.
_WEIGHT_VMEM = 48 * 1024 * 1024
_VMEM_LIMIT = 96 * 1024 * 1024


def _expert_tile(D: int, F: int, itemsize: int) -> int:
    """The widest tile of an expert's ``F`` (a multiple of 128 that divides it)
    of which two buffers of all three blocks fit ``_WEIGHT_VMEM``; ``F`` whole
    where it has no such divisor (the interpreter's small shapes)."""
    fits = [f for f in range(_LANES, F + 1, _LANES)
            if F % f == 0 and 2 * 3 * D * f * itemsize <= _WEIGHT_VMEM]
    return max(fits) if fits else F


def fused(T: int, held) -> bool:
    """Whether ``routed_experts`` multiplies a call of ``T`` tokens through the
    kernel: under the ridge, on a TPU, where hidden and expert widths are whole
    lane tiles."""
    _, _, D, F = held["e_gate"].shape
    return T <= RIDGE_TOKENS and _use_pallas() and D % _LANES == 0 and F % _LANES == 0


def _kernel(ids_ref, meta_ref,  # scalar prefetch: [held] touched experts, (count, layer)
            y_ref,  # [T, D]
            w_ref,  # [T, held] float32
            gate_ref, up_ref,  # [1, 1, D, f]
            down_ref,  # [1, 1, f, D]
            o_ref,  # [T, D]
            acc_ref):  # [T, D] float32
    step, tile = pl.program_id(0), pl.program_id(1)

    @pl.when((step == 0) & (tile == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(step < meta_ref[0])
    def _():
        y = y_ref[...]
        g = jnp.dot(y, gate_ref[0, 0].astype(y.dtype), preferred_element_type=jnp.float32)
        u = jnp.dot(y, up_ref[0, 0].astype(y.dtype), preferred_element_type=jnp.float32)
        a = (jax.nn.silu(g) * u).astype(y.dtype)
        out = jnp.dot(a, down_ref[0, 0].astype(y.dtype), preferred_element_type=jnp.float32)
        w = w_ref[...]
        mine = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1) == ids_ref[step]
        acc_ref[...] += jnp.sum(jnp.where(mine, w, 0.0), axis=1, keepdims=True) * out

    @pl.when((step == pl.num_programs(0) - 1) & (tile == pl.num_programs(1) - 1))
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


# Under a jit of its own: a layer's call is traced and lowered once a program,
# not once a layer and step of the window.
@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_decode_experts(y, w, sizes, held, layer, *, interpret: bool = False):
    """y: [T, D]; w: [T, E] float32 gates (0: not chosen); sizes: [E] pairs an
    expert, of which the kernel reads only which are 0; held: ``e_gate``,
    ``e_up`` ``[layers, E, D, F]``, ``e_down`` ``[layers, E, F, D]``; layer:
    this layer's number in the stack (it may be traced) → m [T, D] in y's dtype."""
    T, D = y.shape
    _, E, _, F = held["e_gate"].shape
    f = _expert_tile(D, F, held["e_gate"].dtype.itemsize)
    tiles = F // f
    rows = -(-T // 16) * 16  # whole sublane tiles of a bfloat16 operand
    y_in = jnp.pad(y, ((0, rows - T), (0, 0)))
    w_in = jnp.pad(w.astype(jnp.float32), ((0, rows - T), (0, 0)))
    touched = sizes > 0
    count = jnp.sum(touched, dtype=jnp.int32)
    ids = jnp.argsort(~touched, stable=True).astype(jnp.int32)  # the touched first, in order
    ids = jnp.where(jnp.arange(E) < count, ids, ids[jnp.maximum(count - 1, 0)])
    meta = jnp.stack([count, jnp.asarray(layer, jnp.int32)])

    def block(along_rows: bool):
        def index(step, tile, ids, meta):
            tile = jnp.where(step < meta[0], tile, tiles - 1)
            return (meta[1], ids[step]) + ((tile, 0) if along_rows else (0, tile))
        return pl.BlockSpec((1, 1, f, D) if along_rows else (1, 1, D, f), index)

    def whole(width):
        return pl.BlockSpec((rows, width), lambda step, tile, ids, meta: (0, 0))

    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(E, tiles),
            in_specs=[whole(D), whole(E), block(False), block(False), block(True)],
            out_specs=whole(D),
            scratch_shapes=[pltpu.VMEM((rows, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, D), y.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_decode_experts",
    )(ids, meta, y_in, w_in, held["e_gate"], held["e_up"], held["e_down"])
    return out[:T]
