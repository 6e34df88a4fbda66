"""The held experts of one layer as ONE Pallas kernel, in the loop nest the
call's static token count asks for: ``moe_decode_experts`` for a few tokens,
``moe_grouped_experts`` for many.

For the call's tokens ``y`` ``[T, D]``, their gates ``w`` ``[T, held]`` (float32,
0 where a token did not choose the expert) and the layer's stacked experts::

    m[T, D] = sum over touched e of  w[:, e] * (silu(y W_gate[e]) * (y W_up[e])) W_down[e]

``models/latent_moe.routed_experts`` is the only caller and decides by
``fused(T, held)`` / ``grouped(T, held)``: the call's STATIC token count under
or over the chip's ridge (``RIDGE_TOKENS``), on a TPU where the widths tile. On
a CPU, and at widths that are not lane tiles, both say no and three
``ragged_dot`` multiply the sorted pairs; the interpreter runs the kernels in
the tests (``tests/test_latent_moe.py``, ``tests/test_kda_moe.py``), the chip
runs them against that form in ``tests/test_chip_bringup.py``.

What the two share: the weights' blocks are ``[1, 1, D, f]`` / ``[1, 1, f, D]``
of the stacks ``[layers, held, ...]`` at ``(layer, expert, tile)``, the layer a
prefetched scalar: read in place, no reshape, no transposed copy, no slice of
a layer's experts. ``f`` tiles an expert's width ``F`` (``_expert_tile``: the
largest multiple of 128 that divides ``F`` with two buffers of the three
weight blocks inside ``_WEIGHT_VMEM``: 768, whole, at ``D`` 2,560 / ``F`` 768;
512 at ``D`` 7,680 / ``F`` 2,048). A step past the count of what there is to
do names the block that is resident and does nothing, so an untouched expert
costs no DMA. Products are accumulated in float32 and ``silu(g) * u`` is
rounded to the activations' precision, as the plain form does. The products
are the natural ``y @ W`` (tokens streamed through the matrix unit, a 128 x
128 piece of the weights latched); the transposed product read 1-10% slower
(PERF.md section 5, PR 51).

**Under the ridge: ``moe_decode_experts``.** Dense over the tokens, sparse over
the experts: every touched expert multiplies ALL ``T`` rows and a row that did
not choose it weighs 0. No sort, no gather, no inverse permutation; the ``T``
multiply-adds a weight read are free, the weights' bytes are the cost, and
those are read once.

- grid ``(held, F // f)``: the first axis walks the list of touched experts,
  the second an expert's tiles. Both are sequential: the result is one float32
  accumulator ``[T, D]`` in VMEM, cast once by the last step.
- scalar prefetch: ``ids`` ``[held]``, the touched experts in index order, the
  places past their count filled with the last one; ``meta`` = (count, layer).
- a step adds its product times the expert's column of ``w``; ``y`` and ``w``
  are resident; the column is picked by a mask over the lanes.
- on the chip it read the touched weights at 83-89% of the memory's pace at
  16-256 tokens on both served widths (PERF.md section 5, PR 51).

**Over the ridge: ``moe_grouped_experts``.** Dense rows would multiply every
token by every expert (at 1,024 tokens 9.6 ms a layer, or more VMEM than the
chip has), so the work is sparse over the tokens as well: the caller sorts the
(token, choice) pairs by expert, the held experts' first, and the kernel walks
VISITS: an expert with pairs and one tile of ``_ROW_TILE`` sorted rows that its
group lies in, in order of expert, then tile.

- grid ``(held + row tiles - 1, F // f)``: at most one visit an expert and one a
  tile boundary inside a group. Scalar prefetch: a visit's expert and row tile,
  the groups' bounds, ``meta`` = (count of visits, layer).
- an expert's weights are fetched once however many tiles its rows cross (the
  block's index does not change between its visits; with ``F`` tiled they come
  again for each further row tile), a tile's rows stay resident over its
  experts, and tiles past the last held pair (the pairs of experts held
  elsewhere: 3/4 of the rows at a quarter of the experts, 15/16 at a
  sixteenth) are never visited: nothing is read, computed or written there.
- a visit sends the whole tile through its expert (a weight's byte does 128
  operations: under the ridge whatever the widths) and writes only its group's
  rows; the tile's other rows keep what an earlier visit wrote, or 0.
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


def _kernels(held) -> bool:
    """On a TPU, where hidden and expert widths are whole lane tiles."""
    _, _, D, F = held["e_gate"].shape
    return _use_pallas() and D % _LANES == 0 and F % _LANES == 0


def fused(T: int, held) -> bool:
    """Whether ``routed_experts`` multiplies a call of ``T`` tokens through
    ``moe_decode_experts``: under the ridge, where the kernels run."""
    return T <= RIDGE_TOKENS and _kernels(held)


def grouped(T: int, held) -> bool:
    """Whether ``routed_experts`` multiplies a call of ``T`` tokens' sorted
    pairs through ``moe_grouped_experts``: over the ridge, where the kernels run."""
    return T > RIDGE_TOKENS and _kernels(held)


def _weight_blocks(D: int, f: int, tiles: int):
    """The blocks of an expert's gate, up and down matrices in the stacks
    ``[layers, held, ...]``, for a grid ``(step, tile of F)`` whose first
    prefetched scalars name a step's expert and whose last are (count of
    steps, layer): a step past the count names the resident block."""
    def block(along_rows: bool):
        def index(step, tile, experts, *rest):
            count, layer = rest[-1][0], rest[-1][1]
            tile = jnp.where(step < count, tile, tiles - 1)
            return (layer, experts[step]) + ((tile, 0) if along_rows else (0, tile))
        return pl.BlockSpec((1, 1, f, D) if along_rows else (1, 1, D, f), index)
    return [block(False), block(False), block(True)]


def _through_expert(x, gate_ref, up_ref, down_ref):
    """x [rows, D] through the resident tile of an expert: float32 [rows, D]."""
    g = jnp.dot(x, gate_ref[0, 0].astype(x.dtype), preferred_element_type=jnp.float32)
    u = jnp.dot(x, up_ref[0, 0].astype(x.dtype), preferred_element_type=jnp.float32)
    a = (jax.nn.silu(g) * u).astype(x.dtype)
    return jnp.dot(a, down_ref[0, 0].astype(x.dtype), preferred_element_type=jnp.float32)


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
        out = _through_expert(y_ref[...], gate_ref, up_ref, down_ref)
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

    def whole(width):
        return pl.BlockSpec((rows, width), lambda step, tile, ids, meta: (0, 0))

    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(E, tiles),
            in_specs=[whole(D), whole(E), *_weight_blocks(D, f, tiles)],
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


def _grouped_kernel(experts_ref, tiles_ref,  # scalar prefetch: [visits] a visit's expert and row tile
                    bounds_ref,  # [E + 1] the sorted rows at which an expert's group starts
                    meta_ref,  # (count of visits, layer)
                    x_ref,  # [rows, D]
                    gate_ref, up_ref,  # [1, 1, D, f]
                    down_ref,  # [1, 1, f, D]
                    o_ref,  # [rows, D]
                    acc_ref):  # [rows, D] float32
    visit, tile = pl.program_id(0), pl.program_id(1)
    live = visit < meta_ref[0]

    @pl.when(live)
    def _():
        out = _through_expert(x_ref[...], gate_ref, up_ref, down_ref)

        @pl.when(tile == 0)
        def _():
            acc_ref[...] = out

        @pl.when(tile > 0)
        def _():
            acc_ref[...] += out

    @pl.when(live & (tile == pl.num_programs(1) - 1))
    def _():
        # The visit's expert owns the rows of its group; the tile's other rows
        # keep what an earlier visit of the tile wrote, or 0 on its first.
        rows = o_ref.shape[0]
        expert, at = experts_ref[visit], tiles_ref[visit]
        row = at * rows + jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
        mine = (row >= bounds_ref[expert]) & (row < bounds_ref[expert + 1])
        first = (visit == 0) | (tiles_ref[jnp.maximum(visit - 1, 0)] != at)
        kept = jnp.where(first, jnp.zeros_like(o_ref), o_ref[...])
        o_ref[...] = jnp.where(mine, acc_ref[...].astype(o_ref.dtype), kept)


# The rows of a tile of the sorted pairs. Every visit sends the whole tile
# through its expert, so a weight's byte does 2 x 128 / 2 operations: under the
# ridge whatever the widths, and a tile the matrix unit streams at its pace.
_ROW_TILE = 128


@functools.partial(jax.jit, static_argnames=("row_tile", "interpret"))
def moe_grouped_experts(x, sizes, held, layer, *, row_tile: int = _ROW_TILE, interpret: bool = False):
    """x: [N, D] the tokens of the pairs sorted by held expert, the held
    experts' first; sizes: [E] pairs an expert; held: ``e_gate``, ``e_up``
    ``[layers, E, D, F]``, ``e_down`` ``[layers, E, F, D]``; layer: this layer's
    number in the stack (it may be traced) → [N, D] in x's dtype: row i through
    the expert whose group holds it; past the last group 0 to the end of its
    row tile, and after that tile whatever the memory held (no visit goes there)."""
    N, D = x.shape
    _, E, _, F = held["e_gate"].shape
    f = _expert_tile(D, F, held["e_gate"].dtype.itemsize)
    tiles = F // f
    row_tiles = -(-N // row_tile)
    x_in = jnp.pad(x, ((0, row_tiles * row_tile - N), (0, 0)))
    # A visit is an expert with pairs and one row tile its group lies in: in
    # order of expert, then tile, so that an expert's weights stay resident
    # over its tiles and a tile's rows over its experts. At most one visit an
    # expert and one a tile boundary inside a group.
    visits = E + row_tiles - 1
    bounds = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes, dtype=jnp.int32)])
    first_tile = bounds[:-1] // row_tile
    spans = jnp.where(sizes > 0, (bounds[1:] - 1) // row_tile - first_tile + 1, 0)
    upto = jnp.cumsum(spans)
    count = upto[-1]
    # Places past the count name the last visit: its blocks are resident.
    at = jnp.minimum(jnp.arange(visits, dtype=jnp.int32), jnp.maximum(count - 1, 0))
    expert = jnp.minimum(jnp.sum(upto[None, :] <= at[:, None], axis=1, dtype=jnp.int32), E - 1)
    row_at = (first_tile[expert] + at - (upto[expert] - spans[expert])).astype(jnp.int32)
    meta = jnp.stack([count, jnp.asarray(layer, jnp.int32)])

    rows = pl.BlockSpec((row_tile, D), lambda visit, tile, experts, rows_at, bounds, meta: (rows_at[visit], 0))
    out = pl.pallas_call(
        _grouped_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(visits, tiles),
            in_specs=[rows, *_weight_blocks(D, f, tiles)],
            out_specs=rows,
            scratch_shapes=[pltpu.VMEM((row_tile, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(x_in.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_grouped_experts",
    )(expert, row_at, bounds, meta, x_in, held["e_gate"], held["e_up"], held["e_down"])
    return out[:N]
