"""One step of a state-space layer's recurrence over the slots' stored states.

For a slot whose state is ``S`` (per head ``[p, n]``; stored TRANSPOSED and
flat, ``[n, heads * p]``: see below) and whose token gives a decay ``a`` and a
step ``d`` per head, ``x`` ``[heads, p]``, ``B`` and ``C`` ``[n]``::

    S <- a S + (d x) B^T        y = S C

``ssm_update(pool, base, lens, decay, dx, B, C)`` does that for every slot
with ``lens > 0`` IN the flat pool ``[layers * slots, n, heads * p]`` at rows
``base + slot``, and gives ``y`` ``[slots, heads * p]``; a slot with ``lens``
0 (idle, or mid-prefill) is read and written by nobody and its ``y`` is zeros.

- ``reference_ssm_update``: the plain ``jax.numpy`` form. CPU, and the oracle.
- ``_ssm_state_update``: the Pallas kernel, named ``ssm_state_update``. The
  pool is its input and its output (aliased); one grid step is one slot, whose
  state comes into VMEM whole, is advanced a lane tile at a time and goes back.
  A skipped slot is given the block index of its nearest live neighbour: the
  pipeline neither fetches nor writes back a block whose index did not change,
  so nothing of the skipped slot moves.

**Why the state lies transposed.** ``a`` and ``d x`` are one number a (head,
p) pair and ``B``, ``C`` one a state column ``n``. With ``(head, p)`` on the
lanes, the first two are rows that broadcast over sublanes for nothing, the
sum over ``n`` for ``y`` is a sum over sublanes that leaves a lane-dense row,
and only ``B`` and ``C`` (128 numbers) have to be turned into columns. The
other way round every one of the 4,096 pairs would need turning, twice a step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import _use_pallas

_LANES = 128


def reference_ssm_update(pool, base, lens, decay, dx, B, C):
    """pool: [P, n, hp] float32; base: first row of this layer's slots; lens:
    [b]; decay, dx: [b, hp] float32 (per (head, p): the head's decay, step
    times input); B, C: [b, n] float32 → (pool', y [b, hp] float32)."""
    b = lens.shape[0]
    S = jax.lax.dynamic_slice_in_dim(pool, base, b, axis=0)
    new = decay[:, None, :] * S + B[:, :, None] * dx[:, None, :]
    live = (lens > 0)[:, None, None]
    new = jnp.where(live, new, S)
    y = jnp.where(live[:, 0], jnp.sum(new * C[:, :, None], axis=1), 0.0)
    return jax.lax.dynamic_update_slice_in_dim(pool, new, base, axis=0), y


def _kernel(row_ref, lens_ref,  # scalar prefetch: [b] block row of each step, [b + 1] lens, live count
            rows_ref,  # [1, 8, hp]: row 0 the decay, row 1 d x
            bc_ref,  # [1, 8, n]: row 0 B, row 1 C
            s_ref,  # [1, n, hp] the state, in
            o_ref,  # [1, n, hp] the state, out (the same rows of the same pool)
            y_ref):  # [1, 8, hp]: row 0 is y
    del row_ref
    slot = pl.program_id(0)
    n_slots = pl.num_programs(0)
    n, hp = s_ref.shape[1:]
    live = lens_ref[slot] > 0

    @pl.when(live)
    def _():
        # B and C as columns: the row over every sublane, turned.
        def column(row):
            return jnp.broadcast_to(row, (_LANES, n)).T

        bc = bc_ref[0]
        b_col, c_col = column(bc[0:1]), column(bc[1:2])  # [n, 128]: B[n] along every lane
        for lo in range(0, hp, _LANES):
            at = pl.ds(lo, _LANES)
            s = (rows_ref[0, 0:1, at] * s_ref[0, :, at] + b_col * rows_ref[0, 1:2, at])
            o_ref[0, :, at] = s
            y_ref[0, 0:1, at] = jnp.sum(s * c_col, axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    # With nobody live every step names row 0's block, which is then written
    # back once: give it what was read.
    @pl.when(lens_ref[n_slots] == 0)
    def _():
        o_ref[...] = s_ref[...]


def _ssm_state_update(pool, base, lens, decay, dx, B, C, *, interpret: bool = False):
    b = lens.shape[0]
    P, n, hp = pool.shape
    live = lens > 0
    idx = jnp.arange(b, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(live, idx, -1))  # the nearest live slot at or before
    first = jnp.argmax(live).astype(jnp.int32)  # 0 where none is
    row_of = base + jnp.where(before >= 0, before, first)
    # Two rows a slot each, in a whole sublane tile of eight.
    rows = jnp.pad(jnp.stack([decay, dx], axis=1), ((0, 0), (0, 6), (0, 0)))
    bc = jnp.pad(jnp.stack([B, C], axis=1), ((0, 0), (0, 6), (0, 0)))
    counted = jnp.concatenate([lens.astype(jnp.int32), jnp.sum(live, dtype=jnp.int32)[None]])

    def small(width):
        return pl.BlockSpec((1, 8, width), lambda s, row, lens: (s, 0, 0))

    state = pl.BlockSpec((1, n, hp), lambda s, row, lens: (row[s], 0, 0))
    pool, y = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[small(hp), small(n), state],
            out_specs=[state, small(hp)],
        ),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((b, 8, hp), jnp.float32)],
        # Operands count the scalar-prefetch arguments: the pool is the fifth.
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="ssm_state_update",
    )(row_of.astype(jnp.int32), counted, rows, bc, pool)
    return pool, y[:, 0]


def _tiles(pool) -> bool:
    """The kernel walks the (head, p) axis a lane tile at a time and turns
    ``B`` and ``C`` as whole tiles."""
    _, n, hp = pool.shape
    return pool.dtype == jnp.float32 and hp % _LANES == 0 and n % _LANES == 0


@jax.named_scope("ssm.update")
def ssm_update(pool, base, lens, decay, dx, B, C):
    """The state update of one layer, one token a slot (module docstring): the
    kernel on a TPU where the shapes tile, else the plain form."""
    if _use_pallas() and _tiles(pool):
        return _ssm_state_update(pool, base, lens, decay, dx, B, C)
    return reference_ssm_update(pool, base, lens, decay, dx, B, C)
