"""A state-space layer's recurrence over the slots' stored states: one token a
slot (``ssm_update``), and a chunk call's tiles (``ssm_chunk_scan``, below).

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

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import _use_pallas

_LANES = 128
_EXACT = jax.lax.Precision.HIGHEST  # products of float32 state and decays
_GROUP = 8  # lane tiles the chunk scan's kernel unrolls


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


# ---------------------------------------------------------------------------
# A chunk call's tiles
# ---------------------------------------------------------------------------
#
# The token axis is ``n`` tiles of ``T`` tokens, tile ``t`` of pool row
# ``row[t]`` (a row past the pool: nobody's) with ``live[t]`` real tokens. A
# tile's state begins from nothing (``fresh``), from the tile before it
# (``cont``) or from its row of the pool, and a segment's ``last`` tile leaves
# the row as the segment's last real token does. Inside a tile, token i hears
# token j <= i through ``(C_i . B_j) prod_{j < k <= i} a_k``; what came in
# reaches it through ``C_i`` and the decay since the tile began. A padded
# token has ``dt = 0``: its decay is 1 and nothing of it enters.
#
# - ``reference_ssm_chunk_scan``: the plain form: einsums over all tiles, their
#   ``[n, T, T, heads]`` and ``[n, N, heads * p]`` intermediates through HBM, and
#   a ``lax.scan`` between tiles with the pool as its carry.
# - ``_ssm_chunk_scan``: the Pallas kernel, named ``ssm_chunk_scan``. One grid
#   step is one tile, in order; the running state stays in a VMEM scratch from
#   tile to tile, the pool stays in HBM and gives ONE row where a segment
#   begins from its stored state. A tile with no real token fetches nothing
#   (its input block is its live neighbour's, which the pipeline does not fetch
#   again), computes nothing and writes zeros. A segment's last tile hands its
#   state out, and a loop of as many steps as segments ended puts those rows
#   into the pool in place. The kernel does NOT write the pool itself: a
#   custom call whose output is the (aliased) pool is a second pool to XLA's
#   rematerialisation pass, 4.8 GB at the served size, and with it over its
#   limit the pass recomputes a layer's projections instead of keeping them
#   (a call of 51 ms took 85, measured); an in-place update of a row it counts
#   as none.


def _steps(dt, live, A):
    """→ (the step with padding's taken out: no step, no decay; its running log
    decay ``cum`` [n, T, h] float32, a cumulative sum in both forms)."""
    real = jnp.arange(dt.shape[1])[None, :] < live[:, None]
    dt = jnp.where(real[:, :, None], dt, 0.0)
    return dt, jnp.cumsum(-dt * A, axis=1)


def reference_ssm_chunk_scan(pool, row, fresh, cont, last, live, dt, A, xs, B, C):
    """pool: [P, N, hp] float32; row, live: [n] int32; fresh, cont, last: [n]
    bool; dt: [n, T, h] float32, the step; A: [h], the decay's rate (``a =
    exp(-dt A)``); xs: [n, T, hp], B, C: [n, T, N] float32 → (pool', y [n, T,
    hp] float32, zeros in a tile with no real token)."""
    n, T, h = dt.shape
    P, N, hp = pool.shape
    p = hp // h
    dt, cum = _steps(dt, live, A)
    dx = dt[..., None] * xs.reshape(n, T, h, p)
    heard = jnp.tril(jnp.ones((T, T), bool))[None, :, :, None]
    decays = jnp.exp(jnp.where(heard, cum[:, :, None, :] - cum[:, None, :, :], -jnp.inf))
    scores = jnp.einsum("niN,njN->nij", C, B, precision=_EXACT)[..., None] * decays
    y = jnp.einsum("nijh,njhp->nihp", scores, dx, precision=_EXACT)
    # What the tile adds to the state by its end, and how far it decays what came in.
    to_end = jnp.exp(cum[:, -1:, :] - cum)  # [n, T, h]
    added = jnp.einsum("njN,njhp->nNhp", B, to_end[..., None] * dx, precision=_EXACT)
    added = added.reshape(n, N, hp)
    whole = jnp.repeat(jnp.exp(cum[:, -1, :]), p, axis=-1)  # [n, hp]

    # Between tiles, with the pool as the carry: a tile takes its slot's
    # stored rows where it lies (ONE row read: a gather of the tiles' rows
    # has the compiler slice the whole pool) and leaves its outgoing state
    # there, in place. A segment's later tiles overwrite its earlier ones',
    # so what stays is the state after the last; nobody's tile puts back
    # what it read.
    def tile(carry, t):
        pool, before = carry
        fresh_t, cont_t, row_t, mine_t, whole_t, added_t = t
        stored = jax.lax.dynamic_index_in_dim(pool, row_t, axis=0, keepdims=False)
        came = jnp.where(fresh_t, 0.0, jnp.where(cont_t, before, stored))
        left = whole_t[None, :] * came + added_t
        pool = jax.lax.dynamic_update_index_in_dim(
            pool, jnp.where(mine_t, left, stored), row_t, axis=0)
        return (pool, left), came

    (pool, _), came = jax.lax.scan(
        tile, (pool, jnp.zeros(pool.shape[1:], pool.dtype)),
        (fresh, cont, jnp.minimum(row, P - 1), row < P, whole, added))
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "niN,nNhp->nihp", C, came.reshape(n, N, h, p), precision=_EXACT)
    return pool, jnp.where((live > 0)[:, None, None], y.reshape(n, T, hp), 0.0)


def _scan_kernel(meta_ref,  # scalar prefetch [6, n]: live, zero, load, row, store, (the inputs' block)
                 xs_ref,  # [1, T, hp]
                 dt_ref, cum_ref,  # [1, T, h]: the step (0 on padding), its running log decay
                 cumt_ref,  # [1, h / g, g * T]: cum by (head, token), a lane tile's g heads side by side
                 end_ref,  # [1, 8, hp]: row 0 is cum at the tile's end, by (head, p)
                 bt_ref, btg_ref,  # [1, N, T], [1, N, g * T]: B turned, and that g times side by side
                 c_ref,  # [1, T, N]
                 pool_ref,  # [P, N, hp] in HBM
                 y_ref,  # [1, T, hp]
                 ends_ref,  # [n, N, hp] in HBM: tile t's outgoing state, where it ends its segment
                 s_ref,  # scratch [N, hp]: the running state
                 cols_ref,  # scratch [2, hp / 128, T, 128]: cum and dt of a lane tile's heads, over their lanes
                 sem):
    t = pl.program_id(0)
    live, zero, load, row, store = (meta_ref[k, t] for k in range(5))
    T, hp = xs_ref.shape[1:]
    h = dt_ref.shape[2]
    p = hp // h
    g = _LANES // p  # heads a lane tile

    @pl.when(zero == 1)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(load == 1)
    def _():
        copy = pltpu.make_async_copy(pool_ref.at[row], s_ref, sem)
        copy.start()
        copy.wait()  # ray-tpu: lint-ignore[RTL008]

    @pl.when(live == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(live > 0)
    def _():
        def dot(a, b):
            return jnp.dot(a, b, precision=_EXACT, preferred_element_type=jnp.float32)

        def iota(shape, axis):
            return jax.lax.broadcasted_iota(jnp.int32, shape, axis)

        c, bt, cums, dts = c_ref[0], bt_ref[0], cum_ref[0], dt_ref[0]
        # (C_i . B_j), once for all heads, g times side by side: lanes are (head, j).
        heard = iota((T, g * T), 1) % T <= iota((T, g * T), 0)
        cb = dot(c, btg_ref[0])
        # A lane tile holds g heads: of the decays [T, (head, j)] and of x and y [T, (head, p)]
        # alike (T = p), so ONE product with x's heads on the diagonal serves them all.
        head_of = iota((T, _LANES), 1) // p
        diagonal = iota((g * T, _LANES), 0) // T == iota((g * T, _LANES), 1) // p

        def spread(cols):  # [T, g] -> [T, 128]: a head's column over the head's lanes
            out = jnp.broadcast_to(cols[:, g - 1:g], (T, _LANES))
            for j in range(g - 1):
                out = jnp.where(head_of == j, jnp.broadcast_to(cols[:, j:j + 1], (T, _LANES)), out)
            return out

        # Every head's step and running decay over the head's lanes, a lane tile an entry.
        for k in range(hp // _LANES):
            cols_ref[0, k] = spread(cums[:, k * g:(k + 1) * g])
            cols_ref[1, k] = spread(dts[:, k * g:(k + 1) * g])

        # A loop of groups of lane tiles, a group unrolled (all 32 unrolled are 1 MB of
        # code in HBM and seconds more of set-up, for 0.6 ms a call). A group's columns
        # of x, y and the state come and go in one piece, so that inside it nothing is
        # read through an address the compiler cannot tell from a write's.
        group = math.gcd(hp // _LANES, _GROUP)

        def lane_tiles(i, _):
            wide = pl.ds(pl.multiple_of(i * group * _LANES, group * _LANES), group * _LANES)
            xs, came = xs_ref[0, :, wide], s_ref[:, wide]
            ys, left = [], []
            for j in range(group):
                k, at = i * group + j, slice(j * _LANES, (j + 1) * _LANES)
                cum = cols_ref[0, k]  # by (i, head)
                dx = cols_ref[1, k] * xs[:, at]
                # Inside the tile: token i hears token j <= i through (C_i . B_j) prod a.
                decays = jnp.exp(jnp.where(heard, cum - cumt_ref[0, pl.ds(k, 1), :], -jnp.inf))
                y = dot(cb * decays, jnp.where(diagonal, jnp.concatenate([dx] * g, axis=0), 0.0))
                # What came in, through C and the decay since the tile began; what goes out.
                s, end = came[:, at], end_ref[0, 0:1, pl.ds(pl.multiple_of(k * _LANES, _LANES), _LANES)]
                ys.append(y + jnp.exp(cum) * dot(c, s))
                left.append(jnp.exp(end) * s + dot(bt, jnp.exp(end - cum) * dx))
            y_ref[0, :, wide] = jnp.concatenate(ys, axis=1)
            s_ref[:, wide] = jnp.concatenate(left, axis=1)

        jax.lax.fori_loop(0, hp // _LANES // group, lane_tiles, None)

    @pl.when(store == 1)
    def _():
        copy = pltpu.make_async_copy(s_ref, ends_ref.at[t], sem)
        copy.start()
        copy.wait()  # ray-tpu: lint-ignore[RTL008]


# Under a jit of its own: a layer's call is then traced and lowered once a program,
# not once a layer of the period (nine times: seconds of set-up on a busy host).
@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_chunk_scan(pool, row, fresh, cont, last, live, dt, A, xs, B, C, *, interpret: bool = False):
    n, T, h = dt.shape
    P, N, hp = pool.shape
    g = _LANES // (hp // h)
    mine = row < P
    run = mine & (live > 0)
    idx = jnp.arange(n, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(run, idx, -1))  # the nearest tile at or before that runs
    src = jnp.where(before >= 0, before, jnp.argmax(run).astype(jnp.int32))
    meta = jnp.stack([jnp.where(run, live, 0), mine & fresh, mine & ~fresh & ~cont,
                      jnp.minimum(row, P - 1), last & mine, src]).astype(jnp.int32)
    dt, cum = _steps(dt, live, A)
    cum_t = cum.transpose(0, 2, 1).reshape(n, h // g, g * T)
    end = jnp.pad(jnp.repeat(cum[:, -1:, :], hp // h, axis=-1), ((0, 0), (0, 7), (0, 0)))
    bt = B.transpose(0, 2, 1)

    def tile(*block, of=lambda t, meta: meta[5, t]):
        return pl.BlockSpec((1,) + block, lambda t, meta: (of(t, meta), 0, 0))

    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    y, ends = pl.pallas_call(
        _scan_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[tile(T, hp), tile(T, h), tile(T, h), tile(h // g, g * T), tile(8, hp),
                      tile(N, T), tile(N, g * T), tile(T, N), hbm],
            out_specs=[tile(T, hp, of=lambda t, meta: t), hbm],
            scratch_shapes=[pltpu.VMEM((N, hp), jnp.float32),
                            pltpu.VMEM((2, hp // _LANES, T, _LANES), jnp.float32),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=[jax.ShapeDtypeStruct((n, T, hp), jnp.float32),
                   jax.ShapeDtypeStruct((n, N, hp), pool.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="ssm_chunk_scan",
    )(meta, xs, dt, cum, cum_t, end, bt, jnp.concatenate([bt] * g, axis=-1), C, pool)
    # The segments that ended, in place: one row each.
    ended = jnp.nonzero(meta[4], size=n, fill_value=0)[0]

    def put(k, pool):
        t = ended[k]
        state = jax.lax.dynamic_index_in_dim(ends, t, axis=0, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(pool, state, meta[3, t], axis=0)

    return jax.lax.fori_loop(0, jnp.sum(meta[4]), put, pool), y


def _scan_tiles(pool, dt, xs) -> bool:
    """The kernel walks ``(head, p)`` a lane tile at a time, a lane tile's heads'
    decays ``[T, (head, j)]`` beside each other as their outputs are (so a tile
    is as many tokens as a head is wide), and multiplies with the state's
    columns as a whole lane tile."""
    P, N, hp = pool.shape
    n, T, h = dt.shape
    p = hp // h
    return (_tiles(pool) and xs.dtype == jnp.float32 and _LANES % p == 0 and T == p and T % 8 == 0
            and h % (_LANES // p) == 0)


@jax.named_scope("ssm.scan")
def ssm_chunk_scan(pool, row, fresh, cont, last, live, dt, A, xs, B, C):
    """The recurrence of one layer over a chunk call's tiles (the comment
    above): the kernel on a TPU where the shapes tile, else the plain form."""
    if _use_pallas() and _scan_tiles(pool, dt, xs):
        return _ssm_chunk_scan(pool, row, fresh, cont, last, live, dt, A, xs, B, C)
    return reference_ssm_chunk_scan(pool, row, fresh, cont, last, live, dt, A, xs, B, C)
