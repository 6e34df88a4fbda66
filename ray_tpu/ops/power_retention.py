"""Power retention of degree 2 ("Scaling Context Requires Rethinking Attention",
arXiv:2507.04239) over the slots' stored states: one token a slot
(``power_update``), and a chunk call's tiles (``power_chunk_scan``, below: the
plain form and its kernel; no option selects, the platform and the shapes do).

A key/value head weighs a past token ``j`` for a query at ``t`` by ``(q_t . k_j)
** 2 / d`` times the decays since, and that square is a plain inner product of
``phi(q_t)`` and ``phi(k_j)`` (``expand``), so the sum over the past is a state::

    S_t = g_t S_{t-1} + phi(k_t) v_t^T     z_t = g_t z_{t-1} + phi(k_t)
    y_t = phi(q_t)^T S_t / (phi(q_t) . z_t + eps)

**The layout.** ``phi(a)[r * d + i] = c_r a_i a_{(i + r) mod d}`` for ``r = 0 ..
d / 2``: the products of a vector with itself turned by ``r``, ``d / 2 + 1``
rows of ``d`` (8,320 entries at ``d`` 128 where the least map has 8,256: row ``d
/ 2`` holds each of its pairs twice, at weight 1 where rows 1 .. ``d / 2 - 1``
have the square root of 2), every row a lane tile. The normaliser is FOLDED
into the state as one more value: a token's values are ``[v | 1 | 0 ..]``
(``VALUES`` = 136 rows: 128, the one, and seven of nothing to the sublane
tile), so ``z`` is row 128 of the state, the update is one rule and the read's
row 128 is the denominator. A head's state is ``[VALUES, P]`` float32 with the
VALUES on the sublanes and ``phi`` on the lanes (4.53 MB at ``d`` 128); the
pool's row a slot is ``[kv heads, VALUES, P]``, and the queries of one state
(``G`` of them: grouped-query heads) read it in one pass.

``power_update(pool, base, lens, g, k, q, v)`` does a step for every slot with
``lens > 0`` IN the flat pool ``[layers * slots, heads, VALUES, P]`` at rows
``base + slot`` and gives ``y`` ``[slots, heads, G, d]``; a slot with ``lens`` 0
(idle, or mid-prefill) is read and written by nobody and its ``y`` is zeros.

- ``reference_power_update``: the plain ``jax.numpy`` form. CPU, and the oracle.
- ``_power_state_update``: the Pallas kernel, named ``power_state_update``. A
  slot's state (36 MB) is no VMEM's, so the grid is (slot, head) and a step
  holds ONE head's state; the pool is its input and its output (aliased), and a
  skipped slot is given the block of its nearest live neighbour's LAST head
  (the first live slot's first, where none is before it), so nothing of it
  moves (``ops/ssm.py``). Five queries a state are five multiply-adds an entry:
  the vector unit's work, not the matrix unit's (a product of 8 rows would load
  every tile of the state as a weight). The state is walked eight VALUES at a
  time, each lane tile updated and then read by the queries while it is in
  registers; what would be broadcasts in the kernel are made outside (the
  values spread over the lanes: 70 KB a head; ``phi`` of the queries and the
  key and the decay as rows: 266 KB) and the sums over the lanes are left to
  XLA (the kernel gives ``[G, VALUES, 128]`` partial sums a head, 348 KB):
  together 8% of the state's own 9 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import _use_pallas

_LANES = 128
_SUBLANES = 8
_EXACT = jax.lax.Precision.HIGHEST  # every product that reads or makes a float32 state
EPS = 1e-2  # beside the denominator, which is a sum of squares times decays: never below 0


def values_rows(d: int) -> int:
    """Rows of a head's state: the values, the normaliser's one, to the sublane tile."""
    return -(-(d + 1) // _SUBLANES) * _SUBLANES


def phi_width(d: int) -> int:
    """Entries of ``expand``'s map of a vector of ``d``."""
    return (d // 2 + 1) * d


def expand(a):
    """phi: [.., d] float32 → [.., (d / 2 + 1) * d] with ``expand(a) . expand(b)
    = (a . b) ** 2 / d`` (the module docstring's layout; ``d`` even)."""
    d = a.shape[-1]
    twice = jnp.concatenate([a, a], axis=-1)
    turned = jnp.stack([twice[..., r:r + d] for r in range(d // 2 + 1)], axis=-2)  # [.., d/2+1, d]
    weight = jnp.full((d // 2 + 1,), 2.0 ** 0.5, jnp.float32).at[0].set(1.0).at[d // 2].set(1.0)
    out = a[..., None, :] * turned * (weight * d ** -0.5)[:, None]
    return out.reshape(a.shape[:-1] + (phi_width(d),))


def with_one(v):
    """A token's values with the normaliser's one behind them: [.., d] → [.., VALUES]."""
    d = v.shape[-1]
    pad = jnp.zeros(v.shape[:-1] + (values_rows(d) - d - 1,), v.dtype)
    return jnp.concatenate([v, jnp.ones_like(v[..., :1]), pad], axis=-1)


def normalise(read, d: int):
    """What the queries read of a state, [.., VALUES] → y [.., d]: the values
    over the normaliser's row."""
    return read[..., :d] / (read[..., d:d + 1] + EPS)


def reference_power_update(pool, base, lens, g, k, q, v):
    """pool: [R, H, VALUES, P] float32; base: first row of this layer's slots;
    lens: [b]; g: [b, H] (the decay, in (0, 1]); k, v: [b, H, d]; q: [b, H, G,
    d], all float32 → (pool', y [b, H, G, d] float32)."""
    b, d = lens.shape[0], k.shape[-1]
    with jax.named_scope("power.expand"):
        phi_q, phi_k = expand(q), expand(k)
    S = jax.lax.dynamic_slice_in_dim(pool, base, b, axis=0)
    new = g[..., None, None] * S + with_one(v)[..., :, None] * phi_k[..., None, :]
    live = (lens > 0)[:, None, None, None]
    new = jnp.where(live, new, S)
    read = jnp.einsum("bhgp,bhvp->bhgv", phi_q, new, precision=_EXACT)
    y = jnp.where(live, normalise(read, d), 0.0)
    return jax.lax.dynamic_update_slice_in_dim(pool, new, base, axis=0), y


def _kernel(row_ref, fixed_ref, lens_ref,  # scalar prefetch: [b] block row, [b] fixed head or -1, [b + 1] lens, live
            rows_ref,  # [1, 1, 8, P]: rows 0 .. G-1 phi of the queries, row G phi of the key, row G + 1 the decay
            vals_ref,  # [1, 1, VALUES, 128]: the token's values (and the one) spread over the lanes
            s_ref,  # [1, 1, VALUES, P] the head's state, in
            o_ref,  # [1, 1, VALUES, P] the same rows of the same pool, out
            y_ref,  # [1, 1, G, VALUES, 128]: the queries' reads, a sum over the lanes short
            *, G: int):
    del row_ref, fixed_ref
    slot = pl.program_id(0)
    n_slots = pl.num_programs(0)
    V, P = s_ref.shape[2:]
    live = lens_ref[slot] > 0

    @pl.when(live)
    def _():
        def eight_values(j, carry):
            at = pl.ds(pl.multiple_of(j * _SUBLANES, _SUBLANES), _SUBLANES)
            value = vals_ref[0, 0, at, :]  # [8, 128]
            reads = [jnp.zeros((_SUBLANES, _LANES), jnp.float32) for _ in range(G)]
            for t in range(P // _LANES):
                lanes = pl.ds(t * _LANES, _LANES)
                s = (rows_ref[0, 0, G + 1:G + 2, lanes] * s_ref[0, 0, at, lanes]
                     + value * rows_ref[0, 0, G:G + 1, lanes])
                o_ref[0, 0, at, lanes] = s
                for a in range(G):
                    reads[a] = reads[a] + s * rows_ref[0, 0, a:a + 1, lanes]
            for a in range(G):
                y_ref[0, 0, a, at, :] = reads[a]
            return carry

        jax.lax.fori_loop(0, V // _SUBLANES, eight_values, None)

    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    # With nobody live every step names one block, which is then written back
    # once: give it what was read.
    @pl.when(lens_ref[n_slots] == 0)
    def _():
        o_ref[...] = s_ref[...]


# Under a jit of its own: a layer's call is then traced and lowered once a
# program, not once a layer and step of the window (eighty times).
@functools.partial(jax.jit, static_argnames=("interpret",))
def _power_state_update(pool, base, lens, g, k, q, v, *, interpret: bool = False):
    b = lens.shape[0]
    R, H, V, P = pool.shape
    G, d = q.shape[2:]
    live = lens > 0
    idx = jnp.arange(b, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(live, idx, -1))  # the nearest live slot at or before
    first = jnp.argmax(live).astype(jnp.int32)  # 0 where none is
    row_of = base + jnp.where(before >= 0, before, first)
    # The head a skipped slot's steps all name: its neighbour's last, or the first's first.
    fixed = jnp.where(live, -1, jnp.where(before >= 0, H - 1, 0)).astype(jnp.int32)
    counted = jnp.concatenate([lens.astype(jnp.int32), jnp.sum(live, dtype=jnp.int32)[None]])
    with jax.named_scope("power.expand"):
        rows = jnp.concatenate([
            expand(q), expand(k)[:, :, None], jnp.broadcast_to(g[:, :, None, None], (b, H, 1, P)),
            jnp.zeros((b, H, _SUBLANES - G - 2, P), jnp.float32)], axis=2)
        vals = jnp.broadcast_to(with_one(v)[..., None], (b, H, V, _LANES))

    def small(*block):
        return pl.BlockSpec((1, 1) + block, lambda s, h, *_: (s, h) + (0,) * len(block))

    state = pl.BlockSpec(
        (1, 1, V, P),
        lambda s, h, row, fixed, lens: (row[s], jnp.where(fixed[s] < 0, h, fixed[s]), 0, 0))
    pool, y = pl.pallas_call(
        functools.partial(_kernel, G=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, H),
            in_specs=[small(_SUBLANES, P), small(V, _LANES), state],
            out_specs=[state, small(G, V, _LANES)],
        ),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((b, H, G, V, _LANES), jnp.float32)],
        # Operands count the scalar-prefetch arguments: the pool is the sixth.
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="power_state_update",
    )(row_of.astype(jnp.int32), fixed, counted, rows, vals, pool)
    return pool, jnp.where(live[:, None, None, None], normalise(jnp.sum(y, axis=-1), d), 0.0)


def _tiles(pool, q) -> bool:
    """The kernel's rows: the queries, the key and the decay in one sublane
    tile, ``phi`` whole lane tiles."""
    return (pool.dtype == jnp.float32 and q.shape[2] + 2 <= _SUBLANES
            and pool.shape[-1] % _LANES == 0 and pool.shape[-2] % _SUBLANES == 0)


def power_update(pool, base, lens, g, k, q, v):
    """The state update of one layer, one token a slot (module docstring): the
    kernel on a TPU where the shapes tile, else the plain form. ``phi`` of the
    queries and the key is made under the scope ``power.expand`` in both."""
    with jax.named_scope("power.update"):
        if _use_pallas() and _tiles(pool, q):
            return _power_state_update(pool, base, lens, g, k, q, v)
        return reference_power_update(pool, base, lens, g, k, q, v)


# ---------------------------------------------------------------------------
# A chunk call's tiles
# ---------------------------------------------------------------------------
#
# The token axis is ``n`` tiles of ``C`` tokens, tile ``t`` of pool row
# ``row[t]`` (a row past the pool: nobody's) with ``live[t]`` real tokens; a
# tile's state begins from nothing (``fresh``), from the tile before it
# (``cont``) or from its row of the pool, as ``ops/ssm.py`` has it. A padded
# token has ``log g = 0`` and ``k = 0``: no decay, and nothing of it enters.
#
# Inside a tile, with ``G_i`` the running sum of ``log g`` since the tile began
# (a head's, <= 0), ``S_0`` what came in and ``V'`` the values with their one::
#
#     A = ((Q K^T) ** 2 / d) * exp(G_i - G_j) * [j <= i]
#     read = A V' + exp(G_i) phi(Q) S_0^T          S_C = exp(G_C) S_0 + V'^T diag(exp(G_C - G_j)) phi(K)
#
# Every exponent is <= 0, so nothing is split. Everything float32 at ``highest``.
#
# - ``reference_power_chunk_scan``: the plain form. CPU, and the oracle. ``phi``
#   of a tile's queries and keys is made inside the tile's step (of a whole
#   call's it would be 1.6 GB; of a tile's 5,120 queries it is 170 MB, through
#   HBM); only ``S_0`` goes from tile to tile, in a ``lax.scan`` with the pool
#   as its carry (``ops/ssm.py``: a tile reads its row where it lies and writes
#   it back; a gather of the tiles' rows before the scan copied the WHOLE pool
#   on a v5e).
# - ``_power_chunk_scan``: the Pallas kernel, named ``power_chunk_scan``. The
#   grid is (head, tile), a head's tiles in order: the head's running state
#   ``[VALUES, P]`` (4.5 MB) stays in a VMEM scratch from tile to tile, zeroed
#   where a segment begins its prompt, copied from ``pool[row, head]`` (the pool
#   stays in HBM) where it takes up a stored state. ``phi`` never leaves VMEM:
#   a step walks the state's ``d / 2 + 1`` lane tiles, and for lane tile ``r``
#   makes ``q roll(q, -r)`` of the tile's ``G * C`` queries and ``c_r k roll(k,
#   -r)`` of its keys from the operands it holds, reads ``S_r`` (``c_r S_r`` times
#   the queries' row of ``phi``, TURNED: the reads are ``[VALUES, G * C]``, the
#   normaliser's row eight more rows of the streamed operand and ``exp(G_i)`` a
#   row over the sublanes) and then advances it. The quadratic form inside the
#   tile is two more products a step. A tile with no real token fetches nothing
#   (its blocks are its live neighbour's), computes nothing and writes zeros.
#   A segment's last tile hands its state out (``ends``), and a loop of as many
#   steps as segments ended puts those rows into the pool in place: the kernel
#   does not write the pool (``ops/ssm.py``: a custom call whose output is the
#   aliased pool is a second pool to XLA's rematerialisation pass).
#
# The two forms differ where a call holds TWO segments of one row: the plain
# form's second segment takes up what the first left (the pool is its carry),
# the kernel's reads the INPUT pool, which no segment of this call has written
# yet. A call holds at most one segment a row: ``hybrid_ssm._segments`` joins a
# slot's neighbouring tiles into one, and the engine packs a slot's chunk once a
# call.


def _steps(live, log_g, k):
    """→ (k with padding's taken out; ``G`` [n, C, H], the running sum of log g
    since the tile began with padding's taken as 0: no key, no decay), in both forms."""
    real = jnp.arange(log_g.shape[1])[None, :] < live[:, None]  # [n, C]
    return jnp.where(real[..., None, None], k, 0.0), jnp.cumsum(jnp.where(real[..., None], log_g, 0.0), axis=1)


@jax.jit
def reference_power_chunk_scan(pool, row, fresh, cont, last, live, log_g, q, k, v):
    """pool: [R, H, VALUES, P] float32; row, live: [n] int32; fresh, cont, last:
    [n] bool; log_g: [n, C, H] (<= 0); k, v: [n, C, H, d]; q: [n, C, H, G, d],
    all float32 → (pool', y [n, C, H, G, d] float32, zeros in a tile with no
    real token)."""
    del last  # a segment's later tiles overwrite its earlier ones' rows
    n, C, H, G, d = q.shape
    R = pool.shape[0]
    k, run = _steps(live, log_g, k)
    lower = jnp.tril(jnp.ones((C, C), bool))

    def tile(carry, t):
        pool, before = carry
        fresh_t, cont_t, row_t, mine_t, run_t, q_t, k_t, v_t = t
        stored = jax.lax.dynamic_index_in_dim(pool, row_t, axis=0, keepdims=False)
        came = jnp.where(fresh_t, 0.0, jnp.where(cont_t, before, stored))  # [H, VALUES, P]
        values = with_one(v_t)  # [C, H, VALUES]
        scores = jnp.einsum("ihgd,jhd->hgij", q_t, k_t, precision=_EXACT)
        since = jnp.where(lower, run_t.T[:, :, None] - run_t.T[:, None, :], -jnp.inf)  # [H, C, C]
        A = jnp.square(scores) * (d ** -1.0) * jnp.exp(since)[:, None]
        read = jnp.einsum("hgij,jhv->ihgv", A, values, precision=_EXACT)
        read = read + jnp.exp(run_t)[:, :, None, None] * jnp.einsum(
            "ihgp,hvp->ihgv", expand(q_t), came, precision=_EXACT)
        to_end = jnp.exp(run_t[-1][None] - run_t)  # [C, H]
        left = jnp.exp(run_t[-1])[:, None, None] * came + jnp.einsum(
            "jhv,jhp->hvp", values * to_end[..., None], expand(k_t), precision=_EXACT)
        pool = jax.lax.dynamic_update_index_in_dim(
            pool, jnp.where(mine_t, left, stored), row_t, axis=0)
        return (pool, left), normalise(read, d)

    (pool, _), y = jax.lax.scan(
        tile, (pool, jnp.zeros(pool.shape[1:], pool.dtype)),
        (fresh, cont, jnp.minimum(row, R - 1), row < R, run, q, k, v))
    return pool, jnp.where((live > 0)[:, None, None, None, None], y, 0.0)


def _scan_kernel(meta_ref,  # scalar prefetch [6, n]: live, zero, load, row, store, (the inputs' block)
                 q_ref,  # [1, 1, G * C, d]: the tile's queries of this state, query head by query head
                 k_ref,  # [1, 1, C, d]: its keys (0 on padding)
                 vt_ref,  # [1, 1, VALUES, C]: its values with their one, turned
                 heard_ref,  # [1, 1, C, C]: exp(G_i - G_j) / d at [j, i] where j <= i, else 0
                 rows_ref,  # [1, 1, 8, G * C]: row 0 exp(G_i) a query, row 1 exp(G_C - G_j) a key, row 2 exp(G_C)
                 pool_ref,  # [R, H, VALUES, P] in HBM
                 y_ref,  # [1, 1, VALUES, G * C]: the queries' reads, turned
                 ends_ref,  # [n, H, VALUES, P] in HBM: tile t's outgoing state, where it ends its segment
                 s_ref,  # scratch [VALUES, P]: the head's running state
                 sem, *, group: int):
    h, t = pl.program_id(0), pl.program_id(1)
    live, zero, load, row, store = (meta_ref[j, t] for j in range(5))
    GC, d = q_ref.shape[2:]
    C = k_ref.shape[2]
    P = s_ref.shape[1]

    @pl.when(zero == 1)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(load == 1)
    def _():
        copy = pltpu.make_async_copy(pool_ref.at[row, h], s_ref, sem)
        copy.start()
        copy.wait()  # ray-tpu: lint-ignore[RTL008]

    @pl.when(live == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(live > 0)
    def _():
        def dot(a, b, turned=False):  # a b, or a b^T
            return jax.lax.dot_general(a, b, (((1,), (1 if turned else 0,)), ((), ())),
                                       precision=_EXACT, preferred_element_type=jnp.float32)

        q, k, vt = q_ref[0, 0], k_ref[0, 0], vt_ref[0, 0]
        to_end = vt * rows_ref[0, 0, 1:2, :C]  # V'^T diag(exp(G_C - G_j))
        whole = rows_ref[0, 0, 2:3, :d]  # exp(G_C), over a lane tile
        y_ref[...] = jnp.zeros_like(y_ref)

        # Row r of phi is c_r a roll(a, -r), a lane tile of the state: made here of the operands
        # in hand, read by the queries (S_0, before the update) and then advanced. A loop of groups
        # of lane tiles, a group unrolled (``ops/ssm.py``: all unrolled are seconds of set-up).
        def lane_tiles(i, _):
            read = None
            for j in range(group):
                r = i * group + j
                c = jnp.where((r == 0) | (r == d // 2), 1.0, 2.0 ** 0.5) * d ** -0.5
                at = pl.ds(pl.multiple_of(r * d, d), d)
                turn = jnp.where(r == 0, 0, d - r)
                s = s_ref[:, at]
                part = dot(s * c, q * pltpu.roll(q, turn, 1), turned=True)  # [VALUES, G * C]
                read = part if read is None else read + part
                s_ref[:, at] = whole * s + dot(to_end, k * pltpu.roll(k, turn, 1) * c)
            y_ref[0, 0] += read

        jax.lax.fori_loop(0, P // d // group, lane_tiles, None)
        # Inside the tile: query i hears key j <= i through (q_i . k_j) ** 2 / d and the decays between.
        heard = jnp.concatenate([heard_ref[0, 0]] * (GC // C), axis=1)
        inside = dot(vt, jnp.square(dot(k, q, turned=True)) * heard)
        y_ref[0, 0] = inside + rows_ref[0, 0, 0:1, :] * y_ref[0, 0]

    @pl.when(store == 1)
    def _():
        copy = pltpu.make_async_copy(s_ref, ends_ref.at[t, h], sem)
        copy.start()
        copy.wait()  # ray-tpu: lint-ignore[RTL008]


_SCAN_GROUP = 5  # lane tiles the chunk scan's kernel unrolls (of d / 2 + 1 = 65)


# Under a jit of its own, as the update's kernel is.
@functools.partial(jax.jit, static_argnames=("interpret",))
def _power_chunk_scan(pool, row, fresh, cont, last, live, log_g, q, k, v, *, interpret: bool = False):
    n, C, H, G, d = q.shape
    R, _, V, P = pool.shape
    assert (P // d) % _SCAN_GROUP == 0, (P, d, _SCAN_GROUP)
    mine = row < R
    run = mine & (live > 0)
    idx = jnp.arange(n, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(run, idx, -1))  # the nearest tile at or before that runs
    src = jnp.where(before >= 0, before, jnp.argmax(run).astype(jnp.int32))
    meta = jnp.stack([jnp.where(run, live, 0), mine & fresh, mine & ~fresh & ~cont,
                      jnp.minimum(row, R - 1), last & mine, src]).astype(jnp.int32)
    k, since = _steps(live, log_g, k)
    since = since.transpose(0, 2, 1)  # [n, H, C]
    at_or_after = jnp.triu(jnp.ones((C, C), bool))  # [j, i]: j <= i
    heard = jnp.exp(jnp.where(at_or_after, since[..., None, :] - since[..., :, None], -jnp.inf)) * d ** -1.0
    end = since[..., -1:]
    rows = jnp.exp(jnp.stack([since, end - since, jnp.broadcast_to(end, since.shape)], axis=2))  # [n, H, 3, C]
    rows = jnp.pad(jnp.tile(rows, (1, 1, 1, G)), ((0, 0), (0, 0), (0, _SUBLANES - 3), (0, 0)))

    def tile(*block, of=lambda t, meta: meta[5, t]):
        return pl.BlockSpec((1, 1) + block, lambda h, t, meta: (of(t, meta), h, 0, 0))

    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    y, ends = pl.pallas_call(
        functools.partial(_scan_kernel, group=_SCAN_GROUP),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H, n),
            in_specs=[tile(G * C, d), tile(C, d), tile(V, C), tile(C, C), tile(_SUBLANES, G * C), hbm],
            out_specs=[tile(V, G * C, of=lambda t, meta: t), hbm],
            scratch_shapes=[pltpu.VMEM((V, P), jnp.float32), pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=[jax.ShapeDtypeStruct((n, H, V, G * C), jnp.float32),
                   jax.ShapeDtypeStruct((n, H, V, P), pool.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="power_chunk_scan",
    )(meta, q.transpose(0, 2, 3, 1, 4).reshape(n, H, G * C, d), k.transpose(0, 2, 1, 3),
      with_one(v).transpose(0, 2, 3, 1), heard, rows, pool)
    # The segments that ended, in place: one row each.
    ended = jnp.nonzero(meta[4], size=n, fill_value=0)[0]

    def put(j, pool):
        t = ended[j]
        state = jax.lax.dynamic_index_in_dim(ends, t, axis=0, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(pool, state, meta[3, t], axis=0)

    pool = jax.lax.fori_loop(0, jnp.sum(meta[4]), put, pool)
    read = y.reshape(n, H, V, G, C).transpose(0, 4, 1, 3, 2)  # [n, C, H, G, VALUES]
    return pool, jnp.where((live > 0)[:, None, None, None, None], normalise(read, d), 0.0)


def _scan_tiles(pool, q) -> bool:
    """The chunk scan's kernel: a row of ``phi`` is the head's whole lane tile
    (so a roll along the lanes turns it), the VALUES whole sublane tiles, and a
    tile's tokens a lane tile too (they are the lanes of the decays and of the
    values turned)."""
    n, C, H, G, d = q.shape
    return (pool.dtype == jnp.float32 and q.dtype == jnp.float32 and d == _LANES and C == _LANES
            and pool.shape[-2] % _SUBLANES == 0 and pool.shape[-1] == phi_width(d))


@jax.named_scope("power.scan")
def power_chunk_scan(pool, row, fresh, cont, last, live, log_g, q, k, v):
    """The recurrence of one layer over a chunk call's tiles (the comment
    above): the kernel on a TPU where the shapes tile, else the plain form."""
    if _use_pallas() and _scan_tiles(pool, q):
        return _power_chunk_scan(pool, row, fresh, cont, last, live, log_g, q, k, v)
    return reference_power_chunk_scan(pool, row, fresh, cont, last, live, log_g, q, k, v)
