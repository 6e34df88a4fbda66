"""Power retention of degree 2 ("Scaling Context Requires Rethinking Attention",
arXiv:2507.04239) over the slots' stored states: one token a slot
(``power_update``), and a chunk call's tiles (``power_chunk_scan``, below).

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
# Every exponent is <= 0, so nothing is split. ``phi`` of a tile's queries and
# keys is made inside the tile's step (of a whole call's it would be 1.6 GB);
# only ``S_0`` goes from tile to tile, in a ``lax.scan`` with the pool as its
# carry (``ops/ssm.py``: a tile reads its row where it lies and writes it back;
# a gather of the tiles' rows before the scan copied the WHOLE pool on a v5e).
# Everything float32 at ``highest``.


@jax.jit
def reference_power_chunk_scan(pool, row, fresh, cont, last, live, log_g, q, k, v):
    """pool: [R, H, VALUES, P] float32; row, live: [n] int32; fresh, cont, last:
    [n] bool; log_g: [n, C, H] (<= 0); k, v: [n, C, H, d]; q: [n, C, H, G, d],
    all float32 → (pool', y [n, C, H, G, d] float32, zeros in a tile with no
    real token)."""
    del last  # a segment's later tiles overwrite its earlier ones' rows
    n, C, H, G, d = q.shape
    R = pool.shape[0]
    real = jnp.arange(C)[None, :] < live[:, None]  # [n, C]
    log_g = jnp.where(real[..., None], log_g, 0.0)
    k = jnp.where(real[..., None, None], k, 0.0)
    run = jnp.cumsum(log_g, axis=1)  # [n, C, H]
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


@jax.named_scope("power.scan")
def power_chunk_scan(pool, row, fresh, cont, last, live, log_g, q, k, v):
    """The recurrence of one layer over a chunk call's tiles (the comment
    above). The plain form everywhere: it has no kernel yet."""
    return reference_power_chunk_scan(pool, row, fresh, cont, last, live, log_g, q, k, v)
