"""The delta rule with a decay a CHANNEL (Kimi Delta Attention, arXiv:2510.26692)
over the slots' stored states: one token a slot (``kda_update``), and a chunk
call's tiles (``kda_chunk_scan``, below).

A head's state is ``S`` ``[K keys, V values]`` float32 (the pool's row a slot:
``[heads, K, V]``). A token gives the head a decay ``a`` ``[K]`` (one a key
channel, in (0, 1]), a key ``k`` and a query ``q`` ``[K]``, a value ``v``
``[V]`` and a step ``beta``::

    S <- diag(a) S      u = v - S^T k      S <- S + beta k u^T      o = S^T q

``ops/ssm.py``'s update cannot do it: there the decay is ONE number a head and
the update never reads the state it writes; here the decay is a channel's and
``u`` is read from ``S`` before ``S`` is written.

``kda_update(pool, base, lens, a, k, q, v, beta)`` does that for every slot
with ``lens > 0`` IN the flat pool ``[layers * slots, heads, K, V]`` at rows
``base + slot``, and gives ``o`` ``[slots, heads, V]``; a slot with ``lens`` 0
(idle, or mid-prefill) is read and written by nobody and its ``o`` is zeros.

- ``reference_kda_update``: the plain ``jax.numpy`` form. CPU, and the oracle.
- ``_kda_state_update``: the Pallas kernel, named ``kda_state_update``. As
  ``ops/ssm.py``'s: the pool is its input and its output (aliased), one grid
  step is one slot whose state comes into VMEM whole, and a skipped slot is
  given the block index of its nearest live neighbour, so nothing of it moves.
  With keys on the sublanes and values on the lanes, ``a``, ``k`` and ``q`` are
  one number a sublane: they come TURNED (``[K, heads]``, made outside: three
  arrays of 16 KB a slot) and a head's column is spread over the lanes; ``v``,
  ``beta`` and ``o`` are rows. The sums over keys are sums over sublanes.
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
# Tokens over which a tile's products of decays are taken at once: a decay is
# exp(g) with g above -5 (``kda_lower_bound``), and sixteen steps of -5 are
# exp(-80), which float32 holds (and its inverse); sixty-four are not.
_SUB = 16


def reference_kda_update(pool, base, lens, a, k, q, v, beta):
    """pool: [P, H, K, V] float32; base: first row of this layer's slots; lens:
    [b]; a, k, q: [b, H, K]; v: [b, H, V]; beta: [b, H], all float32 →
    (pool', o [b, H, V] float32)."""
    b = lens.shape[0]
    S = jax.lax.dynamic_slice_in_dim(pool, base, b, axis=0)
    new = a[..., None] * S
    u = beta[..., None] * (v - jnp.sum(new * k[..., None], axis=2))
    new = new + k[..., None] * u[:, :, None, :]
    live = (lens > 0)[:, None, None, None]
    new = jnp.where(live, new, S)
    o = jnp.where(live[:, :, 0], jnp.sum(new * q[..., None], axis=2), 0.0)
    return jax.lax.dynamic_update_slice_in_dim(pool, new, base, axis=0), o


def _kernel(row_ref, lens_ref,  # scalar prefetch: [b] block row of each step, [b + 1] lens, live count
            cols_ref,  # [1, K, W]: lanes [0, H) a head's decay, [H, 2H) its key, [2H, 3H) its query
            rows_ref,  # [1, 8, H * V]: row 0 the value, row 1 beta over the head's lanes
            s_ref,  # [1, H, K, V] the state, in
            o_ref,  # [1, H, K, V] the state, out (the same rows of the same pool)
            y_ref):  # [1, 8, H * V]: row 0 is o
    del row_ref
    slot = pl.program_id(0)
    n_slots = pl.num_programs(0)
    H, K, V = s_ref.shape[1:]
    live = lens_ref[slot] > 0

    @pl.when(live)
    def _():
        for h in range(H):
            at = pl.ds(h * V, V)
            a, k, q = (cols_ref[0, :, j * H + h:j * H + h + 1] for j in range(3))  # [K, 1]
            s = a * s_ref[0, h]
            u = rows_ref[0, 1:2, at] * (rows_ref[0, 0:1, at] - jnp.sum(s * k, axis=0, keepdims=True))
            s = s + k * u
            o_ref[0, h] = s
            y_ref[0, 0:1, at] = jnp.sum(s * q, axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    # With nobody live every step names row 0's block, which is then written
    # back once: give it what was read.
    @pl.when(lens_ref[n_slots] == 0)
    def _():
        o_ref[...] = s_ref[...]


# Under a jit of its own: a layer's call is then traced and lowered once a
# program, not once a layer and step of the window (sixty times).
@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_state_update(pool, base, lens, a, k, q, v, beta, *, interpret: bool = False):
    b = lens.shape[0]
    P, H, K, V = pool.shape
    live = lens > 0
    idx = jnp.arange(b, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(live, idx, -1))  # the nearest live slot at or before
    first = jnp.argmax(live).astype(jnp.int32)  # 0 where none is
    row_of = base + jnp.where(before >= 0, before, first)
    width = -(-3 * H // _LANES) * _LANES
    cols = jnp.concatenate([a, k, q], axis=1).transpose(0, 2, 1)  # [b, K, 3H]
    cols = jnp.pad(cols, ((0, 0), (0, 0), (0, width - 3 * H)))
    rows = jnp.stack([v.reshape(b, H * V), jnp.repeat(beta, V, axis=-1)], axis=1)
    rows = jnp.pad(rows, ((0, 0), (0, 6), (0, 0)))
    counted = jnp.concatenate([lens.astype(jnp.int32), jnp.sum(live, dtype=jnp.int32)[None]])

    def small(*block):
        return pl.BlockSpec((1,) + block, lambda s, row, lens: (s, 0, 0))

    state = pl.BlockSpec((1, H, K, V), lambda s, row, lens: (row[s], 0, 0, 0))
    pool, y = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[small(K, width), small(8, H * V), state],
            out_specs=[state, small(8, H * V)],
        ),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((b, 8, H * V), jnp.float32)],
        # Operands count the scalar-prefetch arguments: the pool is the fifth.
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="kda_state_update",
    )(row_of.astype(jnp.int32), counted, cols, rows, pool)
    return pool, y[:, 0].reshape(b, H, V)


def _tiles(pool) -> bool:
    """The kernel walks the heads one at a time, a head's state whole tiles:
    values a lane tile, keys whole sublane tiles."""
    _, H, K, V = pool.shape
    return pool.dtype == jnp.float32 and V % _LANES == 0 and K % 8 == 0


@jax.named_scope("kda.update")
def kda_update(pool, base, lens, a, k, q, v, beta):
    """The state update of one layer, one token a slot (module docstring): the
    kernel on a TPU where the shapes tile, else the plain form."""
    if _use_pallas() and _tiles(pool):
        return _kda_state_update(pool, base, lens, a, k, q, v, beta)
    return reference_kda_update(pool, base, lens, a, k, q, v, beta)


# ---------------------------------------------------------------------------
# A chunk call's tiles
# ---------------------------------------------------------------------------
#
# The token axis is ``n`` tiles of ``T`` tokens, tile ``t`` of pool row
# ``row[t]`` (a row past the pool: nobody's) with ``live[t]`` real tokens; a
# tile's state begins from nothing (``fresh``), from the tile before it
# (``cont``) or from its row of the pool, as ``ops/ssm.py`` has it. A padded
# token has ``g = 0`` and ``beta = 0``: no decay, and nothing of it enters.
#
# Inside a tile, with ``G_i`` the running sum of the log decays ``g`` since the
# tile began (a channel's, <= 0) and ``S_0`` what came in, the tokens' ``u``
# solve a unit lower triangular system and the rest are products::
#
#     (I + A) U = V - (K * exp(G)) S_0      A_ij = beta_j sum_c k_ic k_jc exp(G_ic - G_jc), j < i
#     O = (Q * exp(G)) S_0 + B U            B_ij = beta_j sum_c q_ic k_jc exp(G_ic - G_jc), j <= i
#     S_T = diag(exp(G_T)) S_0 + (K * exp(G_T - G) * beta)^T U
#
# ``exp(G_i - G_j)`` is a channel's, so it cannot leave the sum over channels
# as one factor a token, and as two (``exp(G_i)``, ``exp(-G_j)``) the second
# overflows: sixty-four steps of -5. The rows are taken in sub-tiles of
# ``_SUB``: for the rows of one sub-tile the running sum is split at the
# sub-tile's beginning ``r``, ``exp(G_i - G_r)`` (at most 1) with the row and
# ``exp(G_r - G_j)`` (at most exp(80) for a column inside the sub-tile, at most
# 1 before it, and never used behind it) with the column. ``(I + A)^-1`` is the
# product ``(I - A)(I + A^2)(I + A^4)..``: ``A`` is strictly lower, so the
# series ends. Everything float32 at ``highest``; only ``S_0`` goes from tile
# to tile, in a ``lax.scan`` with the pool as its carry (``ops/ssm.py``).


def _inverse_unit_lower(A):
    """(I + A)^-1 for strictly lower triangular A [.., T, T]."""
    T = A.shape[-1]
    eye = jnp.eye(T, dtype=A.dtype)
    power, inv = -A, eye - A
    for _ in range(max(0, math.ceil(math.log2(T)) - 1)):
        power = jnp.matmul(power, power, precision=_EXACT)
        inv = inv + jnp.matmul(inv, power, precision=_EXACT)
    return inv


@jax.jit
def reference_kda_chunk_scan(pool, row, fresh, cont, last, live, g, q, k, v, beta):
    """pool: [P, H, K, V] float32; row, live: [n] int32; fresh, cont, last: [n]
    bool; g (log decay, <= 0), q, k: [n, T, H, K]; v: [n, T, H, V]; beta: [n,
    T, H], all float32 → (pool', o [n, T, H, V] float32, zeros in a tile with
    no real token)."""
    del last  # a segment's later tiles overwrite its earlier ones' rows
    n, T, H, K = g.shape
    P = pool.shape[0]
    sub = math.gcd(T, _SUB)
    I = T // sub
    real = (jnp.arange(T)[None, :] < live[:, None])[:, :, None]
    g = jnp.where(real[..., None], g, 0.0)
    beta = jnp.where(real, beta, 0.0)
    G = jnp.cumsum(g, axis=1)  # [n, T, H, K]
    # The running sum where each sub-tile begins: 0, then the token's before it.
    G_r = jnp.concatenate([jnp.zeros_like(G[:, :1]), G[:, sub - 1:T - 1:sub]], axis=1)  # [n, I, H, K]
    row_f = jnp.exp(G.reshape(n, I, sub, H, K) - G_r[:, :, None])
    upto = jnp.arange(T)[None, :] < (jnp.arange(I)[:, None] + 1) * sub  # [I, T]: not behind the sub-tile
    col_f = jnp.exp(jnp.where(upto[None, :, :, None, None], G_r[:, :, None] - G[:, None], 0.0))
    kc = k[:, None] * col_f  # [n, I, T, H, K]

    def against_keys(x):  # [n, T, H, K] rows -> [n, H, T, T]
        out = jnp.einsum("nIihc,nIjhc->nhIij", x.reshape(n, I, sub, H, K) * row_f, kc,
                         precision=_EXACT)
        return out.reshape(n, H, T, T) * beta.transpose(0, 2, 1)[:, :, None, :]

    lower = jnp.tril(jnp.ones((T, T), bool))
    inv = _inverse_unit_lower(jnp.where(lower & ~jnp.eye(T, dtype=bool), against_keys(k), 0.0))
    B = jnp.where(lower, against_keys(q), 0.0)
    decayed = jnp.exp(G)
    # U = U0 - W S_0: what the tile's own values give, and what came in takes away.
    U0 = jnp.einsum("nhij,njhv->nhiv", inv, v, precision=_EXACT)
    W = jnp.einsum("nhij,njhc->nhic", inv, k * decayed, precision=_EXACT)
    q_in = (q * decayed).transpose(0, 2, 1, 3)  # [n, H, T, K]
    k_out = (k * jnp.exp(G[:, -1:] - G) * beta[..., None]).transpose(0, 2, 3, 1)  # [n, H, K, T]
    whole = jnp.exp(G[:, -1])  # [n, H, K]

    def tile(carry, t):
        pool, before = carry
        fresh_t, cont_t, row_t, mine_t, U0_t, W_t, B_t, q_t, k_t, whole_t = t
        stored = jax.lax.dynamic_index_in_dim(pool, row_t, axis=0, keepdims=False)
        came = jnp.where(fresh_t, 0.0, jnp.where(cont_t, before, stored))
        U = U0_t - jnp.matmul(W_t, came, precision=_EXACT)  # [H, T, V]
        o = jnp.matmul(q_t, came, precision=_EXACT) + jnp.matmul(B_t, U, precision=_EXACT)
        left = whole_t[..., None] * came + jnp.matmul(k_t, U, precision=_EXACT)
        pool = jax.lax.dynamic_update_index_in_dim(
            pool, jnp.where(mine_t, left, stored), row_t, axis=0)
        return (pool, left), o

    (pool, _), o = jax.lax.scan(
        tile, (pool, jnp.zeros(pool.shape[1:], pool.dtype)),
        (fresh, cont, jnp.minimum(row, P - 1), row < P, U0, W, B, q_in, k_out, whole))
    return pool, jnp.where((live > 0)[:, None, None, None], o.transpose(0, 2, 1, 3), 0.0)


@jax.named_scope("kda.scan")
def kda_chunk_scan(pool, row, fresh, cont, last, live, g, q, k, v, beta):
    """The recurrence of one layer over a chunk call's tiles (the comment
    above). The plain form everywhere: it has no kernel yet."""
    return reference_kda_chunk_scan(pool, row, fresh, cont, last, live, g, q, k, v, beta)
