"""The plain reference of the decoder with selecting and sliding latent attention:
float32, ``jax.numpy``, every matrix product under
``default_matmul_precision("highest")``; no cache, kernel or batching.

Every norm an RMSNorm (eps from the configuration); pre-norm residuals ``h +=
mixer(attn_norm(h))``, ``h += ffn(mlp_norm(h))``. Layer ``i`` is
``layer_types[i]``.

1. **Both kinds of mixer**, at their own sizes, ``x = attn_norm(h)`` at position
   ``t``: ``c_q = r_q q_norm(x W_dq)``; ``q = c_q W_uq``, per head ``[q_nope |
   RoPE(q_rope, t)]``; ``[c_kv | k_r] = x W_dkv``; ``c = r_kv kv_norm(c_kv)``;
   ``k_rope = RoPE(k_r, t)``, one for all heads; ``r = sqrt(hidden / rank)``.
   Attention in the EXPANDED form, a head at a time, in blocks of queries: per
   head ``[k_nope | v] = c W_ukv``, scores ``(q_nope . k_nope + q_rope . k_rope)
   / sqrt(nope + rope)`` over the positions the layer may read, softmax, ``o =
   sum p v``; ``o_j <- o_j sigmoid(x W_hg)_j``; ``a = concat(o) W_o``.
2. **What a full layer may read**: ``q^I = c_q W_iq`` (``index_heads`` heads of
   ``index_dim``), ``k^I = index_norm(x W_ik)``, the first ``rope`` numbers of
   each rotated to their position, ``w = (x W_iw) / sqrt(index_heads *
   index_dim)``; ``I(t, s) = sum_j w_j relu(q^I_j(t) . k^I(s))`` over ``s <= t``,
   made whole for a block of queries, a head at a time; the positions whose
   ``I`` is at least the ``topk``-th largest of the row (all of ``0..t`` while
   there are no more than ``topk``; a tie at that place, which rounding
   decides, takes both).
3. **What a sliding layer may read**: ``t - window < s <= t`` (a banded mask
   over the block's own stretch of keys).
4. **Feed-forward**: SwiGLU of ``ffn`` in the leading layers; else ``s =
   sigmoid(y W_r)`` over ALL routed experts, the ``per_token`` largest of ``s +
   expert_bias``, ``g = scale * s / sum of the chosen``, ``m = shared(y) + sum
   g_e expert_e(y)`` over the chosen experts THAT THIS SHARE HOLDS.
5. ``logits = final_norm(h) W_head``.

Departures from the published model, all stated in the configuration's file:
only the held experts add to ``m``, the vocabulary is the share's slice, the
rotary pairs are (i, i + d/2) as in ``reference.rope``, ``index_norm`` is an
RMSNorm, the indexer's Hadamard rotation is left out (orthogonal), the rescale
is read as LongCat-Flash's.

Nothing the program made enters here: weights come from
``weights_sparse_latent_moe`` and the seed, rounded to the configuration's
``weight_dtype`` and taken back to float32, one layer's attention, one dense
feed-forward or ONE expert at a time. A full layer's attention costs by its
mask, not by its list: a block of queries multiplies every key up to the end of
its group of blocks (``_GROUPS`` key widths a sequence, so that the first
quarter of a 34k sequence does not pay for all of it).

``quantize="int8"`` is the control, as in ``reference.py``: every weight matrix
but the router and the indexer's three rounded to int8 with a scale per output
channel, every activation that enters a rounded matrix to int8 with a scale
per token. The indexer reads the unrounded ``x`` and ``c_q``: the control then
selects nearly as the reference does, and what it fails by is the arithmetic
of attention and feed-forward, not a reshuffled list.

``select="recent"`` is a PLANTED FAULT, for the control that asks what the
comparison makes of a wrong selection: the full layers attend to the ``topk``
most recent positions in place of the indexer's choice, all else as it is.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import weights_sparse_latent_moe as W
from chipbench.reference import HIGHEST, _act, _fake_int8, rms_norm, rope
from chipbench.reference_latent_moe import (_capacity, _program, _run, held_counts, one_expert,
                                            swiglu)

BLOCK = 1024  # queries a block of the attention
_GROUPS = 4  # key widths a full layer's blocks are multiplied at
FULL_PRECISION = ("router", "expert_bias", "w_iq", "w_ik", "w_iw")  # what the control leaves alone
INDEX, RECENT = "index", "recent"  # what a full layer reads: the indexer's choice, or the planted fault


def _prepare(tree: dict, weight_dtype, quantize) -> dict:
    """Weights as the configuration holds them, back in float32, behind a
    barrier (``reference_latent_moe._prepare`` says why)."""
    if quantize not in (None, "int8"):
        raise ValueError(f"unknown control precision {quantize!r}")

    def one(name, x):
        x = x.astype(weight_dtype).astype(jnp.float32)
        matrix = not name.endswith("norm") and name != "embed"
        return _fake_int8(x) if quantize and matrix and name not in FULL_PRECISION else x
    return jax.lax.optimization_barrier({k: one(k, v) for k, v in tree.items()})


def _block(t: int) -> int:
    return BLOCK if t % BLOCK == 0 else t


def index_scores(qi, w, ki, q_pos):
    """``I`` of a block of queries (qi [b, heads, dim], w [b, heads], at
    positions ``q_pos`` [b]) against the keys ``ki`` [m, dim] of positions
    ``0..m-1``, ``-inf`` past a query's own: [b, m]. A head at a time: the
    scores of all heads at once would be ``index_heads`` times the tile."""
    def head(acc, args):
        qh, wh = args  # [b, dim], [b]
        return acc + wh[:, None] * jax.nn.relu(qh @ ki.T), None

    scores, _ = jax.lax.scan(head, jnp.zeros((qi.shape[0], ki.shape[0]), jnp.float32),
                             (qi.transpose(1, 0, 2), w.T))
    return jnp.where(q_pos[:, None] >= jnp.arange(ki.shape[0])[None, :], scores, -jnp.inf)


def selected(scores, topk: int):
    """The mask of a row's ``topk`` largest scores (``-inf``: never)."""
    kth = jax.lax.top_k(scores, min(topk, scores.shape[-1]))[0][:, -1:]
    return (scores >= kth) & (scores > -jnp.inf)


def _heads_attend(c_q, pos, w_uq, w_ukv, c, k_rope, mask, a: W.Attn, act):
    """A block of queries of every head against the keys ``c`` / ``k_rope``
    [m, .] under ``mask`` [b, m]: c_q [b, q_rank] the block's query latents at
    positions ``pos``; w_uq [H, q_rank, nope + rope] and w_ukv [H, kv_rank, nope +
    v] by head -> [H, b, v]. A head's queries, keys and values are made here, for
    the block, and nothing of ``heads`` times the sequence ever exists."""
    def one_head(args):
        wq, wkv = args
        q = act(c_q) @ wq
        qr = rope(q[:, None, a.nope:], pos, a.theta)[:, 0]
        up = c @ wkv
        s = (q[:, :a.nope] @ up[:, :a.nope].T + qr @ k_rope.T) * (a.nope + a.rope) ** -0.5
        return jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1) @ up[:, a.nope:]

    return jax.lax.map(one_head, (w_uq, w_ukv))


def attention(x, ap: dict, dims: W.Dims, kind: str, quantize=None, select=INDEX):
    """A layer's mixer for one sequence. x: [t, hidden] -> [t, hidden]. Two
    passes over blocks of tokens: what a later query reads of a token (its latent,
    its rotary key, in a full layer its index key), then a block of queries
    against them; beside ``x`` and the result only those live whole."""
    a = dims.attn(kind)
    t = x.shape[0]
    b = _block(t)
    act = _act(quantize)
    r_q, r_kv = (dims.hidden / a.q_rank) ** 0.5, (dims.hidden / a.kv_rank) ** 0.5
    full = kind == W.FULL
    w_uq = ap["w_uq"].reshape(a.q_rank, a.heads, a.nope + a.rope).transpose(1, 0, 2)
    w_ukv = ap["w_ukv"].reshape(a.kv_rank, a.heads, a.nope + a.v_dim).transpose(1, 0, 2)
    starts = jnp.arange(0, t, b)

    def rows(lo):
        """The block's normed inputs (unrounded) and positions."""
        return (rms_norm(jax.lax.dynamic_slice_in_dim(x, lo, b), ap["attn_norm"], dims.rms_eps),
                lo + jnp.arange(b))

    def rotated(v, pos):  # the first ``rope`` numbers of each [b, heads, dim] to their positions
        return jnp.concatenate([rope(v[..., :a.rope], pos, a.theta), v[..., a.rope:]], axis=-1)

    def keys_of(lo):
        h, pos = rows(lo)
        kv = act(h) @ ap["w_dkv"]
        c = act(r_kv * rms_norm(kv[:, :a.kv_rank], ap["kv_norm"], dims.rms_eps))
        k_rope = rope(kv[:, None, a.kv_rank:], pos, a.theta)[:, 0]
        if not full:
            return c, k_rope
        ki = rms_norm(h @ ap["w_ik"], ap["index_norm"], dims.rms_eps)
        return c, k_rope, rotated(ki[:, None, :], pos)[:, 0]

    cached = [v.reshape((t,) + v.shape[2:]) for v in jax.lax.map(keys_of, starts)]

    def queries_of(lo):
        h, pos = rows(lo)
        c_q = r_q * rms_norm(act(h) @ ap["w_dq"], ap["q_norm"], dims.rms_eps)
        return h, pos, c_q, jax.nn.sigmoid(act(h) @ ap["w_hg"])

    def finish(o, gate):  # o: [H, b, v] -> the block's rows of the result
        o = o.transpose(1, 0, 2) * gate[:, :, None]
        return act(o.reshape(b, a.heads * a.v_dim)) @ ap["wo"]

    out = []
    if full:
        c, k_rope, ki = cached
        n_blocks = t // b
        for g in range(_GROUPS):  # the blocks of a group share a key width: one program each
            first, last = -(-n_blocks * g // _GROUPS), -(-n_blocks * (g + 1) // _GROUPS)
            if first == last:
                continue
            keys = last * b

            def block(lo, keys=keys):
                h, pos, c_q, gate = queries_of(lo)
                if select == RECENT:
                    at = jnp.arange(keys)[None, :]
                    mask = (at <= pos[:, None]) & (at > pos[:, None] - dims.topk)
                else:
                    qi = rotated((c_q @ ap["w_iq"]).reshape(b, dims.index_heads, dims.index_dim), pos)
                    w = (h @ ap["w_iw"]) * (dims.index_heads * dims.index_dim) ** -0.5
                    mask = selected(index_scores(qi, w, ki[:keys], pos), dims.topk)
                return finish(_heads_attend(c_q, pos, w_uq, w_ukv, c[:keys], k_rope[:keys], mask, a, act),
                              gate)

            out.append(jax.lax.map(block, starts[first:last]).reshape((last - first) * b, -1))
    else:
        c, k_rope = cached
        reach = dims.window - 1  # keys before a block's first query that it may read
        c_p = jnp.pad(c, ((reach, 0), (0, 0)))
        k_p = jnp.pad(k_rope, ((reach, 0), (0, 0)))

        def block(lo):
            _h, pos, c_q, gate = queries_of(lo)
            at = lo - reach + jnp.arange(b + reach)  # the stretch's positions; below 0: padding
            mask = (at[None, :] <= pos[:, None]) & (at[None, :] > pos[:, None] - dims.window) & (at[None, :] >= 0)
            return finish(_heads_attend(
                c_q, pos, w_uq, w_ukv, jax.lax.dynamic_slice_in_dim(c_p, lo, b + reach),
                jax.lax.dynamic_slice_in_dim(k_p, lo, b + reach), mask, a, act), gate)

        out.append(jax.lax.map(block, starts).reshape(t, -1))
    return jnp.concatenate(out)


def _ffn_rows(y, fp: dict, quantize):
    """The dense feed-forward a block of tokens at a time: its two products of
    ``ffn`` numbers a token would be 1.9 GB each at 34k tokens."""
    t = y.shape[0]
    b = _block(t)
    return jax.lax.map(lambda rows: swiglu(rows, fp["w_gate"], fp["w_up"], fp["w_down"], quantize),
                       y.reshape(t // b, b, -1)).reshape(y.shape)


def route(y, mp: dict, dims: W.Dims):
    """y: [t, hidden] -> (experts [t, k] among ALL routed experts, gates [t, k]):
    chosen on ``score + expert_bias``, weighed by the scores alone."""
    scores = jax.nn.sigmoid(y @ mp["router"])
    experts = jax.lax.top_k(scores + mp["expert_bias"], dims.per_token)[1]
    top = jnp.take_along_axis(scores, experts, axis=-1)
    return experts, dims.scale * top / jnp.sum(top, axis=-1, keepdims=True)


# --- piece by piece from the seed -------------------------------------------
_static = ("dims", "weight_dtype", "quantize")


@functools.partial(jax.jit, static_argnames=_static)
def _embed(key, tokens, dims, weight_dtype, quantize):
    return _prepare(W.top_params(key, dims), weight_dtype, quantize)["embed"][tokens]


@functools.partial(jax.jit, static_argnames=_static + ("kind", "select"), donate_argnums=(2,))
def _attn_block(key, index, x, dims, weight_dtype, quantize, kind, select=INDEX):
    """x -> (h after the mixer, y = mlp_norm(h))."""
    if select not in (INDEX, RECENT):
        raise ValueError(f"unknown selection {select!r}")
    with jax.default_matmul_precision(HIGHEST):
        ap = _prepare(W.attn_params(key, index, dims, kind), weight_dtype, quantize)
        h = x + attention(x, ap, dims, kind, quantize, select)
        return h, rms_norm(h, ap["mlp_norm"], dims.rms_eps)


@functools.partial(jax.jit, static_argnames=_static, donate_argnums=(2,))
def _dense_block(key, index, h, y, dims, weight_dtype, quantize):
    with jax.default_matmul_precision(HIGHEST):
        fp = _prepare(W.dense_params(key, index, dims), weight_dtype, quantize)
        return h + _ffn_rows(y, fp, quantize)


@functools.partial(jax.jit, static_argnames=_static)
def _shared_and_route(key, index, y, dims, weight_dtype, quantize):
    """-> (the shared expert's output, experts, gates, tokens a held expert)."""
    with jax.default_matmul_precision(HIGHEST):
        mp = _prepare(W.moe_params(key, index, dims), weight_dtype, quantize)
        experts, gates = route(y, mp, dims)
        shared = swiglu(y, mp["shared_gate"], mp["shared_up"], mp["shared_down"], quantize)
        return shared, experts, gates, held_counts(experts, dims)


@functools.partial(jax.jit, static_argnames=_static + ("cap",))
def _routed(key, index, y, experts, gates, dims, weight_dtype, quantize, cap):
    """The held experts' weighted outputs, an expert at a time."""
    with jax.default_matmul_precision(HIGHEST):
        def body(m, e):
            ep = _prepare(W.expert_params(key, index, e, dims), weight_dtype, quantize)
            weight = jnp.sum(jnp.where(experts == e, gates, 0.0), axis=-1)
            return m + one_expert(y, ep, weight, cap, quantize), None

        return jax.lax.scan(body, jnp.zeros_like(y), dims.held_first + jnp.arange(dims.held))[0]


@functools.partial(jax.jit, static_argnames=_static, donate_argnums=(0, 1))
def _add(h, part, dims, weight_dtype, quantize):
    """The residual plus one part of the expert layer's result, in the place of
    both: at 34k tokens each is 0.7 GB beside an engine of 12."""
    return h + part


@functools.partial(jax.jit, static_argnames=_static)
def _head(key, x, positions, dims, weight_dtype, quantize):
    """Logits of the hidden states ``x`` [t, hidden] at ``positions`` [m]."""
    with jax.default_matmul_precision(HIGHEST):
        top = _prepare(W.top_params(key, dims), weight_dtype, quantize)
        x = rms_norm(x[positions], top["final_norm"], dims.rms_eps)
        return _act(quantize)(x) @ top["lm_head"]


def precompile(dims: W.Dims, weight_dtype, t: int, m: int) -> None:
    """Compile every piece for sequences of ``t`` tokens judged at ``m``
    positions, running nothing: the replica calls this beside its own set-up,
    so that the check after the window finds its programs made."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    key, index = sds((2,), jnp.uint32), sds((), jnp.int32)
    x = sds((t, dims.hidden), jnp.float32)
    args = dict(dims=dims, weight_dtype=weight_dtype, quantize=None)
    _program(_embed, key, sds((t,), jnp.int32), **args)
    for kind in sorted(set(dims.layer_types)):
        _program(_attn_block, key, index, x, **args, kind=kind)
    _program(_dense_block, key, index, x, x, **args)
    _program(_shared_and_route, key, index, x, **args)
    pairs = (sds((t, dims.per_token), jnp.int32), sds((t, dims.per_token), jnp.float32))
    for most in (0, t // 9 + 1):  # the usual capacity and the next above it
        _program(_routed, key, index, x, *pairs, **args, cap=_capacity(most, t))
    _program(_add, x, x, **args)
    _program(_head, key, x, sds((m,), jnp.int32), **args)


def expert_ffn(key, index: int, y, dims: W.Dims, weight_dtype, quantize=None):
    """``m`` of expert layer ``index`` for one sequence's normed hidden states
    ``y`` [t, hidden] -> (shared expert's part, this share's routed part)."""
    args = dict(dims=dims, weight_dtype=weight_dtype, quantize=quantize)
    shared, experts, gates, counts = _run(_shared_and_route, key, jnp.int32(index), y, **args)
    routed = _run(_routed, key, jnp.int32(index), y, experts, gates, **args,
                  cap=_capacity(counts.max(), y.shape[0]))
    return shared, routed


def hidden_states(key, tokens, dims: W.Dims, weight_dtype, quantize=None, select=INDEX):
    """Final hidden states (before the last norm) of ONE sequence [t]."""
    args = dict(dims=dims, weight_dtype=weight_dtype, quantize=quantize)
    fault = {} if select == INDEX else {"select": select}  # the usual program is ``precompile``'s
    x = _run(_embed, key, tokens, **args)
    for i, kind in enumerate(dims.layer_types):
        h, y = _run(_attn_block, key, jnp.int32(i), x, **args, kind=kind, **fault)
        if i < dims.lead:
            x = _run(_dense_block, key, jnp.int32(i), h, y, **args)
        else:
            shared, experts, gates, counts = _run(_shared_and_route, key, jnp.int32(i), y, **args)
            h = _run(_add, h, shared, **args)
            routed = _run(_routed, key, jnp.int32(i), y, experts, gates, **args,
                          cap=_capacity(counts.max(), y.shape[0]))
            x = _run(_add, h, routed, **args)
    return x


def stream_logits(key, tokens, dims: W.Dims, weight_dtype, quantize=None, positions=None,
                  select=INDEX):
    """Logits of ``tokens`` [n, t] (padded on the right: causal attention keeps
    padding out of earlier positions), at every position or, with ``positions``
    [n, m], at those alone: [n, m, vocab]. A sequence at a time: memory."""
    out = []
    for i in range(tokens.shape[0]):
        x = hidden_states(key, tokens[i], dims, weight_dtype, quantize, select)
        at = jnp.arange(x.shape[0], dtype=jnp.int32) if positions is None else positions[i]
        out.append(_run(_head, key, x, at, dims=dims, weight_dtype=weight_dtype, quantize=quantize))
    return jnp.stack(out)
