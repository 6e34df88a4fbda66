"""The plain reference of the latent-attention expert decoder: float32,
``jax.numpy``, every matrix product under ``default_matmul_precision("highest")``.

One layer, for a token's hidden state ``h`` at position ``t`` (RMSNorm, eps from
the configuration):

1. ``x = attn_norm(h)``; ``c_q = q_norm(x W_dq)``; ``q = c_q W_uq``: per head
   ``[q_nope | q_rope]``; ``q_rope = RoPE(q_rope, t)``.
2. ``[c_kv | k_r] = x W_dkv``; ``c = kv_norm(c_kv)``; ``k_rope = RoPE(k_r, t)``, one
   for all heads.
3. Attention in the EXPANDED form, a head at a time, in blocks of queries: per
   head ``[k_nope | v] = c W_ukv``, scores ``(q_nope . k_nope + q_rope . k_rope) /
   sqrt(nope + rope)`` over ``s <= t``, softmax, ``o = sum p v``; ``a = concat(o) W_o``.
4. ``h = h + post_attn_norm(a)``; ``y = mlp_norm(h)``; ``h = h + post_mlp_norm(m)``.
5. ``m``: SwiGLU of ``intermediate_size`` in the leading dense layers; else ``s =
   sigmoid(y W_r)`` over ALL routed experts, the ``num_experts_per_tok`` largest,
   ``g = routed_scaling_factor * s / sum of the chosen``, ``m = shared(y) + sum g_e
   expert_e(y)`` over the chosen experts THAT THIS SHARE HOLDS.
6. ``logits = final_norm(h) W_head``.

Departures from the published model, all of the configuration's cut and stated
in its file: only the held experts add to ``m`` (what the others would have
added is left out, as in the program; with every expert held this is the whole
layer), the vocabulary is the share's slice, the rotary pairs are (i, i + d/2)
as in ``reference.rope`` (with random weights a permutation of columns), no
``mscale`` on the softmax scale (the source has no ``rope_scaling``).

Nothing the program made enters here: weights come from ``weights_latent_moe``
and the seed, rounded to the configuration's ``weight_dtype`` and taken back to
float32, one layer's attention, one dense feed-forward or ONE expert at a time
(an expert layer whole is 4 GB in float32, beside an engine of 12 GB). An
expert multiplies only the tokens routed to it, gathered to a static capacity
that is chosen from the layer's own counts, so none is ever dropped.

``quantize="int8"`` is the control, as in ``reference.py``: every weight matrix
but the router rounded to int8 with a scale per output channel, every
activation that enters one of them to int8 with a scale per token.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import weights_latent_moe as W
from chipbench.reference import HIGHEST, _act, _fake_int8, rms_norm, rope

BLOCK = 1024  # queries a block of the attention
FULL_PRECISION = ("router",)  # matrices the control leaves alone


def _prepare(tree: dict, weight_dtype, quantize) -> dict:
    """Weights as the configuration holds them, back in float32. Behind a
    barrier: left free, the compiler draws a matrix's random numbers inside
    the product that reads it, tile by tile (the head's program took 30 s to
    compile so, and 4 s with the matrix made first)."""
    if quantize not in (None, "int8"):
        raise ValueError(f"unknown control precision {quantize!r}")

    def one(name, x):
        x = x.astype(weight_dtype).astype(jnp.float32)
        matrix = not name.endswith("norm") and name != "embed"
        return _fake_int8(x) if quantize and matrix and name not in FULL_PRECISION else x
    return jax.lax.optimization_barrier({k: one(k, v) for k, v in tree.items()})


def attention(x, ap: dict, dims: W.Dims, quantize=None):
    """The attention sublayer's output ``a`` for one sequence. x: [t, hidden]."""
    t = x.shape[0]
    pos = jnp.arange(t)
    act = _act(quantize)
    h = act(rms_norm(x, ap["attn_norm"], dims.rms_eps))
    c_q = act(rms_norm(h @ ap["w_dq"], ap["q_norm"], dims.rms_eps))
    q = (c_q @ ap["w_uq"]).reshape(t, dims.heads, dims.nope + dims.rope)
    q_nope, q_rope = q[..., :dims.nope], rope(q[..., dims.nope:], pos, dims.rope_theta)
    kv = h @ ap["w_dkv"]
    c = act(rms_norm(kv[:, :dims.kv_rank], ap["kv_norm"], dims.rms_eps))
    k_rope = rope(kv[:, None, dims.kv_rank:], pos, dims.rope_theta)[:, 0]
    scale = (dims.nope + dims.rope) ** -0.5

    def one_head(args):
        qn, qr, w = args  # [t, nope], [t, rope], [kv_rank, nope + v]
        up = c @ w
        k_nope, v = up[:, :dims.nope], up[:, dims.nope:]
        out = []
        for lo in range(0, t, BLOCK):
            hi = min(t, lo + BLOCK)
            s = (qn[lo:hi] @ k_nope[:hi].T + qr[lo:hi] @ k_rope[:hi].T) * scale
            s = jnp.where(pos[lo:hi, None] >= pos[None, :hi], s, -jnp.inf)
            out.append(jax.nn.softmax(s, axis=-1) @ v[:hi])
        return jnp.concatenate(out)

    w_ukv = ap["w_ukv"].reshape(dims.kv_rank, dims.heads, dims.nope + dims.v_dim)
    o = jax.lax.map(one_head, (q_nope.transpose(1, 0, 2), q_rope.transpose(1, 0, 2),
                               w_ukv.transpose(1, 0, 2)))  # [heads, t, v]
    return act(o.transpose(1, 0, 2).reshape(t, dims.heads * dims.v_dim)) @ ap["wo"]


def swiglu(y, gate, up, down, quantize=None):
    act = _act(quantize)
    y = act(y)
    return act(jax.nn.silu(y @ gate) * (y @ up)) @ down


def route(y, router, dims: W.Dims):
    """y: [t, hidden] -> (experts [t, k] among ALL routed experts, gates [t, k])."""
    scores = jax.nn.sigmoid(y @ router)
    top, experts = jax.lax.top_k(scores, dims.per_token)
    return experts, dims.scale * top / jnp.sum(top, axis=-1, keepdims=True)


def held_counts(experts, dims: W.Dims):
    """Tokens routed to each held expert: [held]."""
    held = dims.held_first + jnp.arange(dims.held)
    return jnp.sum(experts[:, :, None] == held[None, None, :], axis=(0, 1))


def one_expert(y, ep: dict, weight, cap: int, quantize=None):
    """One expert's weighted part of ``m``: ``weight`` [t] is the token's gate
    for it (0 where it was not chosen); the at most ``cap`` chosen tokens are
    gathered, multiplied and added back where they came from."""
    t = y.shape[0]
    idx = jnp.nonzero(weight > 0, size=cap, fill_value=t)[0]
    at = jnp.minimum(idx, t - 1)
    out = swiglu(y[at], ep["e_gate"], ep["e_up"], ep["e_down"], quantize)
    out = out * jnp.where(idx < t, weight[at], 0.0)[:, None]
    return jnp.zeros_like(y).at[at].add(out)


# --- piece by piece from the seed -------------------------------------------
_static = ("dims", "weight_dtype", "quantize")
_PROGRAMS: dict = {}


def _program(piece, *args, **static):
    """The compiled program of one jitted piece for arguments of these shapes,
    made once a process. Compiled ahead of the call, because ``precompile``
    has only shapes to give, and a program compiled from shapes is not found
    again by the piece's own call (it would compile a second time)."""
    key = (piece, tuple((tuple(a.shape), jnp.dtype(a.dtype).name) for a in args),
           tuple(sorted(static.items(), key=lambda kv: kv[0])))
    if key not in _PROGRAMS:
        _PROGRAMS[key] = piece.lower(*args, **static).compile()
    return _PROGRAMS[key]


def _run(piece, *args, **static):
    return _program(piece, *args, **static)(*args)


@functools.partial(jax.jit, static_argnames=_static)
def _embed(key, tokens, dims, weight_dtype, quantize):
    return _prepare(W.top_params(key, dims), weight_dtype, quantize)["embed"][tokens]


@functools.partial(jax.jit, static_argnames=_static, donate_argnums=(2,))
def _attn_block(key, index, x, dims, weight_dtype, quantize):
    """x -> (h after the attention sublayer, y = mlp_norm(h))."""
    with jax.default_matmul_precision(HIGHEST):
        ap = _prepare(W.attn_params(key, index, dims), weight_dtype, quantize)
        h = x + rms_norm(attention(x, ap, dims, quantize), ap["post_attn_norm"], dims.rms_eps)
        return h, rms_norm(h, ap["mlp_norm"], dims.rms_eps)


@functools.partial(jax.jit, static_argnames=_static, donate_argnums=(2,))
def _dense_block(key, index, h, y, dims, weight_dtype, quantize):
    with jax.default_matmul_precision(HIGHEST):
        fp = _prepare(W.dense_params(key, index, dims), weight_dtype, quantize)
        m = swiglu(y, fp["w_gate"], fp["w_up"], fp["w_down"], quantize)
        return h + rms_norm(m, W.norm_params(dims)["post_mlp_norm"], dims.rms_eps)


@functools.partial(jax.jit, static_argnames=_static)
def _shared_and_route(key, index, y, dims, weight_dtype, quantize):
    """-> (the shared expert's output, experts, gates, tokens a held expert)."""
    with jax.default_matmul_precision(HIGHEST):
        mp = _prepare(W.moe_params(key, index, dims), weight_dtype, quantize)
        experts, gates = route(y, mp["router"], dims)
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


@functools.partial(jax.jit, static_argnames=_static, donate_argnums=(0,))
def _finish(h, shared, routed, dims, weight_dtype, quantize):
    return h + rms_norm(shared + routed, W.norm_params(dims)["post_mlp_norm"], dims.rms_eps)


@functools.partial(jax.jit, static_argnames=_static)
def _head(key, x, positions, dims, weight_dtype, quantize):
    """Logits of the hidden states ``x`` [t, hidden] at ``positions`` [m]."""
    with jax.default_matmul_precision(HIGHEST):
        top = _prepare(W.top_params(key, dims), weight_dtype, quantize)
        x = rms_norm(x[positions], top["final_norm"], dims.rms_eps)
        return _act(quantize)(x) @ top["lm_head"]


def _capacity(most: int, t: int) -> int:
    """The static capacity of an expert's gather: a ninth of the sequence (the
    mean share of an expert is 1/32) or, where the layer's fullest expert
    holds more, the next power of two above it: no token is ever dropped, and
    one size serves nearly every layer."""
    usual = -(-t // 9)
    return usual if most <= usual else min(t, 1 << (int(most) - 1).bit_length())


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
    _program(_attn_block, key, index, x, **args)
    _program(_dense_block, key, index, x, x, **args)
    _program(_shared_and_route, key, index, x, **args)
    pairs = (sds((t, dims.per_token), jnp.int32), sds((t, dims.per_token), jnp.float32))
    for most in (0, t // 9 + 1):  # the usual capacity and the next above it
        _program(_routed, key, index, x, *pairs, **args, cap=_capacity(most, t))
    _program(_finish, x, x, x, **args)
    _program(_head, key, x, sds((m,), jnp.int32), **args)


def expert_ffn(key, index: int, y, dims: W.Dims, weight_dtype, quantize=None):
    """``m`` of expert layer ``index`` for one sequence's normed hidden states
    ``y`` [t, hidden] -> (shared expert's part, this share's routed part)."""
    args = dict(dims=dims, weight_dtype=weight_dtype, quantize=quantize)
    shared, experts, gates, counts = _run(_shared_and_route, key, jnp.int32(index), y, **args)
    routed = _run(_routed, key, jnp.int32(index), y, experts, gates, **args,
                  cap=_capacity(counts.max(), y.shape[0]))
    return shared, routed


def hidden_states(key, tokens, dims: W.Dims, weight_dtype, quantize=None):
    """Final hidden states (before the last norm) of ONE sequence [t]."""
    args = dict(dims=dims, weight_dtype=weight_dtype, quantize=quantize)
    x = _run(_embed, key, tokens, **args)
    for i in range(dims.layers):
        h, y = _run(_attn_block, key, jnp.int32(i), x, **args)
        if i < dims.lead:
            x = _run(_dense_block, key, jnp.int32(i), h, y, **args)
        else:
            shared, routed = expert_ffn(key, i, y, dims, weight_dtype, quantize)
            x = _run(_finish, h, shared, routed, **args)
    return x


def stream_logits(key, tokens, dims: W.Dims, weight_dtype, quantize=None, positions=None):
    """Logits of ``tokens`` [n, t] (padded on the right: causal attention keeps
    padding out of earlier positions), at every position or, with ``positions``
    [n, m], at those alone: [n, m, vocab]. A sequence at a time: memory."""
    out = []
    for i in range(tokens.shape[0]):
        x = hidden_states(key, tokens[i], dims, weight_dtype, quantize)
        at = jnp.arange(x.shape[0], dtype=jnp.int32) if positions is None else positions[i]
        out.append(_run(_head, key, x, at, dims=dims, weight_dtype=weight_dtype, quantize=quantize))
    return jnp.stack(out)
