"""The plain reference of the multi-stream latent-attention expert decoder
(``xing4_0``): float32, ``jax.numpy``, every matrix product under
``default_matmul_precision("highest")``.

A token's state is ``X`` in ``R^{n x C}`` (``n = hc_mult`` residual streams). The
model: ``X_0`` = ``n`` copies of the token's embedding; 40 layers, each an attention
sublayer and then a feed-forward sublayer; ``h = sum_i X_i``; ``logits = final_norm(h)
W_head``. One sublayer ``F`` with its own map parameters (``phi`` [n C, 2n + n^2],
``alpha`` = (a_pre, a_post, a_res), ``b_pre``, ``b_post`` [n], ``b_res`` [n, n]),
written for ONE token (``maps``, ``mix_in``, ``mix_out``; a sequence is a ``vmap``):

1. ``x^ = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)`` (one RMS over all ``n C`` numbers,
   no gain).
2. ``[p | q | r] = x^ phi``; ``H~pre = a_pre p + b_pre``; ``H~post = a_post q + b_post``;
   ``H~res = a_res mat(r) + b_res`` (``mat``: row-major ``n x n``).
3. ``Hpre = sigmoid(H~pre)``; ``Hpost = 2 sigmoid(H~post)``; ``Hres = SK(clip(H~res,
   clamp_min, clamp_max))``: ``M = exp(.)``, then ``hc_sinkhorn_iters`` times: every
   column over its sum, then every row over its sum (``hc_eps`` beside each sum).
4. ``u = Hpre X``; ``y = F(norm(u))``.
5. ``X' = Hres X + Hpost^T y``. No other addition.

``F`` is latent attention after ``attn_norm`` in the EXPANDED form
(``reference_latent_moe``'s steps 1-3, a head at a time, in blocks of queries) with
YaRN: each of the ``rope / 2`` rotary frequencies blended between itself and itself /
``factor`` by the linear ramp of ``beta_fast`` and ``beta_slow`` over the original
positions, cos and sin unscaled (``mscale == mscale_all_dim``), the softmax scale
``(0.1 ln factor + 1)^2 / sqrt(nope + rope)``; and after ``mlp_norm`` a SwiGLU of
``intermediate_size`` in the leading dense layers, else ``s = sigmoid(y W_r)`` over ALL
routed experts, the ``num_experts_per_tok`` largest of ``s + expert_bias``, ``g = scale *
s / sum of the chosen``, ``m = shared(y) + sum g_e expert_e(y)`` over the chosen experts
THAT THIS SHARE HOLDS. No sandwich norm.

Departures from the published model, all of the configuration's cut and stated in
its file: only the held experts add to ``m``, the vocabulary is the share's slice, the
rotary pairs are (i, i + d/2) as in ``reference.rope``, no multi-token-prediction
module. What the source's ``config.json`` does not say (the order inside a Sinkhorn
round, the place of ``hc_eps``, the copies at the start and the sum at the end, the
norm without gain) is in the file's ``assumed``.

Nothing the program made enters here: weights come from ``weights_hyper_latent_moe``
and the seed, rounded to the configuration's ``weight_dtype`` and taken back to float32
(the maps' parameters are float32 as they are), one sublayer or ONE expert at a time,
and nothing of ``ray_tpu`` is imported.

``quantize="int8"`` is the control, as in ``reference_latent_moe.py`` (the router, its
bias and the maps left in full precision). ``residual="plain"`` is a PLANTED FAULT of the
mechanism: ``Hres`` the identity and ``Hpre = Hpost = 1`` whatever the token, the residual
path every other model has.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench import weights_hyper_latent_moe as W
from chipbench.reference import HIGHEST, _act, rms_norm
from chipbench.reference_latent_moe import (BLOCK, _capacity, _program, _routed, _run, held_counts,
                                            swiglu)
# Weights back in float32 behind a barrier; its control leaves ``router`` and
# ``expert_bias`` alone. The maps' parameters never pass through it: float32 as drawn.
from chipbench.reference_sparse_latent_moe import _prepare

MHC, PLAIN = "mhc", "plain"  # the residual path: the model's, or the planted fault


# --- the streams: one token ---------------------------------------------------
def sinkhorn(logits, iters: int, eps: float):
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)  # every column over its sum
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)  # every row over its sum
    return m


def maps(X, hp: dict, dims: W.Dims, residual: str = MHC):
    """One token's streams X [n, C] -> (Hpre [n], Hpost [n], Hres [n, n])."""
    n = dims.streams
    if residual == PLAIN:
        return jnp.ones((n,)), jnp.ones((n,)), jnp.eye(n)
    x = X.reshape(-1)
    x = x / jnp.sqrt(jnp.mean(jnp.square(x)) + dims.hc_eps)
    h = x @ hp["phi"]
    a_pre, a_post, a_res = hp["alpha"]
    pre = jax.nn.sigmoid(a_pre * h[:n] + hp["b_pre"])
    post = 2.0 * jax.nn.sigmoid(a_post * h[n:2 * n] + hp["b_post"])
    res = jnp.clip(a_res * h[2 * n:].reshape(n, n) + hp["b_res"], dims.clamp_min, dims.clamp_max)
    return pre, post, sinkhorn(res, dims.sinkhorn_iters, dims.hc_eps)


def mix_in(pre, X):
    return pre @ X  # [C]


def mix_out(res, post, X, y):
    return res @ X + post[:, None] * y[None, :]  # [n, C]


def sublayer(X, hp: dict, dims: W.Dims, residual: str, f):
    """X [t, n, C] -> X' through one sublayer ``f`` ([t, C] -> [t, C])."""
    pre, post, res = jax.vmap(lambda x: maps(x, hp, dims, residual))(X)
    y = f(jax.vmap(mix_in)(pre, X))
    return jax.vmap(mix_out)(res, post, X, y)


# --- attention ----------------------------------------------------------------
def rope_frequencies(dims: W.Dims):
    """The ``rope / 2`` frequencies as YaRN blends them (static, whatever the length)."""
    dim, base = dims.rope, dims.rope_theta
    half = dim // 2
    own = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if dims.rope_factor == 1:
        return own

    def pair_that_turns(rotations):
        return dim * math.log(dims.rope_original / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(pair_that_turns(dims.beta_fast)), 0)
    high = min(math.ceil(pair_that_turns(dims.beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low) / ((high - low) or 0.001), 0, 1)
    return own * (1 - ramp) + own / dims.rope_factor * ramp


def softmax_scale(dims: W.Dims) -> float:
    mscale = 1.0 if dims.rope_factor <= 1 else 0.1 * dims.mscale_all_dim * math.log(dims.rope_factor) + 1.0
    return mscale * mscale / math.sqrt(dims.nope + dims.rope)


def rope(x, positions, freqs):
    """x: [t, heads, head_dim]; pairs are (i, i + head_dim/2), as ``reference.rope``."""
    half = x.shape[-1] // 2
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(u, ap: dict, dims: W.Dims, quantize=None):
    """The attention sublayer's ``F(norm(u))`` for one sequence. u: [t, hidden]."""
    if dims.mscale != dims.mscale_all_dim:
        raise ValueError("cos and sin are left unscaled: mscale must equal mscale_all_dim")
    t = u.shape[0]
    pos = jnp.arange(t)
    act = _act(quantize)
    freqs = rope_frequencies(dims)
    h = act(rms_norm(u, ap["attn_norm"], dims.rms_eps))
    c_q = act(rms_norm(h @ ap["w_dq"], ap["q_norm"], dims.rms_eps))
    q = (c_q @ ap["w_uq"]).reshape(t, dims.heads, dims.nope + dims.rope)
    q_nope, q_rope = q[..., :dims.nope], rope(q[..., dims.nope:], pos, freqs)
    kv = h @ ap["w_dkv"]
    c = act(rms_norm(kv[:, :dims.kv_rank], ap["kv_norm"], dims.rms_eps))
    k_rope = rope(kv[:, None, dims.kv_rank:], pos, freqs)[:, 0]
    scale = softmax_scale(dims)

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


def route(y, mp: dict, dims: W.Dims):
    """y: [t, hidden] -> (experts [t, k] among ALL routed experts, gates [t, k]): chosen
    on ``score + expert_bias`` (one group), weighed by the scores alone."""
    scores = jax.nn.sigmoid(y @ mp["router"])
    experts = jax.lax.top_k(scores + mp["expert_bias"], dims.per_token)[1]
    top = jnp.take_along_axis(scores, experts, axis=-1)
    return experts, dims.scale * top / jnp.sum(top, axis=-1, keepdims=True)


# --- piece by piece from the seed ---------------------------------------------
_static = ("dims", "weight_dtype", "quantize")
_mixed = _static + ("residual",)


@functools.partial(jax.jit, static_argnames=_static)
def _embed(key, tokens, dims, weight_dtype, quantize):
    """The streams at the start: ``n`` copies of each token's embedding, [t, n, C]."""
    x = _prepare(W.top_params(key, dims), weight_dtype, quantize)["embed"][tokens]
    return jnp.broadcast_to(x[:, None, :], (x.shape[0], dims.streams, x.shape[1]))


@functools.partial(jax.jit, static_argnames=_mixed, donate_argnums=(2,))
def _attn_block(key, index, X, dims, weight_dtype, quantize, residual=MHC):
    with jax.default_matmul_precision(HIGHEST):
        ap = _prepare(W.attn_params(key, index, dims), weight_dtype, quantize)
        hp = W.hc_params(key, index, 0, dims)
        return sublayer(X, hp, dims, residual, lambda u: attention(u, ap, dims, quantize))


@functools.partial(jax.jit, static_argnames=_mixed, donate_argnums=(2,))
def _dense_block(key, index, X, dims, weight_dtype, quantize, residual=MHC):
    with jax.default_matmul_precision(HIGHEST):
        fp = _prepare({**W.dense_params(key, index, dims),
                       "mlp_norm": W.attn_params(key, index, dims)["mlp_norm"]}, weight_dtype, quantize)
        hp = W.hc_params(key, index, 1, dims)
        return sublayer(X, hp, dims, residual, lambda u: swiglu(
            rms_norm(u, fp["mlp_norm"], dims.rms_eps), fp["w_gate"], fp["w_up"], fp["w_down"], quantize))


@functools.partial(jax.jit, static_argnames=_mixed)
def _shared_and_route(key, index, X, dims, weight_dtype, quantize, residual=MHC):
    """-> (y = mlp_norm(Hpre X), the shared expert's output, experts, gates, tokens a
    held expert)."""
    with jax.default_matmul_precision(HIGHEST):
        mp = _prepare({**W.moe_params(key, index, dims),
                       "mlp_norm": W.attn_params(key, index, dims)["mlp_norm"]}, weight_dtype, quantize)
        hp = W.hc_params(key, index, 1, dims)
        pre, _, _ = jax.vmap(lambda x: maps(x, hp, dims, residual))(X)
        y = rms_norm(jax.vmap(mix_in)(pre, X), mp["mlp_norm"], dims.rms_eps)
        experts, gates = route(y, mp, dims)
        shared = swiglu(y, mp["shared_gate"], mp["shared_up"], mp["shared_down"], quantize)
        return y, shared, experts, gates, held_counts(experts, dims)


@functools.partial(jax.jit, static_argnames=_mixed, donate_argnums=(2,))
def _mix_out(key, index, X, shared, routed, dims, weight_dtype, quantize, residual=MHC):
    """The expert layer's result back into the streams (the maps made again from ``X``:
    they are a function of it)."""
    with jax.default_matmul_precision(HIGHEST):
        hp = W.hc_params(key, index, 1, dims)
        _, post, res = jax.vmap(lambda x: maps(x, hp, dims, residual))(X)
        return jax.vmap(mix_out)(res, post, X, shared + routed)


@functools.partial(jax.jit, static_argnames=_static)
def _head(key, X, positions, dims, weight_dtype, quantize):
    """Logits of the streams ``X`` [t, n, C] at ``positions`` [m]."""
    with jax.default_matmul_precision(HIGHEST):
        top = _prepare(W.top_params(key, dims), weight_dtype, quantize)
        h = rms_norm(jnp.sum(X[positions], axis=1), top["final_norm"], dims.rms_eps)
        return _act(quantize)(h) @ top["lm_head"]


def precompile(dims: W.Dims, weight_dtype, t: int, m: int) -> None:
    """Compile every piece for sequences of ``t`` tokens judged at ``m`` positions,
    running nothing: the replica calls this beside its own set-up, so that the check
    after the window finds its programs made."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    key, index = sds((2,), jnp.uint32), sds((), jnp.int32)
    y = sds((t, dims.hidden), jnp.float32)
    X = sds((t, dims.streams, dims.hidden), jnp.float32)
    args = dict(dims=dims, weight_dtype=weight_dtype, quantize=None)
    _program(_embed, key, sds((t,), jnp.int32), **args)
    _program(_attn_block, key, index, X, **args)
    _program(_dense_block, key, index, X, **args)
    _program(_shared_and_route, key, index, X, **args)
    pairs = (sds((t, dims.per_token), jnp.int32), sds((t, dims.per_token), jnp.float32))
    for most in (0, t // 9 + 1):  # the usual capacity and the next above it
        _program(_routed, key, index, y, *pairs, **args, cap=_capacity(most, t))
    _program(_mix_out, key, index, X, y, y, **args)
    _program(_head, key, X, sds((m,), jnp.int32), **args)


def expert_ffn(key, index: int, X, dims: W.Dims, weight_dtype, quantize=None, residual=MHC):
    """Expert layer ``index``'s ``m`` for one sequence's streams X [t, n, C] -> (shared
    expert's part, this share's routed part), each [t, C]."""
    args = dict(dims=dims, weight_dtype=weight_dtype, quantize=quantize)
    fault = {} if residual == MHC else {"residual": residual}  # the usual program is ``precompile``'s
    y, shared, experts, gates, counts = _run(_shared_and_route, key, jnp.int32(index), X, **args, **fault)
    routed = _run(_routed, key, jnp.int32(index), y, experts, gates, **args,
                  cap=_capacity(counts.max(), y.shape[0]))
    return shared, routed


def final_streams(key, tokens, dims: W.Dims, weight_dtype, quantize=None, residual=MHC):
    """The streams after the last layer of ONE sequence [t]: [t, n, C]."""
    if residual not in (MHC, PLAIN):
        raise ValueError(f"unknown residual path {residual!r}")
    args = dict(dims=dims, weight_dtype=weight_dtype, quantize=quantize)
    fault = {} if residual == MHC else {"residual": residual}
    X = _run(_embed, key, tokens, **args)
    for i in range(dims.layers):
        X = _run(_attn_block, key, jnp.int32(i), X, **args, **fault)
        if i < dims.lead:
            X = _run(_dense_block, key, jnp.int32(i), X, **args, **fault)
        else:
            shared, routed = expert_ffn(key, i, X, dims, weight_dtype, quantize, residual)
            X = _run(_mix_out, key, jnp.int32(i), X, shared, routed, **args, **fault)
    return X


def stream_logits(key, tokens, dims: W.Dims, weight_dtype, quantize=None, positions=None,
                  residual=MHC):
    """Logits of ``tokens`` [n, t] (padded on the right: causal attention keeps padding
    out of earlier positions), at every position or, with ``positions`` [n, m], at those
    alone: [n, m, vocab]. A sequence at a time: memory."""
    out = []
    for i in range(tokens.shape[0]):
        X = final_streams(key, tokens[i], dims, weight_dtype, quantize, residual)
        at = jnp.arange(X.shape[0], dtype=jnp.int32) if positions is None else positions[i]
        out.append(_run(_head, key, X, at, dims=dims, weight_dtype=weight_dtype, quantize=quantize))
    return jnp.stack(out)
