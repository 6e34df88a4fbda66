"""The plain reference of the KDA / latent-attention expert decoder: float32,
``jax.numpy``, every matrix product under ``default_matmul_precision("highest")``,
the delta rule token by token, latent attention in the expanded form.

RMSNorm with the configuration's eps; ``h += mixer(norm(h))``, ``h +=
ffn(mlp_norm(h))``; layer ``i`` is latent attention where ``(i + 1) % group == 0``,
else KDA:

1. KDA (``H`` heads of ``K`` keys and values; ``x = norm(h)``): ``[q | k | v] =
   silu(conv(x W_qkv))``, the convolution depthwise over the ``taps`` last
   inputs, zeros before position 0, no bias; per head ``q = q / |q| / sqrt(K)``,
   ``k = k / |k|`` (``|.|^2 + 1e-6`` under the root); ``beta = sigmoid(x W_b)``;
   ``g = lower * sigmoid(exp(A_log) * (x W_g + dt_bias))``, ``a = exp(g)``, a
   CHANNEL's; from ``S = 0``, a token at a time (``lax.scan``): ``S <- diag(a) S``,
   ``u = v - S^T k``, ``S <- S + beta k u^T``, ``o = S^T q``; out ``=
   (o_norm(o) * sigmoid(x W_z)) W_o``, the norm a head's.
2. Latent attention: ``q = q_norm(x W_q)`` a head (over its ``nope + rope``
   numbers), ``[q_nope | q_rope]``, ``q_rope`` rotated; ``[c_kv | k_r] = x W_dkv``,
   ``c = kv_norm(c_kv)``, ``k_rope = RoPE(k_r)`` one for all heads; EXPANDED: per
   head ``[k_nope | v] = c W_ukv``, scores ``(q_nope . k_nope + q_rope . k_rope) /
   sqrt(nope + rope)`` over ``s <= t``, softmax, ``o = sum p v``; ``o_h *=
   sigmoid(x W_hg)_h``; ``W_o``.
3. Feed-forward: SwiGLU of ``ffn`` in the leading layers; else ``s = sigmoid(y
   W_r)`` over ALL routed experts, selection on ``s + bias`` by groups
   (``weights_kda_moe.select``), gates ``scale * s / sum of the chosen s``, ``m =
   shared(y) + sum g_e expert_e(y)`` over the chosen experts THAT THIS SHARE HOLDS.
4. ``logits = final_norm(h) W_head``.

Departures from the published model, all of the configuration's cut and stated
in its file: only the held experts add to ``m``, the vocabulary is the share's
slice, rotary pairs are (i, i + d/2) as in ``reference.rope``. Nothing the
program made enters here: weights come from ``weights_kda_moe`` and the seed,
rounded to the configuration's ``weight_dtype`` and taken back to float32, one
layer's mixer, one dense feed-forward or ONE expert at a time; ``bias`` is
``weights_kda_moe.calibrate``'s, the benchmark's own.

``quantize="int8"`` is the control, as in ``reference.py``: every weight matrix
but the router rounded to int8 with a scale per output channel, every
activation that enters one of them to int8 with a scale per token.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import weights_kda_moe as W
from chipbench.reference import HIGHEST, _act, _fake_int8, rms_norm, rope
from chipbench.reference_latent_moe import _program, _run, swiglu

BLOCK = 1024  # queries a block of the attention
# What the control leaves alone: the router, and what is no matrix of a product.
FULL_PRECISION = ("router", "conv_w", "dt_bias", "A_log", "embed")


def _prepare(tree: dict, weight_dtype, quantize) -> dict:
    """Weights as the configuration holds them, back in float32. Behind a
    barrier: left free, the compiler draws a matrix's random numbers inside
    the product that reads it, tile by tile (ROADMAP R, point 5)."""
    if quantize not in (None, "int8"):
        raise ValueError(f"unknown control precision {quantize!r}")

    def one(name, x):
        x = x.astype(weight_dtype).astype(jnp.float32)
        matrix = not name.endswith("norm") and name not in FULL_PRECISION
        return _fake_int8(x) if quantize and matrix else x
    return jax.lax.optimization_barrier({k: one(k, v) for k, v in tree.items()})


def kda(x, lp: dict, dims: W.Dims, quantize=None, state_dtype=jnp.float32):
    """The KDA mixer's output for one sequence, the recurrence a token at a
    time from ``S = 0``. x: [t, hidden]. ``state_dtype`` is what ``S`` is kept
    in between tokens (float32; a test rounds it lower to show that the
    comparison sees it)."""
    t = x.shape[0]
    act = _act(quantize)
    H, K = dims.heads, dims.head
    u = act(rms_norm(x, lp["norm"], dims.rms_eps))
    qkv = u @ lp["w_qkv"]
    past = jnp.concatenate([jnp.zeros((dims.taps - 1, qkv.shape[1]), qkv.dtype), qkv])
    qkv = jax.nn.silu(sum(lp["conv_w"][:, j] * past[j:j + t] for j in range(dims.taps)))
    q, k, v = (a.reshape(t, H, K) for a in jnp.split(qkv, 3, axis=-1))

    def unit(a):
        return a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

    q, k = unit(q) / K ** 0.5, unit(k)
    beta = jax.nn.sigmoid(u @ lp["w_b"])  # [t, H]
    rate = jnp.exp(lp["A_log"])[:, None]
    g = dims.lower * jax.nn.sigmoid(rate * (u @ lp["w_g"] + lp["dt_bias"]).reshape(t, H, K))

    def token(S, now):
        a_t, q_t, k_t, v_t, b_t = now
        S = a_t[:, :, None] * S.astype(jnp.float32)
        u_t = v_t - jnp.einsum("hkv,hk->hv", S, k_t)
        S = S + b_t[:, None, None] * k_t[:, :, None] * u_t[:, None, :]
        return S.astype(state_dtype), jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((H, K, K), state_dtype), (jnp.exp(g), q, k, v, beta))
    o = rms_norm(o, lp["o_norm"], dims.rms_eps).reshape(t, H * K)
    return act(o * jax.nn.sigmoid(u @ lp["w_z"])) @ lp["w_o"]


def latent(x, lp: dict, dims: W.Dims, quantize=None):
    """The latent attention mixer's output for one sequence. x: [t, hidden]."""
    t = x.shape[0]
    pos = jnp.arange(t)
    act = _act(quantize)
    u = act(rms_norm(x, lp["norm"], dims.rms_eps))
    q = rms_norm((u @ lp["w_q"]).reshape(t, dims.heads, dims.nope + dims.rope), lp["q_norm"],
                 dims.rms_eps)
    q_nope, q_rope = q[..., :dims.nope], rope(q[..., dims.nope:], pos, dims.rope_theta)
    kv = u @ lp["w_dkv"]
    c = act(rms_norm(kv[:, :dims.kv_rank], lp["kv_norm"], dims.rms_eps))
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

    w_ukv = lp["w_ukv"].reshape(dims.kv_rank, dims.heads, dims.nope + dims.v_dim)
    o = jax.lax.map(one_head, (q_nope.transpose(1, 0, 2), q_rope.transpose(1, 0, 2),
                               w_ukv.transpose(1, 0, 2)))  # [heads, t, v]
    o = o.transpose(1, 0, 2) * jax.nn.sigmoid(u @ lp["w_hg"])[:, :, None]
    return act(o.reshape(t, dims.heads * dims.v_dim)) @ lp["wo"]


def gates(scores, experts, dims: W.Dims):
    """The chosen experts' scores over their sum, times ``scale``: [t, per_token]."""
    top = jnp.take_along_axis(scores, experts, axis=-1)
    return dims.scale * top / jnp.sum(top, axis=-1, keepdims=True)


# --- piece by piece from the seed -------------------------------------------
_static = ("dims", "weight_dtype", "quantize")


@functools.partial(jax.jit, static_argnames=_static)
def _embed(key, tokens, dims, weight_dtype, quantize):
    return _prepare(W.top_params(key, dims), weight_dtype, quantize)["embed"][tokens]


@functools.partial(jax.jit, static_argnames=_static + ("kind", "state_dtype"), donate_argnums=(2,))
def _mixer_block(key, index, x, dims, weight_dtype, quantize, kind, state_dtype=jnp.float32):
    """x -> (h after the mixer, y = mlp_norm(h))."""
    with jax.default_matmul_precision(HIGHEST):
        lp = _prepare(W.mixer_params(key, index, dims, kind), weight_dtype, quantize)
        mixed = (kda(x, lp, dims, quantize, state_dtype) if kind == "kda"
                 else latent(x, lp, dims, quantize))
        h = x + mixed
        return h, rms_norm(h, lp["mlp_norm"], dims.rms_eps)


@functools.partial(jax.jit, static_argnames=_static, donate_argnums=(2,))
def _dense_block(key, index, h, y, dims, weight_dtype, quantize):
    with jax.default_matmul_precision(HIGHEST):
        fp = _prepare(W.dense_params(key, index, dims), weight_dtype, quantize)
        return h + swiglu(y, fp["w_gate"], fp["w_up"], fp["w_down"], quantize)


@functools.partial(jax.jit, static_argnames=_static)
def _shared_and_route(key, index, y, bias, dims, weight_dtype, quantize):
    """-> (the shared expert's output, experts, gates, the router's sigmoid
    scores over ALL routed experts [t, experts])."""
    with jax.default_matmul_precision(HIGHEST):
        mp = _prepare(W.moe_params(key, index, dims), weight_dtype, quantize)
        scores = jax.nn.sigmoid(y @ mp["router"])
        experts = W.select(scores, bias, dims)
        shared = swiglu(y, mp["shared_gate"], mp["shared_up"], mp["shared_down"], quantize)
        return shared, experts, gates(scores, experts, dims), scores


@functools.partial(jax.jit, static_argnames=_static)
def _routed(key, index, y, experts, gates, dims, weight_dtype, quantize):
    """The held experts' weighted outputs, an expert at a time, EVERY token
    through every held expert and weighed by its gate for it (0 where it was
    not chosen): no gather, no capacity, one program whatever the routing (an
    expert of 768 is small, and padding routes a whole tail to the same eight)."""
    with jax.default_matmul_precision(HIGHEST):
        def body(m, e):
            ep = _prepare(W.expert_params(key, index, e, dims), weight_dtype, quantize)
            weight = jnp.sum(jnp.where(experts == e, gates, 0.0), axis=-1)
            out = swiglu(y, ep["e_gate"], ep["e_up"], ep["e_down"], quantize)
            return m + out * weight[:, None], None

        return jax.lax.scan(body, jnp.zeros_like(y), dims.held_first + jnp.arange(dims.held))[0]


@functools.partial(jax.jit, static_argnames=_static)
def _head(key, x, positions, dims, weight_dtype, quantize):
    """Logits of the hidden states ``x`` [t, hidden] at ``positions`` [m]."""
    with jax.default_matmul_precision(HIGHEST):
        top = _prepare(W.top_params(key, dims), weight_dtype, quantize)
        x = rms_norm(x[positions], top["final_norm"], dims.rms_eps)
        return _act(quantize)(x) @ top["lm_head"]


def _args(dims, weight_dtype, quantize=None) -> dict:
    return dict(dims=dims, weight_dtype=weight_dtype, quantize=quantize)


def embed(key, tokens, dims: W.Dims, weight_dtype, quantize=None):
    return _run(_embed, key, tokens, **_args(dims, weight_dtype, quantize))


def mixer_block(key, index: int, x, dims: W.Dims, weight_dtype, quantize=None,
                state_dtype=jnp.float32):
    """Layer ``index``'s mixer for one sequence -> (h, y = mlp_norm(h))."""
    return _run(_mixer_block, key, jnp.int32(index), x, **_args(dims, weight_dtype, quantize),
                kind=dims.kind(index), state_dtype=state_dtype)


def dense_block(key, index: int, h, y, dims: W.Dims, weight_dtype, quantize=None):
    return _run(_dense_block, key, jnp.int32(index), h, y, **_args(dims, weight_dtype, quantize))


def scores(key, index: int, y, dims: W.Dims, weight_dtype):
    """The router's scores of expert layer ``index`` (through the program that
    routes: one compilation fewer than a piece of its own would be)."""
    none = jnp.zeros((dims.experts,), jnp.float32)
    return _run(_shared_and_route, key, jnp.int32(index), y, none, **_args(dims, weight_dtype))[3]


def expert_ffn(key, index: int, y, bias, dims: W.Dims, weight_dtype, quantize=None):
    """``m`` of expert layer ``index`` for one sequence's normed hidden states
    ``y`` [t, hidden], ``bias`` [experts] -> (shared expert's part, this
    share's routed part)."""
    args = _args(dims, weight_dtype, quantize)
    shared, experts, g, _ = _run(_shared_and_route, key, jnp.int32(index), y, bias, **args)
    return shared, _run(_routed, key, jnp.int32(index), y, experts, g, **args)


def expert_block(key, index: int, h, y, bias, dims: W.Dims, weight_dtype, quantize=None):
    shared, routed = expert_ffn(key, index, y, bias, dims, weight_dtype, quantize)
    return h + shared + routed


def precompile(dims: W.Dims, weight_dtype, t: int, m: int) -> None:
    """Compile every piece for sequences of ``t`` tokens judged at ``m``
    positions, running nothing: the replica calls this beside its own set-up,
    so that the calibration and the check after the window find their
    programs made."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    key, index = sds((2,), jnp.uint32), sds((), jnp.int32)
    x = sds((t, dims.hidden), jnp.float32)
    args = _args(dims, weight_dtype)
    _program(_embed, key, sds((t,), jnp.int32), **args)
    for kind in ("kda", "latent"):
        _program(_mixer_block, key, index, x, **args, kind=kind, state_dtype=jnp.float32)
    _program(_dense_block, key, index, x, x, **args)
    _program(_shared_and_route, key, index, x, sds((dims.experts,), jnp.float32), **args)
    pairs = (sds((t, dims.per_token), jnp.int32), sds((t, dims.per_token), jnp.float32))
    _program(_routed, key, index, x, *pairs, **args)
    _program(_head, key, x, sds((m,), jnp.int32), **args)


def hidden_states(key, tokens, bias, dims: W.Dims, weight_dtype, quantize=None,
                  state_dtype=jnp.float32):
    """Final hidden states (before the last norm) of ONE sequence [t]; ``bias``
    [expert layers, experts]."""
    x = embed(key, tokens, dims, weight_dtype, quantize)
    for i in range(dims.layers):
        h, y = mixer_block(key, i, x, dims, weight_dtype, quantize, state_dtype)
        if i < dims.lead:
            x = dense_block(key, i, h, y, dims, weight_dtype, quantize)
        else:
            x = expert_block(key, i, h, y, bias[i - dims.lead], dims, weight_dtype, quantize)
    return x


def stream_logits(key, tokens, bias, dims: W.Dims, weight_dtype, quantize=None, positions=None,
                  state_dtype=jnp.float32):
    """Logits of ``tokens`` [n, t] (padded on the right: a causal model keeps
    padding out of earlier positions), at every position or, with ``positions``
    [n, m], at those alone: [n, m, vocab]. A sequence at a time: memory."""
    out = []
    for i in range(tokens.shape[0]):
        x = hidden_states(key, tokens[i], bias, dims, weight_dtype, quantize, state_dtype)
        at = jnp.arange(x.shape[0], dtype=jnp.int32) if positions is None else positions[i]
        out.append(_run(_head, key, x, at, **_args(dims, weight_dtype, quantize)))
    return jnp.stack(out)
