"""The plain reference: Mistral's decoder as published, float32, ``jax.numpy``.

RMSNorm, grouped-query attention with rotary embeddings (halves rotated, as
in the published code), SwiGLU, an untied head; no kernel, cache or batching.
Every matrix product runs under ``default_matmul_precision("highest")``, or a
TPU would round it to bfloat16. Weights come from ``weights.py`` and the seed,
rounded to the type the configuration states (``weight_dtype``) and taken
back to float32: nothing the program made enters here.

``quantize="int8"`` is the control of "How correct is decided": the same
reference computed in int8, the step below bfloat16 that a later PR would be
tempted by. Every weight matrix is rounded to int8 with a scale per output
channel and every activation that enters a matrix product to int8 with a
scale per token; sums stay in float32, as an int8 unit keeps them. It must
come out as not correct.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import weights as W

HIGHEST = "highest"


QUANTIZED = W.LAYER_MATRICES + ("lm_head",)


def _fake_int8(w):
    """Round to int8 with one scale per output channel ([.., in, out])."""
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    return jnp.round(w / scale) * scale


def _int8_rows(a):
    """An activation rounded to int8 with one scale per token; the gradient
    passes straight through the rounding."""
    scale = jnp.maximum(jnp.max(jnp.abs(a), axis=-1, keepdims=True), 1e-30) / 127.0
    return a + jax.lax.stop_gradient(jnp.round(a / scale) * scale - a)


def _act(quantize):
    return _int8_rows if quantize else (lambda a: a)


def _prepare(tree: dict, weight_dtype, quantize):
    """Weights as the configuration holds them, back in float32."""
    if quantize not in (None, "int8"):
        raise ValueError(f"unknown control precision {quantize!r}")

    def one(name, x):
        if isinstance(x, dict):
            return _prepare(x, weight_dtype, quantize)
        x = x.astype(weight_dtype).astype(jnp.float32)
        return _fake_int8(x) if quantize and name in QUANTIZED else x
    return {k: one(k, v) for k, v in tree.items()}


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, positions, theta):
    """x: [t, heads, head_dim]; pairs are (i, i + head_dim/2)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layer(x, lp: dict, dims: W.Dims, quantize=None):
    """One decoder layer over one sequence. x: [t, hidden] float32."""
    t = x.shape[0]
    pos = jnp.arange(t)
    act = _act(quantize)
    h = act(rms_norm(x, lp["attn_norm"], dims.rms_eps))
    q = rope((h @ lp["wq"]).reshape(t, dims.heads, dims.head_dim), pos, dims.rope_theta)
    k = rope((h @ lp["wk"]).reshape(t, dims.kv_heads, dims.head_dim), pos, dims.rope_theta)
    v = (h @ lp["wv"]).reshape(t, dims.kv_heads, dims.head_dim)
    group = dims.heads // dims.kv_heads
    causal = pos[:, None] >= pos[None, :]

    def one_kv_head(qkv):
        """The ``group`` query heads that share one key/value head; one such
        group at a time keeps the float32 scores of a 4k sequence small."""
        qg, kh, vh = qkv  # [t, group, hd], [t, hd], [t, hd]
        scores = jnp.einsum("tgd,sd->gts", qg, kh) * dims.head_dim ** -0.5
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("gts,sd->tgd", jax.nn.softmax(scores, axis=-1), vh)

    qg = q.reshape(t, dims.kv_heads, group, dims.head_dim).transpose(1, 0, 2, 3)
    o = jax.lax.map(one_kv_head, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = o.transpose(1, 0, 2, 3).reshape(t, dims.heads * dims.head_dim)
    x = x + act(o) @ lp["wo"]
    h = act(rms_norm(x, lp["mlp_norm"], dims.rms_eps))
    return x + act(jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]


def logits_of(x, top: dict, dims: W.Dims, quantize=None):
    return _act(quantize)(rms_norm(x, top["final_norm"], dims.rms_eps)) @ top["lm_head"]


# --- layer by layer from the seed -------------------------------------------
# Each layer's weights are made again from the seed when they are needed and
# dropped after: neither 24 layers for serving nor 8 layers with their
# gradients for training fit beside anything else in float32.

@functools.partial(jax.jit, static_argnames=("dims", "weight_dtype", "quantize"))
def _embed(key, tokens, dims, weight_dtype, quantize):
    top = _prepare(W.top_params(key, dims), weight_dtype, quantize)
    return top["embed"][tokens]


@functools.partial(jax.jit, static_argnames=("dims", "weight_dtype", "quantize"), donate_argnums=(2,))
def _layer(key, index, xs, dims, weight_dtype, quantize):
    with jax.default_matmul_precision(HIGHEST):
        lp = _prepare(W.layer_params(key, index, dims), weight_dtype, quantize)
        return jax.lax.map(lambda x: layer(x, lp, dims, quantize), xs)


@functools.partial(jax.jit, static_argnames=("dims", "weight_dtype", "quantize"))
def _head(key, xs, dims, weight_dtype, quantize):
    with jax.default_matmul_precision(HIGHEST):
        top = _prepare(W.top_params(key, dims), weight_dtype, quantize)
        return jax.lax.map(lambda x: logits_of(x, top, dims, quantize), xs)


def stream_logits(key, tokens, dims: W.Dims, weight_dtype, quantize=None, positions=None):
    """Logits of ``tokens`` [n, t] (padded on the right: causal attention
    keeps padding out of earlier positions), at every position or, with
    ``positions`` [n, m], at those alone: [n, m, vocab]."""
    xs = _embed(key, tokens, dims, weight_dtype, quantize)
    for i in range(dims.layers):
        xs = _layer(key, jnp.int32(i), xs, dims, weight_dtype, quantize)
    if positions is not None:
        xs = jnp.take_along_axis(xs, positions[..., None], axis=1)
    return _head(key, xs, dims, weight_dtype, quantize)


@functools.partial(jax.jit, static_argnames=("dims", "weight_dtype", "quantize"))
def _head_loss_vjp(key, xs, targets, dims, weight_dtype, quantize):
    """Mean next-token cross-entropy over every sequence, with its gradients
    to the final norm, the head and the hidden states."""
    with jax.default_matmul_precision(HIGHEST):
        top = _prepare(W.top_params(key, dims), weight_dtype, quantize)

        def nll(final_norm, lm_head, xs):
            def one(args):
                x, t = args
                logits = logits_of(x, {"final_norm": final_norm, "lm_head": lm_head}, dims, quantize)
                logp = jax.nn.log_softmax(logits, axis=-1)
                return -jnp.take_along_axis(logp, t[:, None], axis=-1)[:, 0].mean()
            return jax.lax.map(jax.checkpoint(one), (xs, targets)).mean()

        loss, vjp = jax.vjp(nll, top["final_norm"], top["lm_head"], xs)
        g_norm, g_head, dxs = vjp(jnp.ones((), jnp.float32))
        return loss, {"final_norm": g_norm, "lm_head": g_head}, dxs


@functools.partial(jax.jit, static_argnames=("dims", "weight_dtype", "quantize"), donate_argnums=(3,))
def _layer_vjp(key, index, xs, dxs, dims, weight_dtype, quantize):
    with jax.default_matmul_precision(HIGHEST):
        lp = _prepare(W.layer_params(key, index, dims), weight_dtype, quantize)
        _, vjp = jax.vjp(
            lambda lp, xs: jax.lax.map(jax.checkpoint(lambda x: layer(x, lp, dims, quantize)), xs), lp, xs)
        return vjp(dxs)


@functools.partial(jax.jit, static_argnames=("dims",))
def _embed_grad(tokens, dxs, dims):
    return jnp.zeros((dims.vocab, dims.hidden), jnp.float32).at[tokens].add(dxs)


def stream_loss_and_grads(key, tokens, dims: W.Dims, weight_dtype, quantize=None):
    """The loss of ``tokens`` [n, t + 1] and its gradients, piece by piece:
    yields ``("loss", None, value)``, ``("top", None, {final_norm, lm_head})``,
    ``("layer", i, {..})`` from the last layer to the first, and ``("top",
    None, {embed})``. The gradients are to the weights as the configuration
    holds them (with ``quantize``, to the rounded ones)."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    xs = [_embed(key, inputs, dims, weight_dtype, quantize)]
    for i in range(dims.layers):
        xs.append(_layer(key, jnp.int32(i), xs[-1] + 0, dims, weight_dtype, quantize))
    loss, g_top, dxs = _head_loss_vjp(key, xs.pop(), targets, dims, weight_dtype, quantize)
    yield "loss", None, loss
    yield "top", None, g_top
    for i in reversed(range(dims.layers)):
        g_layer, dxs = _layer_vjp(key, jnp.int32(i), xs.pop(), dxs, dims, weight_dtype, quantize)
        yield "layer", i, g_layer
    yield "top", None, {"embed": _embed_grad(inputs, dxs, dims)}
