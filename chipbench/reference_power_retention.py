"""The plain reference of the power retention decoder: float32, ``jax.numpy``,
every matrix product under ``default_matmul_precision("highest")``, the mixer in
its MASKED QUADRATIC form: no ``phi``, no state, no tile carried to the next.

With ``d`` the hidden size, ``H`` query heads on ``KV`` key/value heads of
``hd`` (``H / KV`` queries share a key/value head); every norm an RMSNorm with the
configuration's eps:

1. ``x = E[tok]``.
2. Every layer: ``x = x + mixer(norm(x))``, then ``x = x + W_down (silu(W_gate u)
   * (W_up u))``, ``u = mlp_norm(x)``.
3. The mixer, ``h = norm(x)``: ``q = h W_q``, ``k = h W_k``, ``v = h W_v``; ``q, k
   <- rope(rms_norm_head(q, k))`` (a scale of ``hd`` each; pairs ``(i, i + hd /
   2)``, ``rope_theta``); ``log g = log_sigmoid(h W_g + b_g)`` one a key/value head
   a token, ``G_t`` its running sum. For query ``t`` and key ``j <= t`` of the
   query's key/value head: ``w[t, j] = exp(G_t - G_j) (q_t . k_j) ** 2 / hd``;
   ``y_t = sum_j w[t, j] v_j / (sum_j w[t, j] + EPS)``; out ``= concat(y) W_o``.
   The queries go in blocks of ``BLOCK`` against all keys, so that the weights
   of one block of one key/value head's queries are all that is held.
4. ``logits = norm(x_L) W_head``.

Power retention is arXiv:2507.04239's layer at degree 2; what the published
``config.json`` does not say (the gate's shape, the normaliser and ``EPS``, the
norm a head before the rotation) is the configuration file's ``assumed``. Nothing
the program made enters here: weights come from ``weights_power_retention`` and
the seed, rounded to the configuration's ``weight_dtype`` and taken back to
float32, ONE layer at a time.

``quantize="int8"`` is the control, as in ``reference.py``: every weight matrix
(the head too) rounded to int8 with a scale per output channel, every activation
that enters one of them to int8 with a scale per token.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import weights_power_retention as W
from chipbench.reference import HIGHEST, _act, _fake_int8, rms_norm, rope
from chipbench.reference_hybrid_ssm import _program, _run  # a piece compiled ahead of its call, once a process

_static = ("dims", "weight_dtype", "quantize")
EPS = 1e-2  # beside the weights' sum (the configuration's ``assumed``)
BLOCK = 256  # queries whose weights are held at once


def _prepare(tree: dict, weight_dtype, quantize, matrices=W.MATRICES) -> dict:
    """Weights as the configuration holds them, back in float32. Behind a
    barrier: left free, the compiler draws a matrix's random numbers inside
    the product that reads it, tile by tile (ROADMAP R, point 5)."""
    if quantize not in (None, "int8"):
        raise ValueError(f"unknown control precision {quantize!r}")

    def one(name, x):
        x = x.astype(weight_dtype).astype(jnp.float32)
        return _fake_int8(x) if quantize and name in matrices else x
    return jax.lax.optimization_barrier({k: one(k, v) for k, v in tree.items()})


def mixer(x, lp: dict, dims: W.Dims, quantize=None):
    """Power retention's output for one sequence. x: [t, hidden]."""
    t, hd, group = x.shape[0], dims.head_dim, dims.group
    pos = jnp.arange(t)
    act = _act(quantize)
    h = act(rms_norm(x, lp["norm"], dims.rms_eps))
    q = (h @ lp["w_q"]).reshape(t, dims.heads, hd)
    k = (h @ lp["w_k"]).reshape(t, dims.kv_heads, hd)
    v = (h @ lp["w_v"]).reshape(t, dims.kv_heads, hd)
    q = rope(rms_norm(q, lp["q_norm"], dims.rms_eps), pos, dims.rope_theta)
    k = rope(rms_norm(k, lp["k_norm"], dims.rms_eps), pos, dims.rope_theta)
    run = jnp.cumsum(jax.nn.log_sigmoid(h @ lp["w_g"] + lp["b_g"]), axis=0)  # [t, KV]
    block = min(BLOCK, t)
    pad = -t % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, dims.kv_heads, group, hd)
    at = jnp.pad(pos, (0, pad)).reshape(-1, block)  # a padded query looks at key 0 alone

    def one_kv_head(args):
        qh, kh, vh, run_h = args  # [blocks, block, group, hd], [t, hd], [t, hd], [t]

        def one_block(args):
            qs, where = args  # [block, group, hd], [block]
            since = jnp.where(where[:, None] >= pos[None, :], run_h[where][:, None] - run_h[None, :],
                              -jnp.inf)
            w = jnp.square(jnp.einsum("igd,jd->gij", qs, kh)) / hd * jnp.exp(since)[None]
            return jnp.einsum("gij,jd->igd", w, vh) / (jnp.sum(w, axis=-1).T[..., None] + EPS)

        return jax.lax.map(one_block, (qh, at))

    y = jax.lax.map(one_kv_head, (qb.transpose(2, 0, 1, 3, 4), k.transpose(1, 0, 2),
                                  v.transpose(1, 0, 2), run.T))  # [KV, blocks, block, group, hd]
    y = y.transpose(1, 2, 0, 3, 4).reshape(-1, dims.heads * hd)[:t]
    return act(y) @ lp["w_o"]


def layer(x, lp: dict, dims: W.Dims, quantize=None):
    act = _act(quantize)
    x = x + mixer(x, lp, dims, quantize)
    u = act(rms_norm(x, lp["mlp_norm"], dims.rms_eps))
    return x + act(jax.nn.silu(u @ lp["w_gate"]) * (u @ lp["w_up"])) @ lp["w_down"]


def logits_of(x, key, dims: W.Dims, weight_dtype, quantize=None):
    """Logits of the hidden states ``x`` [m, hidden], the head a slice of the
    vocabulary's columns at a time (``weights_power_retention.head_slice``)."""
    u = _act(quantize)(rms_norm(x, W.final_norm(dims), dims.rms_eps))

    def one_slice(j):
        head = _prepare({"lm_head": W.head_slice(key, j, dims)}, weight_dtype, quantize, ("lm_head",))
        return u @ head["lm_head"]

    out = jax.lax.map(one_slice, jnp.arange(W.HEAD_SLICES))  # [slices, m, vocab / slices]
    return out.transpose(1, 0, 2).reshape(x.shape[0], dims.vocab)


# --- piece by piece from the seed -------------------------------------------
@functools.partial(jax.jit, static_argnames=_static)
def _embed(key, tokens, dims, weight_dtype, quantize):
    del quantize  # a row is looked up, not multiplied
    return W.embed_rows(key, tokens, dims).astype(weight_dtype).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=_static, donate_argnums=(2,))
def _layer(key, index, x, dims, weight_dtype, quantize):
    with jax.default_matmul_precision(HIGHEST):
        lp = _prepare(W.layer_params(key, index, dims), weight_dtype, quantize)
        return layer(x, lp, dims, quantize)


@functools.partial(jax.jit, static_argnames=_static)
def _head(key, x, positions, dims, weight_dtype, quantize):
    """Logits of the hidden states ``x`` [t, hidden] at ``positions`` [m]."""
    with jax.default_matmul_precision(HIGHEST):
        return logits_of(x[positions], key, dims, weight_dtype, quantize)


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
    _program(_layer, key, index, x, **args)
    _program(_head, key, x, sds((m,), jnp.int32), **args)


def hidden_states(key, tokens, dims: W.Dims, weight_dtype, quantize=None):
    """Final hidden states (before the last norm) of ONE sequence [t]."""
    args = dict(dims=dims, weight_dtype=weight_dtype, quantize=quantize)
    x = _run(_embed, key, tokens, **args)
    for i in range(dims.layers):
        x = _run(_layer, key, jnp.int32(i), x, **args)
    return x


def stream_logits(key, tokens, dims: W.Dims, weight_dtype, quantize=None, positions=None):
    """Logits of ``tokens`` [n, t] (padded on the right: a causal model keeps
    padding out of earlier positions), at every position or, with ``positions``
    [n, m], at those alone: [n, m, vocab]. A sequence at a time: memory."""
    out = []
    for i in range(tokens.shape[0]):
        x = hidden_states(key, tokens[i], dims, weight_dtype, quantize)
        at = jnp.arange(x.shape[0], dtype=jnp.int32) if positions is None else positions[i]
        out.append(_run(_head, key, x, at, dims=dims, weight_dtype=weight_dtype, quantize=quantize))
    return jnp.stack(out)
