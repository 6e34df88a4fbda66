"""The plain reference of the hybrid state-space decoder: float32, ``jax.numpy``,
every matrix product under ``default_matmul_precision("highest")``, the
recurrence token by token.

With ``d`` the hidden size, ``h`` state-space heads of ``p``, a state of ``n``
columns, one group; every norm an RMSNorm with the configuration's eps:

1. ``x = embedding_multiplier * E[tok]``.
2. Every layer: ``x = x + residual_multiplier * mixer(norm(x))``, then ``x = x +
   residual_multiplier * mlp(norm(x))``, ``mlp(v) = W_o (silu(g) * u)`` with ``[g |
   u] = W_i v``.
3. ``attention`` mixer: ``q, k, v`` without bias, 4 query heads a kv head, NO
   position term, softmax of ``attention_multiplier * q k^T`` over ``s <= t``,
   ``W_o``. Full causal attention over the whole sequence.
4. ``mamba`` mixer: ``[z | xBC | dt] = W_in u``; ``xBC'_t = silu(sum_{k=0..3} w[:,
   k] xBC_{t-3+k} + b)``, zeros before position 0; ``[x | B | C] = xBC'``; per head
   ``D_t = softplus(dt_t + dt_bias)``, ``a_t = exp(D_t A)``, ``A = -exp(A_log)``;
   ``S_t = a_t S_{t-1} + D_t x_t B_t^T`` (``S`` in ``R^{p x n}`` a head, ``S_{-1} = 0``)
   by ``lax.scan`` over the tokens, one at a time; ``y_t = S_t C_t + D x_t``; out
   ``= W_out (norm(y_t * silu(z_t)) * w_norm)``, the norm over all ``h p``.
5. ``logits = (norm(x_L) E^T) / logits_scaling``.

No departure from the published equations; ``mamba_chunk_size`` is a kernel's
block there and enters nothing here. Nothing the program made enters either:
weights come from ``weights_hybrid_ssm`` and the seed, rounded to the
configuration's ``weight_dtype`` and taken back to float32, ONE layer at a time
(the whole model in float32 is 12.8 GB, beside an engine of 12).

``quantize="int8"`` is the control, as in ``reference.py``: every weight matrix
(the head's use of the embedding too) rounded to int8 with a scale per output
channel, every activation that enters one of them to int8 with a scale per token.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import weights_hybrid_ssm as W
from chipbench.reference import HIGHEST, _act, _fake_int8, rms_norm

_static = ("dims", "weight_dtype", "quantize")


def _prepare(tree: dict, weight_dtype, quantize) -> dict:
    """Weights as the configuration holds them, back in float32. Behind a
    barrier: left free, the compiler draws a matrix's random numbers inside
    the product that reads it, tile by tile (ROADMAP R, point 5)."""
    if quantize not in (None, "int8"):
        raise ValueError(f"unknown control precision {quantize!r}")

    def one(name, x):
        x = x.astype(weight_dtype).astype(jnp.float32)
        return _fake_int8(x) if quantize and name in W.MATRICES else x
    return jax.lax.optimization_barrier({k: one(k, v) for k, v in tree.items()})


def mlp(x, lp: dict, dims: W.Dims, quantize=None):
    act = _act(quantize)
    g, u = jnp.split(act(rms_norm(x, lp["mlp_norm"], dims.rms_eps)) @ lp["w_in"], 2, axis=-1)
    return act(jax.nn.silu(g) * u) @ lp["w_out"]


def attention(x, lp: dict, dims: W.Dims, quantize=None):
    """The attention mixer's output for one sequence. x: [t, hidden]."""
    t = x.shape[0]
    act = _act(quantize)
    u = act(rms_norm(x, lp["norm"], dims.rms_eps))
    group = dims.heads // dims.kv_heads
    q = (u @ lp["wq"]).reshape(t, dims.kv_heads, group, dims.head_dim)
    k = (u @ lp["wk"]).reshape(t, dims.kv_heads, dims.head_dim)
    v = (u @ lp["wv"]).reshape(t, dims.kv_heads, dims.head_dim)
    s = jnp.einsum("qkgd,skd->kgqs", q, k) * dims.attn_mult
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, axis=-1), v)
    return act(o.reshape(t, dims.heads * dims.head_dim)) @ lp["wo"]


def mamba(x, lp: dict, dims: W.Dims, quantize=None, state_dtype=jnp.float32):
    """The state-space mixer's output for one sequence, the recurrence a token
    at a time from ``S = 0``. ``state_dtype`` is what ``S`` is kept in between
    tokens (float32; a test rounds it lower to show that the comparison sees it)."""
    t = x.shape[0]
    act = _act(quantize)
    h, p, n = dims.ssm_heads, dims.ssm_head, dims.state
    zxbcdt = act(rms_norm(x, lp["norm"], dims.rms_eps)) @ lp["in_proj"]
    z, xbc, dt = jnp.split(zxbcdt, (dims.inner, dims.inner + dims.conv_dim), axis=-1)
    past = jnp.concatenate([jnp.zeros((dims.conv - 1, dims.conv_dim), xbc.dtype), xbc])
    xbc = jax.nn.silu(sum(lp["conv_w"][:, k] * past[k:k + t] for k in range(dims.conv))
                      + lp["conv_b"])
    xs, B, C = jnp.split(xbc, (dims.inner, dims.inner + dims.state), axis=-1)
    xs = xs.reshape(t, h, p)
    step = jax.nn.softplus(dt + lp["dt_bias"])  # [t, h]
    decay = jnp.exp(-step * jnp.exp(lp["A_log"]))

    def token(S, now):
        a, d, x_t, B_t, C_t = now
        S = a[:, None, None] * S.astype(jnp.float32) + (d[:, None] * x_t)[:, :, None] * B_t
        return S.astype(state_dtype), jnp.einsum("hpn,n->hp", S, C_t)

    _, y = jax.lax.scan(token, jnp.zeros((h, p, n), state_dtype), (decay, step, xs, B, C))
    y = (y + lp["D"][:, None] * xs).reshape(t, h * p) * jax.nn.silu(z)
    return act(rms_norm(y, lp["gate_norm"], dims.rms_eps)) @ lp["out_proj"]


def layer(x, lp: dict, dims: W.Dims, kind: str, quantize=None, state_dtype=jnp.float32):
    mixed = (mamba(x, lp, dims, quantize, state_dtype) if kind == "mamba"
             else attention(x, lp, dims, quantize))
    x = x + dims.residual_mult * mixed
    return x + dims.residual_mult * mlp(x, lp, dims, quantize)


def logits_of(x, top: dict, dims: W.Dims, quantize=None):
    head = top["embed"].T  # [hidden, vocab]: tied
    if quantize:
        head = _fake_int8(head)
    return (_act(quantize)(rms_norm(x, top["final_norm"], dims.rms_eps)) @ head) / dims.logits_scale


# --- piece by piece from the seed -------------------------------------------
_PROGRAMS: dict = {}


def _program(piece, *args, **static):
    """The compiled program of one jitted piece for arguments of these shapes,
    made once a process (``reference_latent_moe._program``: compiled ahead of
    the call, because ``precompile`` has only shapes to give)."""
    key = (piece, tuple((tuple(a.shape), jnp.dtype(a.dtype).name) for a in args),
           tuple(sorted(static.items(), key=lambda kv: kv[0])))
    if key not in _PROGRAMS:
        _PROGRAMS[key] = piece.lower(*args, **static).compile()
    return _PROGRAMS[key]


def _run(piece, *args, **static):
    return _program(piece, *args, **static)(*args)


@functools.partial(jax.jit, static_argnames=_static)
def _embed(key, tokens, dims, weight_dtype, quantize):
    top = W.top_params(key, dims)
    return top["embed"].astype(weight_dtype).astype(jnp.float32)[tokens] * dims.embed_mult


@functools.partial(jax.jit, static_argnames=_static + ("kind",), donate_argnums=(2,))
def _layer(key, index, x, dims, weight_dtype, quantize, kind):
    with jax.default_matmul_precision(HIGHEST):
        lp = _prepare(W.layer_params(key, index, dims, kind), weight_dtype, quantize)
        return layer(x, lp, dims, kind, quantize)


@functools.partial(jax.jit, static_argnames=_static)
def _head(key, x, positions, dims, weight_dtype, quantize):
    """Logits of the hidden states ``x`` [t, hidden] at ``positions`` [m]."""
    with jax.default_matmul_precision(HIGHEST):
        top = jax.lax.optimization_barrier(
            {k: v.astype(weight_dtype).astype(jnp.float32)
             for k, v in W.top_params(key, dims).items()})
        return logits_of(x[positions], top, dims, quantize)


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
    for kind in sorted(set(dims.kinds)):
        _program(_layer, key, index, x, **args, kind=kind)
    _program(_head, key, x, sds((m,), jnp.int32), **args)


def hidden_states(key, tokens, dims: W.Dims, weight_dtype, quantize=None):
    """Final hidden states (before the last norm) of ONE sequence [t]."""
    args = dict(dims=dims, weight_dtype=weight_dtype, quantize=quantize)
    x = _run(_embed, key, tokens, **args)
    for i, kind in enumerate(dims.kinds):
        x = _run(_layer, key, jnp.int32(i), x, **args, kind=kind)
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
