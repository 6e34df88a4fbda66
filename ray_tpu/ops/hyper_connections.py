"""Manifold-constrained hyper-connections: ``n`` residual streams a token, mixed
around every sublayer by three maps made from the token's own streams (mHC,
arXiv:2512.24880, on hyper-connections, arXiv:2409.19606).

For a token's streams ``X`` in ``R^{n x C}`` and one sublayer's parameters
(``phi`` [n C, 2n + n^2], ``alpha`` = (a_pre, a_post, a_res), ``b_pre`` [n],
``b_post`` [n], ``b_res`` [n, n]; all float32):

1. ``x^ = vec(X) / sqrt(mean(vec(X)^2) + eps)``: one RMS over all ``n C`` numbers.
2. ``[p | q | r] = x^ phi``; ``H~pre = a_pre p + b_pre``; ``H~post = a_post q +
   b_post``; ``H~res = a_res mat(r) + b_res`` (``mat``: row-major ``n x n``).
3. ``Hpre = sigmoid(H~pre)``; ``Hpost = 2 sigmoid(H~post)``; ``Hres =
   SK(clip(H~res, lo, hi))``: ``exp``, then ``iters`` times every column over its
   sum, then every row over its sum (``eps`` beside each sum): doubly stochastic
   in the limit (Sinkhorn-Knopp).
4. ``u = Hpre X`` in ``R^C`` is what the sublayer reads (``mix_in``); its output
   ``y`` comes back as ``X' = Hres X + Hpost^T y`` (``mix_out``): stream ``i``
   takes ``Hpost[i] y``. There is no other addition: no ``x + f(x)``.

Plain ``jax.numpy`` everywhere, a TPU included: the maps in float32 whatever
the streams' type (the product with ``phi`` at precision ``highest``: a TPU
would round a float32 product's operands to bfloat16), the two mixes
accumulated in float32 and stored in the streams' type. ``cfg`` is anything
with ``hc_mult``, ``hc_sinkhorn_iters``, ``hc_eps``, ``mhc_h_res_clamp_min`` and
``mhc_h_res_clamp_max``.
"""
from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp


_ROUNDS_A_STEP = 5  # Sinkhorn rounds unrolled in one step of their loop


def sinkhorn(logits, iters: int, eps: float):
    """logits: [.., n, n] float32 → ``exp`` of them after ``iters`` rounds of
    (columns over their sums, rows over their sums).

    Written on the ``n^2`` entries one by one, each an array of the leading
    shape: every sum is then ``n - 1`` additions of arrays and no reduction, and
    a round is elementwise. (As reductions over an axis of 4, each of the 40
    sums became an operation of its own on a TPU: ~80 small operations a
    sublayer, 6,000 a decode step of 40 layers.) The rounds are a loop whose
    step unrolls ``_ROUNDS_A_STEP`` of them: all 20 unrolled are ONE operation
    on a TPU but 1,300 scalar operations in one loop nest for a CPU's compiler
    (24 s a sublayer; 1 s so)."""
    n = logits.shape[-1]

    def round_(_, m):
        cols = [functools.reduce(operator.add, [m[i][j] for i in range(n)]) + eps for j in range(n)]
        m = [[m[i][j] / cols[j] for j in range(n)] for i in range(n)]
        rows = [functools.reduce(operator.add, m[i]) + eps for i in range(n)]
        return tuple(tuple(m[i][j] / rows[i] for j in range(n)) for i in range(n))

    m = tuple(tuple(jnp.exp(logits[..., i, j]) for j in range(n)) for i in range(n))
    m = jax.lax.fori_loop(0, iters, round_, m, unroll=_ROUNDS_A_STEP)
    return jnp.stack([jnp.stack(row, axis=-1) for row in m], axis=-2)


@jax.named_scope("hc.maps")
def maps(X, hp, cfg):
    """X: [.., n, C] → (Hpre [.., n], Hpost [.., n], Hres [.., n, n]), float32."""
    n = cfg.hc_mult
    x = X.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=(-2, -1), keepdims=True) + cfg.hc_eps)
    h = jnp.einsum("...nc,nck->...k", x, hp["phi"].reshape((n, X.shape[-1], -1)),
                   precision=jax.lax.Precision.HIGHEST)
    a_pre, a_post, a_res = hp["alpha"][0], hp["alpha"][1], hp["alpha"][2]
    pre = jax.nn.sigmoid(a_pre * h[..., :n] + hp["b_pre"])
    post = 2.0 * jax.nn.sigmoid(a_post * h[..., n:2 * n] + hp["b_post"])
    res = a_res * h[..., 2 * n:].reshape(h.shape[:-1] + (n, n)) + hp["b_res"]
    res = jnp.clip(res, cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max)
    return pre, post, sinkhorn(res, cfg.hc_sinkhorn_iters, cfg.hc_eps)


@jax.named_scope("hc.in")
def mix_in(pre, X):
    """The sublayer's input: the streams' weighted sum, [.., C] in ``X``'s type."""
    n = X.shape[-2]
    u = sum(pre[..., j, None] * X[..., j, :].astype(jnp.float32) for j in range(n))
    return u.astype(X.dtype)


@jax.named_scope("hc.out")
def mix_out(res, post, X, y):
    """``Hres X + Hpost^T y``: [.., n, C] in ``X``'s type. y: [.., C]."""
    n = X.shape[-2]
    mixed = sum(res[..., :, j, None] * X[..., j, None, :].astype(jnp.float32) for j in range(n))
    return (mixed + post[..., :, None] * y[..., None, :].astype(jnp.float32)).astype(X.dtype)
