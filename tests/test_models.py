"""Model + ops numerical tests (CPU, virtual devices)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer as tf
from ray_tpu.ops.attention import flash_attention, reference_attention


@pytest.fixture(scope="module")
def cfg():
    return tf.TransformerConfig.tiny(dtype=jnp.float32)


def test_forward_shapes(cfg):
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    logits = tf.forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.isfinite(logits).all())


def test_loss_decreases_under_sgd(cfg):
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab_size)
    batch = {"tokens": tokens}

    @jax.jit
    def step(p):
        l, g = jax.value_and_grad(tf.loss_fn)(p, batch, cfg)
        return l, jax.tree.map(lambda a, b: a - 0.1 * b, p, g)

    l0, params = step(params)
    for _ in range(10):
        l, params = step(params)
    assert float(l) < float(l0)


def test_causality(cfg):
    """Changing future tokens must not change past logits."""
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    t1 = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, cfg.vocab_size)
    t2 = t1.at[:, 10:].set((t1[:, 10:] + 1) % cfg.vocab_size)
    l1 = tf.forward(params, t1, cfg)
    l2 = tf.forward(params, t2, cfg)
    np.testing.assert_allclose(l1[:, :10], l2[:, :10], rtol=2e-4, atol=2e-4)
    assert not np.allclose(l1[:, 10:], l2[:, 10:])


def test_gqa_equals_mha_when_repeated():
    cfg_mha = tf.TransformerConfig.tiny(n_kv_heads=4, dtype=jnp.float32)
    params = tf.init_params(jax.random.PRNGKey(0), cfg_mha)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg_mha.vocab_size)
    assert bool(jnp.isfinite(tf.forward(params, tokens, cfg_mha)).all())


def test_moe_forward():
    cfg = tf.TransformerConfig.tiny(num_experts=4, experts_per_token=2, dtype=jnp.float32)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
    logits = tf.forward(params, tokens, cfg)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.slow
def test_flash_attention_matches_reference_interpret():
    """Pallas kernel (interpret mode on CPU) vs jnp reference.

    Tolerance is sized for this backend's reduced-precision matmul (see
    conftest note) — the two computations group matmuls differently.
    """
    from ray_tpu.ops import attention as att

    key = jax.random.PRNGKey(0)
    q, k, v = (
        jax.random.normal(kk, (2, 4, 128, 64), jnp.float32)
        for kk in jax.random.split(key, 3)
    )
    ref = reference_attention(q, k, v, causal=True)
    out, _ = att._flash_forward(q, k, v, causal=True, scale=64**-0.5, block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=2e-2, atol=2e-2)
    # Structural causality check is exact: a change in future keys/values
    # must not perturb earlier rows at all.
    k2 = k.at[:, :, 100:].add(1.0)
    v2 = v.at[:, :, 100:].add(1.0)
    out2, _ = att._flash_forward(q, k2, v2, causal=True, scale=64**-0.5, block_q=64, block_k=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(out[:, :, :100]), np.asarray(out2[:, :, :100]))


@pytest.mark.slow
def test_flash_attention_noncausal_interpret():
    from ray_tpu.ops import attention as att

    key = jax.random.PRNGKey(3)
    q, k, v = (
        jax.random.normal(kk, (1, 2, 128, 64), jnp.float32)
        for kk in jax.random.split(key, 3)
    )
    ref = reference_attention(q, k, v, causal=False)
    out, _ = att._flash_forward(q, k, v, causal=False, scale=64**-0.5, block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=2e-2, atol=2e-2)


@pytest.mark.slow
def test_flash_attention_grad_matches():
    key = jax.random.PRNGKey(5)
    q, k, v = (
        jax.random.normal(kk, (1, 2, 32, 16), jnp.float32)
        for kk in jax.random.split(key, 3)
    )

    def f_flash(q, k, v):
        return flash_attention(q, k, v, True, None).sum()

    def f_ref(q, k, v):
        return reference_attention(q, k, v, causal=True).sum()

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal,q_len,k_len", [(True, 128, 128), (False, 96, 160)],
                         ids=["causal", "noncausal_ragged"])
@pytest.mark.slow
def test_flash_backward_kernels_match_reference(causal, q_len, k_len):
    """Pallas dQ/dKV kernels (interpret mode) vs the reference VJP,
    including ragged lengths that exercise both pad paths."""
    from ray_tpu.ops import attention as att

    key = jax.random.PRNGKey(7)
    kq, kk_, kv, kg = jax.random.split(key, 4)
    q = jax.random.normal(kq, (2, 2, q_len, 64), jnp.float32)
    k = jax.random.normal(kk_, (2, 2, k_len, 64), jnp.float32)
    v = jax.random.normal(kv, (2, 2, k_len, 64), jnp.float32)
    g = jax.random.normal(kg, (2, 2, q_len, 64), jnp.float32)
    scale = 64**-0.5

    o, lse = att._flash_forward(q, k, v, causal=causal, scale=scale,
                                block_q=64, block_k=64, interpret=True)
    dq, dk, dv = att._flash_backward(q, k, v, o, lse, g, causal=causal, scale=scale,
                                     block_q=64, block_k=64, interpret=True)

    def f_ref(q, k, v):
        return (reference_attention(q, k, v, causal=causal, scale=scale) * g).sum()

    rq, rk, rv = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rq), rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rk), rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rv), rtol=2e-2, atol=2e-2)


@pytest.mark.slow
def test_flash_attention_gqa_native_matches_reference():
    """GQA-native kernels (q heads grouped onto shared kv heads — no
    caller-side repeat) vs the reference oracle, forward AND backward
    (VERDICT: 'GQA numerics test vs reference_attention')."""
    from ray_tpu.ops import attention as att

    key = jax.random.PRNGKey(11)
    kq, kk_, kv, kg = jax.random.split(key, 4)
    q = jax.random.normal(kq, (2, 8, 128, 64), jnp.float32)   # 8 q heads
    k = jax.random.normal(kk_, (2, 2, 128, 64), jnp.float32)  # 2 kv heads
    v = jax.random.normal(kv, (2, 2, 128, 64), jnp.float32)
    g = jax.random.normal(kg, (2, 8, 128, 64), jnp.float32)
    scale = 64**-0.5

    ref = reference_attention(q, k, v, causal=True, scale=scale)
    o, lse = att._flash_forward(q, k, v, causal=True, scale=scale,
                                block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(o), rtol=2e-2, atol=2e-2)

    dq, dk, dv = att._flash_backward(q, k, v, o, lse, g, causal=True, scale=scale,
                                     block_q=64, block_k=64, interpret=True)
    assert dk.shape == k.shape and dv.shape == v.shape  # kv-head shaped grads

    def f_ref(q, k, v):
        return (reference_attention(q, k, v, causal=True, scale=scale) * g).sum()

    rq, rk, rv = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rq), rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rk), rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rv), rtol=2e-2, atol=2e-2)


@pytest.mark.slow
def test_flash_attention_gqa_ragged_noncausal():
    """GQA with ragged q/k lengths exercising both pad paths."""
    from ray_tpu.ops import attention as att

    key = jax.random.PRNGKey(13)
    kq, kk_, kv, kg = jax.random.split(key, 4)
    q = jax.random.normal(kq, (1, 4, 96, 64), jnp.float32)
    k = jax.random.normal(kk_, (1, 2, 160, 64), jnp.float32)
    v = jax.random.normal(kv, (1, 2, 160, 64), jnp.float32)
    g = jax.random.normal(kg, (1, 4, 96, 64), jnp.float32)
    scale = 64**-0.5

    ref = reference_attention(q, k, v, causal=False, scale=scale)
    o, lse = att._flash_forward(q, k, v, causal=False, scale=scale,
                                block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(o), rtol=2e-2, atol=2e-2)
    dq, dk, dv = att._flash_backward(q, k, v, o, lse, g, causal=False, scale=scale,
                                     block_q=64, block_k=64, interpret=True)

    def f_ref(q, k, v):
        return (reference_attention(q, k, v, causal=False, scale=scale) * g).sum()

    rq, rk, rv = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rq), rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rk), rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rv), rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# KV-cache inference (ray_tpu/models/generate.py)
# ---------------------------------------------------------------------------


def test_prefill_matches_forward():
    from ray_tpu.models import generate as gen

    cfg = tf.TransformerConfig.tiny(dtype=jnp.float32, remat=False)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)
    full = tf.forward(params, toks, cfg)
    pre, cache = gen.prefill(params, cfg, toks, max_len=20)
    np.testing.assert_allclose(np.asarray(full), np.asarray(pre), rtol=2e-2, atol=2e-2)
    assert cache["k"].shape == (cfg.n_layers, 2, 20, cfg.n_kv_heads, cfg.head_dim)


def test_decode_steps_match_forward():
    """Teacher-forced decode: step logits equal the full-forward logits at
    every position (the KV cache is exact, not approximate)."""
    from ray_tpu.models import generate as gen

    cfg = tf.TransformerConfig.tiny(dtype=jnp.float32, remat=False)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 10), 0, cfg.vocab_size)
    full = np.asarray(tf.forward(params, toks, cfg))

    prompt = toks[:, :4]
    _, cache = gen.prefill(params, cfg, prompt, max_len=10)
    step = jax.jit(lambda t, c, p: gen.decode_step(params, cfg, t, c, p))
    for pos in range(4, 10):
        logits, cache = step(toks[:, pos], cache, pos)
        np.testing.assert_allclose(
            np.asarray(logits), full[:, pos], rtol=3e-2, atol=3e-2
        )


def test_generate_greedy_matches_naive():
    from ray_tpu.models import generate as gen

    cfg = tf.TransformerConfig.tiny(dtype=jnp.float32, remat=False)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 5), 0, cfg.vocab_size)

    out = np.asarray(gen.generate(params, cfg, prompt, max_new_tokens=6))
    assert out.shape == (2, 6)
    assert np.asarray(gen.generate(params, cfg, prompt, max_new_tokens=0)).shape == (2, 0)

    # Naive greedy with the SAME decode numerics (prefill + stepwise
    # argmax): exact equality checks the scan wiring/positions; numeric
    # parity with the full forward is covered by the teacher-forced test.
    logits, cache = gen.prefill(params, cfg, prompt, max_len=5 + 6)
    tok = logits[:, -1].argmax(-1).astype(jnp.int32)
    naive = [np.asarray(tok)]
    pos = 5
    for _ in range(5):
        logits, cache = gen.decode_step(params, cfg, tok, cache, pos)
        tok = logits.argmax(-1).astype(jnp.int32)
        naive.append(np.asarray(tok))
        pos += 1
    np.testing.assert_array_equal(out, np.stack(naive, axis=1))

    # Cross-check vs full-forward greedy, tolerating argmax flips only
    # where the top-2 logit gap is within numeric drift.
    cur = np.asarray(prompt)
    for step_idx in range(6):
        logits = np.asarray(tf.forward(params, jnp.asarray(cur), cfg))[:, -1]
        nxt = logits.argmax(-1).astype(np.int32)
        for b in range(2):
            if nxt[b] != out[b, step_idx]:
                top2 = np.sort(logits[b])[-2:]
                assert top2[1] - top2[0] < 1e-2, (step_idx, b, top2)
        cur = np.concatenate([cur, out[:, step_idx : step_idx + 1]], axis=1)


def test_generate_gqa_and_moe():
    """Decode path handles grouped KV heads and MoE layers."""
    from ray_tpu.models import generate as gen

    cfg = tf.TransformerConfig.tiny(
        dtype=jnp.float32, remat=False, num_experts=4, experts_per_token=2
    )
    assert cfg.n_kv_heads != cfg.n_heads  # tiny() uses GQA
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(4), (1, 4), 0, cfg.vocab_size)
    out = np.asarray(gen.generate(params, cfg, prompt, max_new_tokens=4))
    assert out.shape == (1, 4)
    # Sampled path runs too.
    out2 = np.asarray(
        gen.generate(params, cfg, prompt, max_new_tokens=4, temperature=0.8,
                     key=jax.random.PRNGKey(9))
    )
    assert out2.shape == (1, 4)


@pytest.mark.slow
def test_flash_block_q_gt_block_k_ragged():
    """Causal with block_q > block_k and a partial final q-block: the
    k-block loop must clamp instead of issuing a clamped (row-shifting)
    slice past the padded K length."""
    from ray_tpu.ops import attention as att

    key = jax.random.PRNGKey(11)
    q, k, v = (
        jax.random.normal(kk, (1, 2, 192, 32), jnp.float32)
        for kk in jax.random.split(key, 3)
    )
    ref = reference_attention(q, k, v, causal=True)
    out, lse = att._flash_forward(q, k, v, causal=True, scale=32**-0.5,
                                  block_q=128, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=2e-2, atol=2e-2)
    g = jax.random.normal(key, (1, 2, 192, 32), jnp.float32)
    dq, dk, dv = att._flash_backward(q, k, v, out, lse, g, causal=True,
                                     scale=32**-0.5, block_q=128, block_k=64,
                                     interpret=True)
    def f_ref(q, k, v):
        return (reference_attention(q, k, v, causal=True, scale=32**-0.5) * g).sum()
    rq, rk, rv = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rq), rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rk), rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rv), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("k_len", [128, 96], ids=["q_gt_k", "q_gt_k_padded"])
@pytest.mark.slow
def test_flash_causal_cross_length(k_len):
    """Causal with q_len > k_len (top-left convention): the unmasked
    phase must stay off K padding and in bounds."""
    from ray_tpu.ops import attention as att

    q_len, d = 320, 32
    key = jax.random.PRNGKey(13)
    kq, kk_, kv, kg = jax.random.split(key, 4)
    q = jax.random.normal(kq, (1, 2, q_len, d), jnp.float32)
    k = jax.random.normal(kk_, (1, 2, k_len, d), jnp.float32)
    v = jax.random.normal(kv, (1, 2, k_len, d), jnp.float32)
    g = jax.random.normal(kg, (1, 2, q_len, d), jnp.float32)
    scale = d**-0.5

    # Oracle with the kernel's q_ids >= k_ids convention.
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    qi = jnp.arange(q_len)[:, None]
    ki = jnp.arange(k_len)[None, :]
    logits = jnp.where(qi >= ki, logits, att.DEFAULT_MASK_VALUE)
    ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(logits, axis=-1), v)

    out, lse = att._flash_forward(q, k, v, causal=True, scale=scale,
                                  block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=2e-2, atol=2e-2)

    dq, dk, dv = att._flash_backward(q, k, v, out, lse, g, causal=True,
                                     scale=scale, block_q=64, block_k=64,
                                     interpret=True)

    def f_ref(q, k, v):
        lg = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        lg = jnp.where(qi >= ki, lg, att.DEFAULT_MASK_VALUE)
        o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(lg, axis=-1), v)
        return (o * g).sum()

    rq, rk, rv = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rq), rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rk), rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rv), rtol=2e-2, atol=2e-2)


# -- ViT (models/vit.py) -----------------------------------------------------

def test_vit_forward_shapes():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import vit

    cfg = vit.ViTConfig.tiny(dtype=jnp.float32)
    params = vit.init_params(jax.random.PRNGKey(0), cfg)
    images = jax.random.normal(jax.random.PRNGKey(1), (3, 32, 32, 3))
    logits = vit.forward(params, images, cfg)
    assert logits.shape == (3, 10)
    assert bool(jnp.isfinite(logits).all())


def test_vit_patchify_roundtrip():
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import vit

    cfg = vit.ViTConfig.tiny()
    # patch (0,1) of a ramp image must equal the raw pixel block
    img = np.arange(32 * 32 * 3, dtype=np.float32).reshape(1, 32, 32, 3)
    patches = np.asarray(vit.patchify(jnp.asarray(img), cfg))
    assert patches.shape == (1, 16, 8 * 8 * 3)
    expected = img[0, 0:8, 8:16, :].reshape(-1)
    np.testing.assert_array_equal(patches[0, 1], expected)


@pytest.mark.slow
def test_vit_learns_tiny_classification():
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import vit

    cfg = vit.ViTConfig.tiny(dtype=jnp.float32)
    params = vit.init_params(jax.random.PRNGKey(0), cfg)
    # Learnable toy task: class = which image quadrant is bright.
    key = jax.random.PRNGKey(42)
    n = 64
    labels = jax.random.randint(key, (n,), 0, 4)
    images = jnp.zeros((n, 32, 32, 3))
    for q in range(4):
        r, c = divmod(q, 2)
        images = images.at[jnp.where(labels == q)[0], r*16:(r+1)*16, c*16:(c+1)*16, :].set(1.0)
    batch = {"images": images, "labels": labels % cfg.num_classes}

    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state):
        loss, grads = jax.value_and_grad(vit.loss_fn)(params, batch, cfg)
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    first = None
    for i in range(60):
        params, opt_state, loss = step(params, opt_state)
        if first is None:
            first = float(loss)
    acc = float(vit.accuracy(params, batch, cfg))
    assert float(loss) < first * 0.5
    assert acc >= 0.9, f"acc={acc}"


def test_chunked_nll_matches_full():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import transformer as tf

    cfg = tf.TransformerConfig.tiny(dtype=jnp.float32)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    l0 = float(tf.loss_fn(params, batch, cfg))
    # dividing and non-dividing (padded) chunk sizes
    assert abs(float(tf.loss_fn(params, batch, cfg, logits_chunk=16)) - l0) < 1e-6
    assert abs(float(tf.loss_fn(params, batch, cfg, logits_chunk=30)) - l0) < 1e-6
    g0 = jax.grad(lambda p: tf.loss_fn(p, batch, cfg))(params)
    g1 = jax.grad(lambda p: tf.loss_fn(p, batch, cfg, logits_chunk=16))(params)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)


def test_chunked_nll_respects_mask():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import transformer as tf

    cfg = tf.TransformerConfig.tiny(dtype=jnp.float32)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, cfg.vocab_size)
    mask = jnp.ones((2, 33)).at[:, 20:].set(0.0)
    batch = {"tokens": tokens, "mask": mask}
    l0 = float(tf.loss_fn(params, batch, cfg))
    l1 = float(tf.loss_fn(params, batch, cfg, logits_chunk=8))
    assert abs(l0 - l1) < 1e-6


@pytest.mark.parametrize("remat", [False, "full", "dots"])
def test_remat_policy_same_loss_and_gradients(remat):
    """Whatever a rematerialised layer keeps, the numbers are those of the
    layer that keeps everything: the loss and every gradient leaf (float32 on
    the CPU; to rounding, because XLA fuses a recomputed body its own way)."""
    import dataclasses

    plain = tf.TransformerConfig.tiny(dtype=jnp.float32, remat=False)
    cfg = dataclasses.replace(plain, remat=bool(remat), remat_policy=remat or "full")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, cfg.vocab_size)}
    want_l, want_g = jax.value_and_grad(lambda p: tf.loss_fn(p, batch, plain))(params)
    got_l, got_g = jax.value_and_grad(lambda p: tf.loss_fn(p, batch, cfg))(params)
    assert abs(float(got_l) - float(want_l)) < 1e-6
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        ),
        got_g, want_g,
    )


@pytest.mark.parametrize("remat", [True, False])
def test_remat_policy_attn_is_gone(remat):
    """``"attn"`` named the attention's output AFTER the custom VJP and so
    kept a copy of it beside a kernel that still ran twice: the value is
    refused, rematerialised or not, and the error names the two left."""
    cfg = tf.TransformerConfig.tiny(dtype=jnp.float32, remat=remat, remat_policy="attn")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.zeros((2, 33), jnp.int32)}
    with pytest.raises(ValueError, match="'full' or 'dots'"):
        tf.loss_fn(params, batch, cfg)


def _count_in_jaxpr(jaxpr, name: str) -> int:
    """Pallas calls named ``name`` anywhere in ``jaxpr``, sub-jaxprs included."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += eqn.params["name"] == name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count_in_jaxpr(sub, name)
    return n


@pytest.mark.parametrize("plan_kw", [dict(dp=1), dict(fsdp=4)], ids=["one-device", "fsdp4"])
def test_rematerialised_layer_runs_the_flash_forward_kernel_once(monkeypatch, plan_kw):
    """The forward scan's body and the backward scan's body together hold ONE
    ``flash_fwd``: the kernel's output and logsumexp are kept by name
    (``checkpoint_layer``), so the recomputation is XLA operations only. A
    policy that keeps a name the residuals do not carry (``"attn"``, gone)
    read 2 here."""
    from ray_tpu.parallel import MeshPlan, build_mesh
    from ray_tpu.parallel.train_step import build_loss_fn

    monkeypatch.setenv("RAY_TPU_FORCE_PALLAS", "1")  # the TPU's dispatch, traced only
    cfg = tf.TransformerConfig(
        vocab_size=128, d_model=256, n_layers=2, n_heads=2, n_kv_heads=1, d_ff=128,
        max_seq_len=128, dtype=jnp.bfloat16, remat=True,
    )
    plan = MeshPlan(**plan_kw)
    mesh = build_mesh(plan, devices=jax.devices()[: plan.num_devices])
    loss = build_loss_fn(cfg, plan, mesh)
    params = jax.eval_shape(lambda k: tf.init_params(k, cfg), jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 129), jnp.int32)}
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params, batch).jaxpr
    counts = {k: _count_in_jaxpr(jaxpr, k) for k in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")}
    assert counts == {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1}, counts
