"""The paged-attention kernel against the plain gather-and-einsum form.

The kernel runs under the Pallas interpreter (CPU); the oracle is
``reference_paged_attention``, the form the decode step used before and
still uses where the kernel does not apply. The pool is three layers of
noise and the tables point into the MIDDLE layer (``tables + base``, as
``paged_decode_step`` passes them), so a block read from a neighbouring
layer, a block of another slot, or a token past ``lens`` changes the
answer by O(1). The table is wider than one step of the kernel
(``_BLOCKS_PER_STEP``), so slots take one, two and three steps, the last
one partial, and both buffers carry blocks of two slots.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.paged import TRASH_BLOCK
from ray_tpu.ops import paged_attention as pa

BS, KV, HD = 8, 2, 16
W = 40  # 2.5 steps of 16 blocks
NB = 1 + 4 * W  # a layer's pool: the trash block and four slots' tables
LAYERS, LAYER = 3, 1
EDGE = pa._BLOCKS_PER_STEP * BS  # the first token of a slot's second step
assert 2 * EDGE < W * BS < 3 * EDGE

# name -> lens of the four slots (the position each one's token was written at)
CASES = {
    "lens_0": [0, 37, 0, 200],
    "one_short_of_a_block_edge": [BS - 1, 3 * BS - 1, EDGE - 1, 2 * EDGE - 1],
    "on_a_block_edge": [BS, 3 * BS, EDGE, 2 * EDGE],
    "full_table": [W * BS - 1, 5, W * BS - 1, 177],
    "idle_slot_on_trash": [50, 0, 259, 9],  # slot 1 is the idle one
    # the host lets an idle slot's ``lens`` run on: the whole table, no further
    "lens_past_the_table": [W * BS, 40, 5 * W * BS, 2**30],
}

# float32: both sides compute in float32 and differ by the order of the sums
# (one softmax over the table against a running one over steps): 3.6e-7 at
# worst over these cases, on values up to 2.2; 1e-5 leaves thirty times that
# and is 1e-5 of what a wrong block does. bfloat16: the kernel hands the MXU
# bf16 probabilities (what the chip's default precision makes of the
# reference's float32 ones; the CPU oracle keeps float32) and both round the
# answer to bf16, so they may land on neighbouring bf16 values: 2**-7 at worst
# here; the limit is two ulp of a value in [2, 4), 2**-6 each.
ATOL = {jnp.float32: 1e-5, jnp.bfloat16: 2**-5}


def _problem(case, group, dtype):
    kq, kk, kv, kt = jax.random.split(jax.random.PRNGKey(7), 4)
    pool = (LAYERS * NB, BS, KV, HD)
    q = jax.random.normal(kq, (4, KV * group, HD), jnp.float32).astype(dtype)
    ck = jax.random.normal(kk, pool, jnp.float32).astype(dtype)
    cv = jax.random.normal(kv, pool, jnp.float32).astype(dtype)
    # every slot owns W blocks of the layer's pool, scattered over it
    tables = np.array(jax.random.permutation(kt, jnp.arange(1, NB))).reshape(4, W)
    if case == "idle_slot_on_trash":
        tables[1] = TRASH_BLOCK  # as the host allocator points an idle slot
    tables = jnp.asarray(tables + LAYER * NB, jnp.int32)
    return q, ck, cv, tables, jnp.asarray(CASES[case], jnp.int32)


def _live_rows(tables, lens):
    """Boolean [P, BS]: the (block, offset) pairs some slot attends to."""
    live = np.zeros((LAYERS * NB, BS), bool)
    for row, n in zip(np.asarray(tables), np.asarray(lens)):
        for pos in range(min(n + 1, W * BS)):
            live[row[pos // BS], pos % BS] = True
    return live


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("group", [1, 4], ids=["G1", "G4"])
@pytest.mark.parametrize("case", list(CASES))
def test_kernel_agrees_with_the_plain_form(case, group, dtype):
    q, ck, cv, tables, lens = _problem(case, group, dtype)
    want = pa.reference_paged_attention(q, ck, cv, tables, lens)
    kernel = jax.jit(functools.partial(pa._paged_attend, interpret=True))
    got = kernel(q, ck, cv, tables, lens)
    assert got.shape == want.shape == (4, KV * group * HD) and got.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=0, atol=ATOL[dtype]
    )
    # Nothing but the live rows is read: other noise everywhere else (the
    # other layers, unallocated blocks, rows past ``lens``), the same bits out.
    live = jnp.asarray(_live_rows(tables, lens))[:, :, None, None]
    other_k, other_v = jax.random.split(jax.random.PRNGKey(8))
    ck2 = jnp.where(live, ck, jax.random.normal(other_k, ck.shape, jnp.float32).astype(dtype))
    cv2 = jnp.where(live, cv, jax.random.normal(other_v, cv.shape, jnp.float32).astype(dtype))
    again = kernel(q, ck2, cv2, tables, lens)
    assert np.array_equal(np.asarray(got, np.float32), np.asarray(again, np.float32))


@pytest.mark.parametrize(
    "bs, kv, hd, dtype, tiles",
    [
        (16, 8, 128, jnp.bfloat16, True),  # the serve cell's rows
        (8, 2, 128, jnp.bfloat16, True),  # 16 (token, head) rows: one bf16 tile
        (8, 1, 128, jnp.bfloat16, False),  # 8 rows: half a bf16 tile
        (8, 1, 128, jnp.float32, True),
        (8, 2, 16, jnp.bfloat16, False),  # the tiny test models: lanes of 16
    ],
)
def test_path_is_chosen_from_backend_and_shapes(monkeypatch, bs, kv, hd, dtype, tiles):
    q = jax.ShapeDtypeStruct((4, 4 * kv, hd), dtype)
    pool = jax.ShapeDtypeStruct((9, bs, kv, hd), dtype)
    assert pa._tiles(pool) == tiles
    # on a CPU the plain form, whatever the shapes: no kernel in the program
    monkeypatch.delenv("RAY_TPU_FORCE_PALLAS", raising=False)
    text = jax.jit(pa.paged_attention).lower(
        q, pool, pool, jax.ShapeDtypeStruct((4, 2), jnp.int32),
        jax.ShapeDtypeStruct((4,), jnp.int32),
    ).as_text(debug_info=True)
    assert "paged_attend" not in text and "paged.attend" in text
