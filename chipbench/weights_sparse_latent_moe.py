"""Seeded weights of the decoder with selecting and sliding latent attention,
made by the benchmark and handed to the program.

The tree is the one ``ray_tpu.models.sparse_latent_moe`` takes: ``embed``,
``final_norm``, ``lm_head``, ``lead`` (the leading full layers with their dense
feed-forward, stacked), ``layers`` (ONE period stacked by kind,
``full_attention`` and ``sliding_attention``: ``[periods, a period's, ...]``,
each with its router, ``expert_bias`` and shared expert) and ``experts`` (the
routed experts HELD here, a list with one entry a place in the period:
``[periods, held, ...]``). Every layer has a key of its own and every expert a
key under its layer's, folded from the expert's index among ALL routed
experts, so ``reference_sparse_latent_moe.py`` makes one layer's mixer, or one
expert, again from the seed alone, and another share of the same layer draws
the same experts. The key is a traced argument: a new seed compiles nothing.

Norms one; matrices normal at 1/sqrt(fan_in); the embedding unit variance;
``expert_bias`` normal at 0.01, drawn and NOT fitted (the configuration's file
says so under ``assumed``). **The three matrices that READ a rescaled latent**
(``w_uq`` and ``w_iq`` read ``c_q``, ``w_ukv`` reads ``c``) are drawn at
1/sqrt(hidden_size), as if the hidden state stood where the latent stands: that
is what the rescale ``sqrt(hidden / rank)`` is for, and with it their products
have unit variance. Drawn at 1/sqrt(rank) under the rescale, queries and keys
come out ``r_q`` and ``r_kv`` times too large, the attention's logits have a
spread of 6 where the other families' have 0.8, every softmax is nearly an
argmax, and the network turns a rounding of 1% into other tokens altogether
(the int8 control read 1.2-1.3 of the spread at 93% other tokens, the bfloat16
program 0.91-1.01: my chip run, PR 59, ``chiprun_out/new/checkseeds.log`` of
01:03; at a small size on the CPU 0.94 so and 0.005 drawn this way).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from chipbench.weights import _dense, seed_key  # noqa: F401 - seed_key is this module's too
# What reads only the fields both families' ``Dims`` have (hidden, ffn, expert_ffn,
# held_first, held, vocab): the keys a layer and an expert are drawn from are the same.
from chipbench.weights_latent_moe import (_layer_key, _matrices, dense_params,  # noqa: F401
                                          expert_params, held_params, top_params)

FULL, SLIDING = "full_attention", "sliding_attention"
READ_A_RESCALED_LATENT = ("w_uq", "w_iq", "w_ukv")


@dataclasses.dataclass(frozen=True)
class Attn:
    """One kind of layer's attention sizes."""

    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    theta: float


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration file, under the names the code uses."""

    vocab: int
    hidden: int
    layers: int
    lead: int  # leading dense layers, full ones
    layer_types: tuple
    full: Attn
    sliding: Attn
    index_heads: int
    index_dim: int
    topk: int
    window: int
    ffn: int
    expert_ffn: int
    experts: int  # routed experts of the whole layer: the router's width
    per_token: int
    shared: int
    scale: float
    rms_eps: float
    held_first: int
    held: int  # routed experts of this chip's share

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        def attn(p: str, theta: str) -> Attn:
            return Attn(heads=int(cfg[p + "num_attention_heads"]), q_rank=int(cfg[p + "q_lora_rank"]),
                        kv_rank=int(cfg[p + "kv_lora_rank"]), nope=int(cfg[p + "qk_nope_head_dim"]),
                        rope=int(cfg[p + "qk_rope_head_dim"]), v_dim=int(cfg[p + "v_head_dim"]),
                        theta=float(cfg[theta]))

        return cls(
            vocab=int(cfg["vocab_size"]), hidden=int(cfg["hidden_size"]),
            layers=int(cfg["num_hidden_layers"]), lead=int(cfg["first_k_dense_replace"]),
            layer_types=tuple(cfg["layer_types"]), full=attn("", "rope_theta"),
            sliding=attn("swa_", "swa_rope_theta"), index_heads=int(cfg["index_n_heads"]),
            index_dim=int(cfg["index_head_dim"]), topk=int(cfg["index_topk"]),
            window=int(cfg["sliding_window_size"]), ffn=int(cfg["intermediate_size"]),
            expert_ffn=int(cfg["moe_intermediate_size"]),
            experts=int(cfg["n_routed_experts_published"]), per_token=int(cfg["num_experts_per_tok"]),
            shared=int(cfg["n_shared_experts"]), scale=float(cfg["routed_scaling_factor"]),
            rms_eps=float(cfg["rms_norm_eps"]), held_first=int(cfg["experts_held_first"]),
            held=int(cfg["n_routed_experts"]),
        )

    def attn(self, kind: str) -> Attn:
        return self.full if kind == FULL else self.sliding

    def mixer_shapes(self, kind: str) -> dict:
        """A layer's matrices of its attention (the indexer's among a full layer's)."""
        d, a = self.hidden, self.attn(kind)
        out = {"w_dq": (d, a.q_rank), "w_uq": (a.q_rank, a.heads * (a.nope + a.rope)),
               "w_dkv": (d, a.kv_rank + a.rope), "w_ukv": (a.kv_rank, a.heads * (a.nope + a.v_dim)),
               "w_hg": (d, a.heads), "wo": (a.heads * a.v_dim, d)}
        if kind == FULL:
            out.update({"w_iq": (a.q_rank, self.index_heads * self.index_dim),
                        "w_ik": (d, self.index_dim), "w_iw": (d, self.index_heads)})
        return out

    def norm_shapes(self, kind: str) -> dict:
        d, a = self.hidden, self.attn(kind)
        out = {"attn_norm": (d,), "mlp_norm": (d,), "q_norm": (a.q_rank,), "kv_norm": (a.kv_rank,)}
        return {**out, "index_norm": (self.index_dim,)} if kind == FULL else out


def program_config(dims: Dims, dtype):
    """The program's configuration object for these sizes."""
    from ray_tpu.models.sparse_latent_moe import SparseLatentMoEConfig

    f, s = dims.full, dims.sliding
    return SparseLatentMoEConfig(
        num_hidden_layers=dims.layers, layer_types=dims.layer_types,
        first_k_dense_replace=dims.lead, vocab_size=dims.vocab, hidden_size=dims.hidden,
        num_attention_heads=f.heads, q_lora_rank=f.q_rank, kv_lora_rank=f.kv_rank,
        qk_nope_head_dim=f.nope, qk_rope_head_dim=f.rope, v_head_dim=f.v_dim, rope_theta=f.theta,
        index_n_heads=dims.index_heads, index_head_dim=dims.index_dim, index_topk=dims.topk,
        swa_num_attention_heads=s.heads, swa_q_lora_rank=s.q_rank, swa_kv_lora_rank=s.kv_rank,
        swa_qk_nope_head_dim=s.nope, swa_qk_rope_head_dim=s.rope, swa_v_head_dim=s.v_dim,
        swa_rope_theta=s.theta, sliding_window_size=dims.window, intermediate_size=dims.ffn,
        moe_intermediate_size=dims.expert_ffn, n_routed_experts=dims.experts,
        num_experts_per_tok=dims.per_token, n_shared_experts=dims.shared,
        routed_scaling_factor=dims.scale, rms_norm_eps=dims.rms_eps, held_first=dims.held_first,
        held_count=dims.held, dtype=dtype)


def attn_params(key: jax.Array, index, dims: Dims, kind: str) -> dict:
    """Layer ``index``'s norms and the matrices of its attention, float32; the
    layer is of ``kind``. ``index`` may be traced."""
    mk = jax.random.fold_in(_layer_key(key, index), 0)
    return {**{name: jnp.ones(shape, jnp.float32) for name, shape in dims.norm_shapes(kind).items()},
            **{name: _dense(jax.random.fold_in(mk, j), shape,
                            dims.hidden if name in READ_A_RESCALED_LATENT else shape[0])
               for j, (name, shape) in enumerate(dims.mixer_shapes(kind).items())}}


def moe_params(key: jax.Array, index, dims: Dims) -> dict:
    """An expert layer's router (over ALL routed experts), its selection bias
    and its shared expert."""
    d, s = dims.hidden, dims.expert_ffn * dims.shared
    mk = jax.random.fold_in(_layer_key(key, index), 2)
    out = _matrices(mk, {"router": (d, dims.experts), "shared_gate": (d, s), "shared_up": (d, s),
                         "shared_down": (s, d)})
    out["expert_bias"] = 0.01 * jax.random.normal(
        jax.random.fold_in(mk, len(out)), (dims.experts,), jnp.float32)
    return out


def make_params(key: jax.Array, dims: Dims, dtype) -> dict:
    """The whole tree in ``dtype``. Call under ``jax.jit`` with the layouts the
    program wants as ``out_shardings``."""
    cfg = program_config(dims, dtype)
    size, periods = len(cfg.period), cfg.periods

    def scanned(kind):
        places = [at for at, k in enumerate(cfg.period) if k == kind]
        index = jnp.asarray([[dims.lead + p * size + at for at in places] for p in range(periods)])
        return jax.vmap(jax.vmap(
            lambda i: {**attn_params(key, i, dims, kind), **moe_params(key, i, dims)}))(index)

    lead = jax.vmap(lambda i: {**attn_params(key, i, dims, FULL), **dense_params(key, i, dims)})(
        jnp.arange(dims.lead))
    experts = [jax.vmap(lambda i: held_params(key, i, dims))(dims.lead + at + size * jnp.arange(periods))
               for at in range(size)]
    tree = {**top_params(key, dims), "lead": lead,
            "layers": {kind: scanned(kind) for kind in (FULL, SLIDING) if kind in cfg.period},
            "experts": experts}
    return jax.tree.map(lambda x: x.astype(dtype), tree)
