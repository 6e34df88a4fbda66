"""Seeded weights of the KDA / latent-attention expert decoder, made by the
benchmark and handed to the program.

The tree is the one ``ray_tpu.models.kda_moe`` takes: ``embed``, ``final_norm``,
``lm_head``, ``lead`` (the leading KDA layers with their dense feed-forward,
stacked), ``layers`` (ONE period stacked by kind, ``kda`` and ``latent``:
``[periods, a period's, ...]``, each with its router, ``expert_bias`` and shared
expert) and ``experts`` (the routed experts HELD here, a list with one entry a
place in the period: ``[periods, held, ...]``). Every layer has a key of its
own and every expert a key under its layer's, folded from the expert's index
among ALL routed experts, so ``reference_kda_moe.py`` makes one layer's mixer,
or one expert, again from the seed alone, and another share of the same layer
draws the same experts. The key is a traced argument: a new seed compiles
nothing.

What is no matrix: norms one; the depthwise convolution uniform in
+-1/sqrt(taps), no bias; ``A_log = log U(0.5, 1.5)`` a head and ``dt_bias`` a
channel such that at ``x W_g = 0`` the channel's decay a token is ``exp(-t)``,
``t`` log-uniform in [0.001, 3]: decays from 0.05 to 0.999, memories of one to
a thousand tokens (``x W_g`` is about unit normal and moves ``t`` by a factor
of ``e`` or so either way).

**``expert_bias`` is set as the published rule sets it** (``calibrate``): the
bias enters the SELECTION of experts and not their gates, and training moves it
a step against each expert's load until the loads are level. A trained router
is balanced because of this bias; a zero bias under random weights gives each
seed its own skew, and with selection by GROUPS a skew decides whether this
chip's groups are chosen at all, so the seed would set how many pairs land here
and how many experts a step reads. ``calibrate`` plays sequences of random
tokens of the vocabulary's slice through the reference's own layers, the layers
below with their bias already set, and at each expert layer steps ``b_e +=
gamma * sign(mean load - load_e)`` over those positions' scores until every
expert's load is within ``LEVEL`` of the mean (or ``STEPS`` steps). The program
and the reference get the same numbers: the bias is an argument of both.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from chipbench.weights import _dense, seed_key  # noqa: F401 - seed_key is this module's too

CAL_SEQUENCES = 4  # sequences of random tokens the bias is fitted on
LEVEL = 0.1  # every expert's load within this share of the mean
STEPS = 600


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration file, under the names the code uses."""

    vocab: int
    hidden: int
    layers: int
    lead: int  # leading dense layers
    group: int  # a period: its last layer is latent attention, the others KDA
    heads: int
    head: int  # a KDA head's key and value width
    taps: int
    lower: float
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    ffn: int
    expert_ffn: int
    shared_ffn: int
    experts: int  # routed experts of the whole layer: the router's width
    per_token: int
    groups: int
    top_groups: int
    scale: float
    rope_theta: float
    rms_eps: float
    held_first: int
    held: int  # routed experts of this chip's share

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        return cls(
            vocab=int(cfg["vocab_size"]), hidden=int(cfg["hidden_size"]),
            layers=int(cfg["num_hidden_layers"]), lead=int(cfg["first_k_dense_replace"]),
            group=int(cfg["layer_group_size"]), heads=int(cfg["num_attention_heads"]),
            head=int(cfg["head_dim"]), taps=int(cfg["short_conv_kernel_size"]),
            lower=float(cfg["kda_lower_bound"]), kv_rank=int(cfg["kv_lora_rank"]),
            nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
            v_dim=int(cfg["v_head_dim"]), ffn=int(cfg["intermediate_size"]),
            expert_ffn=int(cfg["moe_intermediate_size"]),
            shared_ffn=int(cfg["moe_shared_expert_intermediate_size"]),
            experts=int(cfg["num_experts_published"]), per_token=int(cfg["num_experts_per_tok"]),
            groups=int(cfg["n_group"]), top_groups=int(cfg["topk_group"]),
            scale=float(cfg["routed_scaling_factor"]), rope_theta=float(cfg["rope_theta"]),
            rms_eps=float(cfg["rms_norm_eps"]), held_first=int(cfg["experts_held_first"]),
            held=int(cfg["num_experts"]),
        )

    @property
    def key_dim(self) -> int:
        return self.heads * self.head

    def kind(self, i: int) -> str:
        return "latent" if (i + 1) % self.group == 0 else "kda"

    def of_kind(self, kind: str) -> tuple:
        """The numbers, among all layers, of the scanned layers of one kind."""
        return tuple(i for i in range(self.lead, self.layers) if self.kind(i) == kind)

    def mixer_shapes(self, kind: str) -> dict:
        d, h = self.hidden, self.heads
        both = {"norm": (d,), "mlp_norm": (d,)}
        if kind == "kda":
            return {**both, "w_qkv": (d, 3 * self.key_dim), "conv_w": (3 * self.key_dim, self.taps),
                    "w_g": (d, self.key_dim), "dt_bias": (self.key_dim,), "A_log": (h,), "w_b": (d, h),
                    "w_z": (d, self.key_dim), "o_norm": (self.head,), "w_o": (self.key_dim, d)}
        qk = self.nope + self.rope
        return {**both, "w_q": (d, h * qk), "q_norm": (qk,), "kv_norm": (self.kv_rank,),
                "w_dkv": (d, self.kv_rank + self.rope),
                "w_ukv": (self.kv_rank, h * (self.nope + self.v_dim)), "w_hg": (d, h),
                "wo": (h * self.v_dim, d)}


def program_config(dims: Dims, dtype):
    """The program's configuration object for these sizes."""
    from ray_tpu.models.kda_moe import KDAMoEConfig

    return KDAMoEConfig(
        num_hidden_layers=dims.layers, first_k_dense_replace=dims.lead, vocab_size=dims.vocab,
        hidden_size=dims.hidden, layer_group_size=dims.group, num_attention_heads=dims.heads,
        head_dim=dims.head, short_conv_kernel_size=dims.taps, kda_lower_bound=dims.lower,
        kv_lora_rank=dims.kv_rank, qk_nope_head_dim=dims.nope, qk_rope_head_dim=dims.rope,
        v_head_dim=dims.v_dim, intermediate_size=dims.ffn, moe_intermediate_size=dims.expert_ffn,
        moe_shared_expert_intermediate_size=dims.shared_ffn, num_experts=dims.experts,
        num_experts_per_tok=dims.per_token, n_group=dims.groups, topk_group=dims.top_groups,
        routed_scaling_factor=dims.scale, rope_theta=dims.rope_theta, rms_norm_eps=dims.rms_eps,
        held_first=dims.held_first, held_count=dims.held, dtype=dtype)


def _layer_key(key, index):
    return jax.random.fold_in(key, index + 1)


def _matrices(key, shapes: dict) -> dict:
    return {name: _dense(jax.random.fold_in(key, j), shape, shape[0])
            for j, (name, shape) in enumerate(shapes.items())}


def mixer_params(key: jax.Array, index, dims: Dims, kind: str) -> dict:
    """Layer ``index``'s two norms and its mixer, of ``kind``, float32."""
    mk = jax.random.fold_in(_layer_key(key, index), 0)
    out = {}
    for j, (name, shape) in enumerate(dims.mixer_shapes(kind).items()):
        k = jax.random.fold_in(mk, j)
        if name.endswith("norm"):
            out[name] = jnp.ones(shape, jnp.float32)
        elif name == "conv_w":
            bound = dims.taps ** -0.5
            out[name] = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        elif name == "A_log":
            out[name] = jnp.log(jax.random.uniform(k, shape, jnp.float32, 0.5, 1.5))
        elif name == "dt_bias":
            t = jnp.exp(jax.random.uniform(k, shape, jnp.float32, jnp.log(1e-3), jnp.log(3.0)))
            share = t / -dims.lower
            out[name] = jnp.log(share) - jnp.log1p(-share)  # over the head's rate, below
        else:
            out[name] = _dense(k, shape, shape[0])
    if kind == "kda":  # sigmoid(exp(A_log) * dt_bias) = t / -lower
        out["dt_bias"] = out["dt_bias"] / jnp.repeat(jnp.exp(out["A_log"]), dims.head)
    return out


def dense_params(key: jax.Array, index, dims: Dims) -> dict:
    """The feed-forward of a leading dense layer."""
    d, f = dims.hidden, dims.ffn
    return _matrices(jax.random.fold_in(_layer_key(key, index), 1),
                     {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)})


def moe_params(key: jax.Array, index, dims: Dims) -> dict:
    """An expert layer's router (over ALL routed experts) and shared expert."""
    d, s = dims.hidden, dims.shared_ffn
    return _matrices(jax.random.fold_in(_layer_key(key, index), 2),
                     {"router": (d, dims.experts), "shared_gate": (d, s), "shared_up": (d, s),
                      "shared_down": (s, d)})


def expert_params(key: jax.Array, index, expert, dims: Dims) -> dict:
    """Routed expert ``expert`` (its index among ALL of the layer's) of layer
    ``index``. Both may be traced."""
    d, f = dims.hidden, dims.expert_ffn
    ek = jax.random.fold_in(jax.random.fold_in(_layer_key(key, index), 3), expert)
    return _matrices(ek, {"e_gate": (d, f), "e_up": (d, f), "e_down": (f, d)})


def held_params(key: jax.Array, index, dims: Dims) -> dict:
    """The routed experts of layer ``index`` that this share holds, stacked."""
    return jax.vmap(lambda e: expert_params(key, index, e, dims))(
        dims.held_first + jnp.arange(dims.held))


def top_params(key: jax.Array, dims: Dims) -> dict:
    """Embedding, final norm and head in float32 (the chip's slice of the
    vocabulary is the whole of what is made)."""
    tk = jax.random.fold_in(key, 0)
    return {
        "embed": _dense(jax.random.fold_in(tk, 0), (dims.vocab, dims.hidden), 1),
        "final_norm": jnp.ones((dims.hidden,), jnp.float32),
        "lm_head": _dense(jax.random.fold_in(tk, 1), (dims.hidden, dims.vocab), dims.hidden),
    }


def make_params(key: jax.Array, dims: Dims, dtype, bias=None) -> dict:
    """The whole tree in ``dtype`` (``expert_bias`` float32 whatever it is: the
    selection adds it to float32 scores); ``bias`` is ``calibrate``'s, ``[expert
    layers, experts]`` (None: zeros). Call under ``jax.jit`` with the layouts
    the program wants as ``out_shardings``."""
    periods = (dims.layers - dims.lead) // dims.group
    lead = jax.vmap(lambda i: {**mixer_params(key, i, dims, "kda"), **dense_params(key, i, dims)})(
        jnp.arange(dims.lead))
    layers = {}
    for kind in ("kda", "latent"):
        numbers = jnp.asarray(dims.of_kind(kind), jnp.int32)
        stacked = jax.vmap(lambda i, kind=kind: {
            **mixer_params(key, i, dims, kind), **moe_params(key, i, dims)})(numbers)
        layers[kind] = jax.tree.map(
            lambda a: a.reshape((periods, len(numbers) // periods) + a.shape[1:]), stacked)
    experts = [jax.vmap(lambda i: held_params(key, i, dims))(
        dims.lead + at + dims.group * jnp.arange(periods)) for at in range(dims.group)]
    tree = {**top_params(key, dims), "lead": lead, "layers": layers, "experts": experts}
    tree = jax.tree.map(lambda x: x.astype(dtype), tree)
    for kind, leaf in bias_leaves(bias, dims).items():
        tree["layers"][kind]["expert_bias"] = leaf
    return tree


def bias_leaves(bias, dims: Dims) -> dict:
    """``bias`` [expert layers, experts] (None: zeros) as the tree holds it:
    kind -> ``[periods, a period's of the kind, experts]`` float32."""
    periods = (dims.layers - dims.lead) // dims.group
    if bias is None:
        bias = jnp.zeros((dims.layers - dims.lead, dims.experts), jnp.float32)
    out = {}
    for kind in ("kda", "latent"):
        numbers = jnp.asarray(dims.of_kind(kind), jnp.int32) - dims.lead
        out[kind] = bias[numbers].astype(jnp.float32).reshape(periods, -1, dims.experts)
    return out


# ---------------------------------------------------------------------------
# The selection bias
# ---------------------------------------------------------------------------


def select(scores, bias, dims: Dims):
    """scores: [t, experts] float32 → the chosen experts [t, per_token]: the
    largest of ``scores + bias`` inside the ``top_groups`` groups (experts side
    by side) whose two largest sum highest."""
    chosen = scores + bias
    t, e = chosen.shape
    if dims.groups > 1:
        grouped = chosen.reshape(t, dims.groups, e // dims.groups)
        best = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        kept = jnp.zeros((t, dims.groups), bool).at[
            jnp.arange(t)[:, None], jax.lax.top_k(best, dims.top_groups)[1]].set(True)
        chosen = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(t, e)
    return jax.lax.top_k(chosen, dims.per_token)[1]


def loads(scores, bias, dims: Dims):
    """Pairs each expert gets from these positions, over the mean: [experts]."""
    experts = select(scores, bias, dims)
    count = jnp.zeros((dims.experts,), jnp.float32).at[experts.reshape(-1)].add(1.0)
    return count / (experts.size / dims.experts)


@functools.partial(jax.jit, static_argnames=("dims",))
def level_bias(scores, dims: Dims):
    """The published rule on fixed scores [t, experts]: ``b_e += gamma *
    sign(1 - load_e)`` with a step that shrinks from 0.03 by 1% a time to
    0.0005, until every load is within ``LEVEL`` of the mean or ``STEPS`` steps.
    → (bias [experts] float32, steps taken, the largest departure left)."""
    def cond(carry):
        _, step, load = carry
        return (step < STEPS) & (jnp.max(jnp.abs(load - 1.0)) > LEVEL)

    def body(carry):
        bias, step, load = carry
        bias = bias + jnp.maximum(0.03 * 0.99 ** step, 5e-4) * jnp.sign(1.0 - load)
        return bias, step + 1, loads(scores, bias, dims)

    zero = jnp.zeros((dims.experts,), jnp.float32)
    bias, steps, load = jax.lax.while_loop(cond, body, (zero, jnp.int32(0), loads(scores, zero, dims)))
    return bias, steps, jnp.max(jnp.abs(load - 1.0))


def calibrate(key: jax.Array, dims: Dims, weight_dtype, t: int, report=None):
    """``expert_bias`` of every expert layer, ``[expert layers, experts]``
    float32: ``CAL_SEQUENCES`` sequences of ``t`` random tokens through the
    reference's layers (its programs for sequences of ``t``, which the check
    after the window uses too), each expert layer's bias levelled on those
    positions' scores before the layer's own experts run. ``report(layer,
    steps, worst)`` is told how each layer went."""
    from chipbench import reference_kda_moe as R

    tokens = jax.random.randint(jax.random.fold_in(key, 0x6B6461), (CAL_SEQUENCES, t), 0, dims.vocab)
    xs = [R.embed(key, row, dims, weight_dtype) for row in tokens]
    rows = []
    for i in range(dims.layers):
        mixed = [R.mixer_block(key, i, x, dims, weight_dtype) for x in xs]
        if i < dims.lead:
            xs = [R.dense_block(key, i, h, y, dims, weight_dtype) for h, y in mixed]
            continue
        scores = jnp.concatenate([R.scores(key, i, y, dims, weight_dtype) for _, y in mixed])
        bias, steps, worst = level_bias(scores, dims)
        if report is not None:
            report(i, int(steps), float(worst))
        rows.append(bias)
        xs = [R.expert_block(key, i, h, y, bias, dims, weight_dtype) for h, y in mixed]
    return jnp.stack(rows)
