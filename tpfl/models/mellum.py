"""Mellum 2 — a decoder-only mixture-of-experts language model with
banded and full attention mixed (JetBrains' Mellum2-12B-A2.5B; sizes and
layer pattern from its published ``config.json``).

Every layer is ``h = x + Attn(RMSNorm(x)); x' = h + MoE(RMSNorm(h))``:

- **attention** over grouped key/value heads (query head ``p`` reads key
  head ``p // (heads // kv_heads)``), no biases, rotary positions on the
  whole head (rotate-half pairing). Of every ``period`` layers the last
  is FULL causal attention under a YaRN rotary table
  (:func:`rotary_frequencies`, cos / sin scaled by ``attention_factor``);
  the others see the ``window`` nearest keys under the plain table;
- **experts**: a softmax router over all ``n_experts`` in float32, the
  ``top_k`` largest with their probabilities normalised over the chosen,
  SwiGLU experts of width ``expert_dim``, no shared expert. The model is
  told which experts it HOLDS (``held_experts`` of them from
  ``first_expert``): the router keeps its published width and the layer
  computes the held experts' part of the sum
  (:func:`tpfl.parallel.moe.held_experts_moe` — dropless, grouped
  matmuls over the rows routed here), the share of one chip of an
  expert-parallel deployment. With all of them held it is the whole
  layer.

The embedding and the bias-free output head are untied; the head owns
its loss (``head_cross_entropy``). :class:`MellumLM` takes the list
``layers`` of published layer indices it runs — a pipeline stage is
data, not a second code path. Matmuls run in ``compute_dtype``;
parameters, norms, the rotary tables, the router and softmax are
float32.

Each layer's router also COUNTS: the share of the step's token-choices
each of the ``n_experts`` received, ``moe_load [n_experts]``, in the
mutable collection ``moe_stats`` — which the engine carries as ``aux``
and folds by ``aux_mode="mean"`` into the federation's mean load per
expert (docs/parallelism.md).
"""

from __future__ import annotations

import math
from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpfl.models.head_loss import head_cross_entropy
from tpfl.parallel.moe import held_experts_moe, route_top_k
from tpfl.parallel.ring_attention import blockwise_attention


def rotary_frequencies(
    head_dim: int, theta: float, yarn: Optional[dict] = None
) -> tuple:
    """``(inv_freq [head_dim / 2] float32, attention_factor)`` of a
    rotary table. Plain: ``theta ** (-2 i / head_dim)``, factor 1. YaRN
    (Peng et al. 2023; ``yarn`` holds ``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    ``attention_factor``): dimension pairs that turn more than
    ``beta_fast`` times over the original context keep their frequency,
    those that turn less than ``beta_slow`` times are slowed by
    ``factor``, with a linear ramp between the two correction bounds."""
    half = head_dim // 2
    base = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / head_dim)
    if yarn is None:
        return base, 1.0
    original = yarn["original_max_position_embeddings"]

    def bound(turns: float) -> float:
        return (head_dim * math.log(original / (turns * 2 * math.pi))) / (
            2 * math.log(theta)
        )

    low = max(math.floor(bound(yarn["beta_fast"])), 0)
    high = min(math.ceil(bound(yarn["beta_slow"])), head_dim - 1)
    ramp = jnp.clip(
        (jnp.arange(half, dtype=jnp.float32) - low) / max(high - low, 1e-3),
        0.0, 1.0,
    )
    inv_freq = ramp * base / yarn["factor"] + (1.0 - ramp) * base
    return inv_freq, float(yarn["attention_factor"])


@jax.named_scope("rope")
def apply_rotary(x, inv_freq, factor: float):
    """Rotate ``x [B, S, H, D]`` by its position (rotate-half pairing:
    dimension ``i`` with ``i + D / 2``), float32 inside, ``x``'s dtype
    out; cos and sin are multiplied by ``factor``."""
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = (jnp.cos(angles) * factor)[None, :, None, :]
    sin = (jnp.sin(angles) * factor)[None, :, None, :]
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin], axis=-1
    ).astype(x.dtype)


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * scale``, computed and returned in
    float32 (the router reads it unrounded)."""

    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        xf = x.astype(jnp.float32)
        return xf * jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps
        ) * scale


class MellumAttention(nn.Module):
    """Grouped-head causal attention with rotary positions; a band of
    ``window`` keys, or (``window`` None) full under ``yarn``."""

    heads: int
    kv_heads: int
    head_dim: int
    window: Optional[int]
    theta: float
    yarn: Optional[Any]
    compute_dtype: Any

    @nn.compact
    def __call__(self, y):
        b, s, dim = y.shape
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.compute_dtype, name=name
        )
        inv_freq, factor = rotary_frequencies(
            self.head_dim, self.theta,
            None if self.window is not None else dict(self.yarn),
        )
        q = dense(self.heads * self.head_dim, "q_proj")(y)
        k = dense(self.kv_heads * self.head_dim, "k_proj")(y)
        v = dense(self.kv_heads * self.head_dim, "v_proj")(y)
        q = apply_rotary(
            q.reshape(b, s, self.heads, self.head_dim), inv_freq, factor
        )
        k = apply_rotary(
            k.reshape(b, s, self.kv_heads, self.head_dim), inv_freq, factor
        )
        out = blockwise_attention(
            q, k, v.reshape(b, s, self.kv_heads, self.head_dim), causal=True,
            window=self.window,
        )
        return dense(dim, "o_proj")(out.reshape(b, s, -1))


class MellumMoE(nn.Module):
    """The held experts' part of the top-k expert layer, and the
    router's count. ``y`` is the float32 norm output."""

    n_experts: int
    top_k: int
    expert_dim: int
    held_experts: int
    first_expert: int
    compute_dtype: Any

    @nn.compact
    def __call__(self, y):
        b, s, dim = y.shape
        init = nn.initializers.lecun_normal
        router = self.param("router", init(), (dim, self.n_experts))
        w_in = self.param(
            "gate_up_proj", init(in_axis=-2, out_axis=-1, batch_axis=0),
            (self.held_experts, dim, 2 * self.expert_dim),
        )
        w_out = self.param(
            "down_proj", init(in_axis=-2, out_axis=-1, batch_axis=0),
            (self.held_experts, self.expert_dim, dim),
        )
        tokens = y.reshape(b * s, dim)
        with jax.named_scope("moe_router"):
            # float32 at full precision whatever the compute dtype: a
            # choice is a comparison of near-equal numbers.
            logits = jnp.dot(
                tokens, router, precision=jax.lax.Precision.HIGHEST
            )
            gate, expert, load = route_top_k(logits, self.top_k)
        stats = self.variable(
            "moe_stats", "moe_load",
            lambda: jnp.zeros((self.n_experts,), jnp.float32),
        )
        if self.is_mutable_collection("moe_stats") and not self.is_initializing():
            stats.value = load
        with jax.named_scope("moe_dispatch"):
            rows = tokens.astype(self.compute_dtype)
        out = held_experts_moe(
            rows, gate, expert, w_in, w_out, self.first_expert, self.n_experts
        )
        return out.reshape(b, s, dim)


class MellumBlock(nn.Module):
    """One published layer."""

    full: bool
    heads: int
    kv_heads: int
    head_dim: int
    window: int
    theta: float
    yarn: Any
    n_experts: int
    top_k: int
    expert_dim: int
    held_experts: int
    first_expert: int
    norm_eps: float
    compute_dtype: Any

    @nn.compact
    def __call__(self, x):
        # Plain scopes a trace sums by (not ``tpfl.*``: those are the
        # round body's legs).
        with jax.named_scope("full_attention" if self.full else "window_attention"):
            mixed = MellumAttention(
                self.heads, self.kv_heads, self.head_dim,
                None if self.full else self.window, self.theta, self.yarn,
                self.compute_dtype, name="attention",
            )(RMSNorm(self.norm_eps, name="norm_attention")(x).astype(
                self.compute_dtype
            ))
        h = x + mixed
        normed = RMSNorm(self.norm_eps, name="norm_moe")(h)
        with jax.named_scope("moe"):
            out = MellumMoE(
                self.n_experts, self.top_k, self.expert_dim,
                self.held_experts, self.first_expert, self.compute_dtype,
                name="moe",
            )(normed)
        return h + out


class MellumLM(nn.Module):
    """The Mellum 2 language model, or the pipeline stage of it that
    holds the published layers ``layers`` (default: all ``n_layers``)
    and the experts ``first_expert .. first_expert + held_experts - 1``
    of each (default: all ``n_experts``). Layer ``l`` is full attention
    where ``l % period == period - 1``, banded elsewhere. Each block is
    recomputed in the backward pass (``nn.remat``): one block's
    activations live at a time."""

    vocab: int = 512
    dim: int = 64
    heads: int = 4
    kv_heads: int = 2
    head_dim: int = 16
    n_layers: int = 4
    layers: Optional[Sequence[int]] = None
    period: int = 4
    window: int = 1024
    rope_theta: float = 500000.0
    yarn: Any = (
        ("factor", 16.0), ("original_max_position_embeddings", 8192),
        ("beta_fast", 32.0), ("beta_slow", 1.0),
        ("attention_factor", 1.2772588722239782),
    )
    n_experts: int = 8
    top_k: int = 2
    expert_dim: int = 32
    held_experts: Optional[int] = None
    first_expert: int = 0
    norm_eps: float = 1e-6
    compute_dtype: Any = jnp.bfloat16

    # What the engine reads off a module (docs/parallelism.md): token ids
    # in, a head that owns its loss, no model-axis sharding rule yet; the
    # mutable collection ``moe_stats`` rides as ``aux``.
    input_dtype = jnp.int32
    owns_cross_entropy = True
    spec_layout = "replicated"

    @nn.compact
    def __call__(self, tokens, train: bool = False, targets=None):
        held = self.n_experts if self.held_experts is None else self.held_experts
        if self.heads % self.kv_heads or not (
            0 <= self.first_expert <= self.n_experts - held
        ):
            raise ValueError(
                f"kv_heads ({self.kv_heads}) must divide heads ({self.heads}), "
                f"and experts {self.first_expert}..{self.first_expert + held - 1} "
                f"must lie among the {self.n_experts}"
            )
        layers = tuple(
            range(self.n_layers) if self.layers is None else self.layers
        )
        # Unit variance (torch.nn.Embedding's default), not the zoo's
        # 1 / sqrt(dim): a token's identity has to dominate the residual
        # stream of an UNTRAINED model. At 1 / sqrt(dim) the first
        # layers' outputs — an average of values, much the same for every
        # token — outweigh it, every token then ranks the experts alike,
        # and from the second layer on all tokens choose the same eight
        # (measured on the chip: PERF.md §6, PR 32).
        x = nn.Embed(
            self.vocab, self.dim, dtype=self.compute_dtype, name="embed",
            embedding_init=nn.initializers.normal(stddev=1.0),
        )(tokens)
        block = nn.remat(MellumBlock)
        for layer in layers:
            x = block(
                full=layer % self.period == self.period - 1, heads=self.heads,
                kv_heads=self.kv_heads, head_dim=self.head_dim,
                window=self.window, theta=self.rope_theta, yarn=self.yarn,
                n_experts=self.n_experts, top_k=self.top_k,
                expert_dim=self.expert_dim, held_experts=held,
                first_expert=self.first_expert, norm_eps=self.norm_eps,
                compute_dtype=self.compute_dtype, name=f"layer_{layer}",
            )(x)
        x = RMSNorm(self.norm_eps, name="norm_out")(x).astype(self.compute_dtype)
        head = nn.Dense(
            self.vocab, use_bias=False, dtype=self.compute_dtype, name="head"
        )
        if targets is None:
            return head(x).astype(jnp.float32)
        if self.is_initializing():
            head(x)  # creates the head's kernel under its name
        return head_cross_entropy(
            x, head.variables["params"]["kernel"], None, targets
        )
