"""ZAYA1 — a decoder-only mixture-of-experts language model whose
attention runs wholly inside a compressed latent (Zyphra's ZAYA1-8B;
sizes from its published ``config.json``, the mechanisms as
arXiv:2511.17127 and arXiv:2510.04476, "Compressed Convolutional
Attention", describe them).

Every layer carries TWO values, the hidden state ``x`` and the router
state ``z`` of the layer below (zero below the first):

``h = a1 * x + c1 * CCA(RMSNorm(x))``, ``(m, z') = MoE(RMSNorm(h), z)``,
``x' = a2 * h + c2 * m`` — ``a1, c1, a2, c2`` learned per-channel
residual scales.

- **CCA** (compressed convolutional attention). Queries and keys are
  projected DOWN to ``heads x head_dim`` and ``kv_heads x head_dim``
  latents and everything up to the softmax happens there; nothing is
  projected back up before it (which is what separates it from latent
  attention with an up-projection). Each latent is mixed ALONG THE
  SEQUENCE by two causal convolutions, zero-padded on the left — a
  depthwise one (``conv_time`` taps a channel), then one grouped by
  head (``conv_head`` taps of a ``head_dim x head_dim`` matrix a head)
  — and the pre-convolution latents come back in as the q-k mean: a
  query head adds half of itself and of its key head, a key head half of
  itself and of the mean of its query heads. Each head of q and k is
  then L2-normalised to length ``sqrt(head_dim)`` in float32 (k times a
  learned temperature a key head), and the first ``rotary_fraction`` of
  its dimensions is rotated by position (rotate-half pairing inside
  that part). The value of a token is half its own projection and half
  the PREVIOUS token's: the first half of the key heads read this
  token, the second half the one before. Causal softmax at
  ``1 / sqrt(head_dim)`` over grouped key heads
  (:func:`tpfl.parallel.ring_attention.blockwise_attention`), and one
  projection back to the model's width.
- **experts**: the router is an MLP, not a matrix. ``z' = RMSNorm(h)
  W_down + gamma z`` (``gamma`` a learned scalar, zero at the start:
  the router of a layer reads the router state of the layer below),
  ``logits = W_3 gelu(W_2 gelu(W_1 RMSNorm(z')))``, all float32; ONE
  expert a token, the most probable under the softmax over all
  ``n_experts`` plus a BALANCING BIAS, weighted by that probability
  itself (:func:`tpfl.parallel.moe.route_by_probability`). The bias is
  state no gradient reaches (``balance_bias [n_experts]`` in the mutable
  collection ``moe_stats``), and here it is FROZEN: zero at the start
  and moved by nothing — the published recipe's controller, whose law
  the published config does not give, is not in this model. SwiGLU experts of
  width ``expert_dim``, no shared expert. As ``MellumLM``, the model is
  told which experts it HOLDS (``held_experts`` of them from
  ``first_expert``) and computes their part of the layer
  (:func:`tpfl.parallel.moe.held_experts_moe`); ``z'`` does not depend
  on what is held.

The bias-free output head is the embedding, transposed, and owns its
loss (``head_cross_entropy``). :class:`ZayaLM` takes the list ``layers``
of published layer indices it runs — a pipeline stage is data, not a
second code path (a stage that does not start at layer 0 starts from
``z = 0`` here; a deployment hands ``z`` on with ``x``). Matmuls run in
``compute_dtype``; parameters, norms, the q / k normalisation, the
rotary table, the router and softmax are float32. Every matrix is drawn
normal(0, ``INIT_STD``) — the two that WRITE to the residual stream
(attention's output, the experts' down projection) ``1 / sqrt(2 x
n_layers)`` smaller, as Megatron-LM draws them — and the convolutions as
``torch.nn.Conv1d`` draws them: an untrained model has to route evenly,
since it stands in for a trained one whose balancing bias has settled.

Each layer's router also COUNTS (``moe_load [n_experts]`` in
``moe_stats``), as ``MellumLM``'s do; the engine carries the collection
as ``aux`` and folds load and bias by the weighted mean (a zero bias
stays zero).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpfl.models.head_loss import head_cross_entropy
from tpfl.models.mellum import RMSNorm, apply_rotary, rotary_frequencies
from tpfl.parallel.moe import held_experts_moe, route_by_probability
from tpfl.parallel.ring_attention import blockwise_attention

#: Standard deviation every matrix is drawn with (the config's
#: ``initializer_range`` is not public: 0.006 as DeepSeek-V3 states for
#: its own sparse experts). It sets how far an UNTRAINED router MLP is
#: from linear: GELU's even part gives every expert's logit an offset
#: that all tokens share, 0.8 x the first layer's pre-activation spread
#: (16 x this) of what tells tokens apart. At 0.02 one expert of the
#: first layer takes 11-15% of the tokens (1 / 16 is 6%) and the half a
#: chip holds 0.54 +- 0.05 of them over seeds; at 0.006 no expert takes
#: more than 16% anywhere and the held half 0.50 +- 0.015 (CPU,
#: published widths, residual writers scaled: PERF.md §6, PR 34).
INIT_STD = 0.006
_normal = nn.initializers.normal(stddev=INIT_STD)
HIGHEST = jax.lax.Precision.HIGHEST


def _conv_init(in_axis, out_axis, batch_axis=()):
    """``torch.nn.Conv1d``'s default: uniform within ``1 / sqrt(fan_in)``."""
    return nn.initializers.variance_scaling(
        1.0 / 3.0, "fan_in", "uniform", in_axis=in_axis, out_axis=out_axis,
        batch_axis=batch_axis,
    )


def shift_time(u, lag: int):
    """``u [B, S, ...]`` delayed by ``lag`` tokens: ``out[t] = u[t -
    lag]``, zero before the sequence starts."""
    if lag == 0:
        return u
    pad = [(0, 0)] * u.ndim
    pad[1] = (lag, 0)
    return jnp.pad(u[:, : u.shape[1] - lag], pad)


def depthwise_causal_conv(u, w):
    """``u [B, S, C]`` float32, ``w [taps, C]``: ``sum_j w[j] *
    u[t - j]`` a channel."""
    return sum(w[j] * shift_time(u, j) for j in range(w.shape[0]))


def head_causal_conv(u, w, dtype):
    """``u [B, S, H, D]``, ``w [H, taps, D, D]``: ``sum_j u[t - j]_h @
    w[h, j]`` a head, multiplied in ``dtype``, float32 out."""
    u, w = u.astype(dtype), w.astype(dtype)
    return sum(
        jnp.einsum(
            "bshc,hcd->bshd", shift_time(u, j), w[:, j],
            preferred_element_type=jnp.float32,
        )
        for j in range(w.shape[1])
    )


def qk_mean(q_lat, k_lat):
    """Half of the pre-convolution latents, mixed across grouped heads:
    (for ``q [B, S, H, D]``: itself plus its key head; for ``k [B, S,
    Hkv, D]``: itself plus the mean of its query heads) / 2."""
    b, s, heads, d = q_lat.shape
    kv_heads = k_lat.shape[2]
    grouped = q_lat.reshape(b, s, kv_heads, heads // kv_heads, d)
    for_q = (grouped + k_lat[:, :, :, None, :]).reshape(q_lat.shape) / 2
    for_k = (k_lat + jnp.mean(grouped, axis=3)) / 2
    return for_q, for_k


def unit_heads(u):
    """Each head of ``u [.., D]`` scaled to length ``sqrt(D)``."""
    norm = jnp.sqrt(jnp.sum(u * u, axis=-1, keepdims=True))
    return u * (u.shape[-1] ** 0.5) / norm


def apply_partial_rotary(u, inv_freq):
    """Rotate the first ``2 len(inv_freq)`` dimensions of each head of
    ``u [B, S, H, D]`` by position (:func:`mellum.apply_rotary` on that
    part); the rest passes untouched."""
    rotated = 2 * inv_freq.shape[0]
    return jnp.concatenate(
        [apply_rotary(u[..., :rotated], inv_freq, 1.0), u[..., rotated:]],
        axis=-1,
    )


class ZayaCCA(nn.Module):
    """Compressed convolutional attention (module docstring); ``y`` is
    the normed input in the compute dtype."""

    heads: int
    kv_heads: int
    head_dim: int
    conv_time: int
    conv_head: int
    rotary_fraction: float
    theta: float
    out_std: float
    compute_dtype: Any

    @nn.compact
    def __call__(self, y):
        b, s, dim = y.shape
        hd, dtype = self.head_dim, self.compute_dtype
        dense = lambda n, name, init=_normal: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=dtype, kernel_init=init, name=name
        )
        with jax.named_scope("cca_proj"):
            q_lat = dense(self.heads * hd, "q_proj")(y)
            k_lat = dense(self.kv_heads * hd, "k_proj")(y)
            # Both value projections side by side: the first half of the
            # key heads from this token, the second from the one before.
            v = dense(self.kv_heads * hd, "v_proj")(y)
        with jax.named_scope("cca_mix"):
            mixed = []
            for name, lat, heads in (("q", q_lat, self.heads), ("k", k_lat, self.kv_heads)):
                w_time = self.param(
                    f"{name}_conv_time", _conv_init(0, 1), (self.conv_time, heads * hd)
                )
                w_head = self.param(
                    f"{name}_conv_head", _conv_init((1, 2), 3, 0),
                    (heads, self.conv_head, hd, hd),
                )
                lat = lat.astype(jnp.float32)
                conv = depthwise_causal_conv(lat, w_time).reshape(b, s, heads, hd)
                mixed.append((
                    lat.reshape(b, s, heads, hd), head_causal_conv(conv, w_head, dtype)
                ))
            (q_lat, q), (k_lat, k) = mixed
            q_mean, k_mean = qk_mean(q_lat, k_lat)
            temperature = self.param(
                "k_temperature", nn.initializers.ones, (self.kv_heads,)
            )
            q = unit_heads(q + q_mean)
            k = unit_heads(k + k_mean) * temperature[:, None]
            now, before = jnp.split(v, 2, axis=-1)
            v = jnp.concatenate([now, shift_time(before, 1)], axis=-1)
        inv_freq, _ = rotary_frequencies(
            int(hd * self.rotary_fraction), self.theta
        )
        q = apply_partial_rotary(q, inv_freq).astype(dtype)
        k = apply_partial_rotary(k, inv_freq).astype(dtype)
        out = blockwise_attention(
            q, k, v.reshape(b, s, self.kv_heads, hd), causal=True
        )
        with jax.named_scope("cca_proj"):
            return dense(
                dim, "o_proj", nn.initializers.normal(stddev=self.out_std)
            )(out.reshape(b, s, -1))


class ZayaMoE(nn.Module):
    """The router MLP with its state, the frozen balancing bias, the held
    experts' part of the top-1 expert layer, and the router's count.
    ``y`` is the float32 norm output, ``z`` the router state of the
    layer below."""

    n_experts: int
    expert_dim: int
    router_dim: int
    held_experts: int
    first_expert: int
    norm_eps: float
    out_std: float
    compute_dtype: Any

    @nn.compact
    def __call__(self, y, z):
        b, s, dim = y.shape
        w_in = self.param(
            "gate_up_proj", _normal, (self.held_experts, dim, 2 * self.expert_dim)
        )
        w_out = self.param(
            "down_proj", nn.initializers.normal(stddev=self.out_std),
            (self.held_experts, self.expert_dim, dim),
        )
        with jax.named_scope("moe_router"):
            # float32 at full precision whatever the compute dtype: a
            # choice is a comparison of near-equal numbers.
            dot = lambda x, name, n: jnp.dot(  # noqa: E731
                x, self.param(name, _normal, (x.shape[-1], n)), precision=HIGHEST
            )
            carry = self.param("router_carry", nn.initializers.zeros, ())
            z = dot(y, "router_down", self.router_dim) + carry * z
            hidden = RMSNorm(self.norm_eps, name="router_norm")(z)
            for name in ("router_fc1", "router_fc2"):
                hidden = jax.nn.gelu(dot(hidden, name, self.router_dim), approximate=False)
            logits = dot(hidden, "router_out", self.n_experts)
            zeros = lambda: jnp.zeros((self.n_experts,), jnp.float32)  # noqa: E731
            stats = self.variable("moe_stats", "moe_load", zeros)
            bias = self.variable("moe_stats", "balance_bias", zeros)
            gate, expert, load = route_by_probability(
                logits.reshape(b * s, self.n_experts), 1, bias.value
            )
            if self.is_mutable_collection("moe_stats") and not self.is_initializing():
                stats.value = load
        with jax.named_scope("moe_dispatch"):
            rows = y.reshape(b * s, dim).astype(self.compute_dtype)
        out = held_experts_moe(
            rows, gate, expert, w_in, w_out, self.first_expert, self.n_experts
        )
        return out.reshape(b, s, dim), z


class ZayaBlock(nn.Module):
    """One published layer: ``(x, z) -> (x', z')``."""

    heads: int
    kv_heads: int
    head_dim: int
    conv_time: int
    conv_head: int
    rotary_fraction: float
    theta: float
    n_experts: int
    expert_dim: int
    router_dim: int
    held_experts: int
    first_expert: int
    norm_eps: float
    out_std: float
    compute_dtype: Any

    def _scaled_sum(self, name: str, x, update):
        """``a * x + c * update`` with learned per-channel ``a``, ``c``
        (ones at the start), float32 inside."""
        dim = x.shape[-1]
        keep = self.param(f"{name}_residual_scale", nn.initializers.ones, (dim,))
        add = self.param(f"{name}_update_scale", nn.initializers.ones, (dim,))
        with jax.named_scope("residual_scale"):
            return (
                keep * x.astype(jnp.float32) + add * update.astype(jnp.float32)
            ).astype(x.dtype)

    @nn.compact
    def __call__(self, x, z):
        # Plain scopes a trace sums by (not ``tpfl.*``: those are the
        # round body's legs).
        with jax.named_scope("cca"):
            mixed = ZayaCCA(
                self.heads, self.kv_heads, self.head_dim, self.conv_time,
                self.conv_head, self.rotary_fraction, self.theta,
                self.out_std, self.compute_dtype, name="attention",
            )(RMSNorm(self.norm_eps, name="norm_attention")(x).astype(
                self.compute_dtype
            ))
        h = self._scaled_sum("attention", x, mixed)
        normed = RMSNorm(self.norm_eps, name="norm_moe")(h)
        with jax.named_scope("moe"):
            out, z = ZayaMoE(
                self.n_experts, self.expert_dim, self.router_dim,
                self.held_experts, self.first_expert, self.norm_eps,
                self.out_std, self.compute_dtype, name="moe",
            )(normed, z)
        return self._scaled_sum("moe", h, out), z


class ZayaLM(nn.Module):
    """The ZAYA1 language model, or the pipeline stage of it that holds
    the published layers ``layers`` (default: all ``n_layers``) and the
    experts ``first_expert .. first_expert + held_experts - 1`` of each
    (default: all ``n_experts``). Each block is recomputed in the
    backward pass (``nn.remat``), carrying ``(x, z)``: one block's
    activations live at a time."""

    vocab: int = 512
    dim: int = 64
    heads: int = 4
    kv_heads: int = 2
    head_dim: int = 16
    n_layers: int = 4
    layers: Optional[Sequence[int]] = None
    conv_time: int = 2
    conv_head: int = 2
    rotary_fraction: float = 0.5
    rope_theta: float = 5000000.0
    n_experts: int = 8
    expert_dim: int = 32
    router_dim: int = 16
    held_experts: Optional[int] = None
    first_expert: int = 0
    norm_eps: float = 1e-5
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
        if self.heads % self.kv_heads or self.kv_heads % 2 or not (
            0 <= self.first_expert <= self.n_experts - held
        ):
            raise ValueError(
                f"kv_heads ({self.kv_heads}) must be even (half read the "
                f"previous token's value) and divide heads ({self.heads}), and "
                f"experts {self.first_expert}..{self.first_expert + held - 1} "
                f"must lie among the {self.n_experts}"
            )
        layers = tuple(
            range(self.n_layers) if self.layers is None else self.layers
        )
        embed = nn.Embed(
            self.vocab, self.dim, dtype=self.compute_dtype, name="embed",
            embedding_init=_normal,
        )
        x = embed(tokens)
        z = jnp.zeros((*tokens.shape, self.router_dim), jnp.float32)
        # The projections that WRITE to the residual stream (attention's
        # output, the experts' down projection) are drawn 1 / sqrt(2 x
        # depth) smaller, the published depth's, as Megatron-LM draws
        # them: drawn like the rest, an untrained model's stream is what
        # its first layers' outputs have in common, not the token, and
        # from the fourth layer on one expert takes a third of all tokens
        # (measured at both 0.02 and 0.006: PERF.md §6, PR 34).
        out_std = INIT_STD / (2.0 * self.n_layers) ** 0.5
        block = nn.remat(ZayaBlock)
        for layer in layers:
            x, z = block(
                heads=self.heads, kv_heads=self.kv_heads,
                head_dim=self.head_dim, conv_time=self.conv_time,
                conv_head=self.conv_head,
                rotary_fraction=self.rotary_fraction, theta=self.rope_theta,
                n_experts=self.n_experts, expert_dim=self.expert_dim,
                router_dim=self.router_dim, held_experts=held,
                first_expert=self.first_expert, norm_eps=self.norm_eps,
                out_std=out_std,
                compute_dtype=self.compute_dtype,
                name=f"layer_{layer}",
            )(x, z)
        x = RMSNorm(self.norm_eps, name="norm_out")(x).astype(self.compute_dtype)
        if targets is None:
            return embed.attend(x).astype(jnp.float32)
        # Tied and bias-free: the head's kernel is the embedding.
        return head_cross_entropy(x, embed.embedding.T, None, targets)
