"""SambaY — a decoder-hybrid-decoder language model (Ren et al. 2025,
"Decoder-Hybrid-Decoder Architecture for Efficient Reasoning with Long
Generation", arXiv:2507.06607; the family of Phi-4-mini-flash-reasoning).

Four kinds of token mixer in one model, two of which READ WHAT AN
EARLIER LAYER PRODUCED, so a block is not a function of its input alone:

- the self-decoder (published layers ``0 .. n/2 + 1``) alternates Mamba-1
  state-space layers with sliding-window attention; its last Mamba layer
  (``n/2``) keeps its scan output as the MEMORY ``m``, and its last layer
  (``n/2 + 1``) is full causal attention that keeps its keys and values;
- the cross-decoder (``n/2 + 2 .. n - 1``) alternates gated memory units
  — an element-wise gate on ``m`` at the same token, no recurrence and no
  attention — with cross-attention that owns only a query and an output
  projection and reads layer ``n/2 + 1``'s keys and values.

Attention is DIFFERENTIAL (Ye et al. 2024, arXiv:2410.05258): two
softmax maps per head, the second subtracted with a learned weight, over
grouped key heads and one double-width value. There is no positional
encoding of any kind (the Mamba layers carry position). Every layer is
``h = x + Mixer(LN(x)); x' = h + SwiGLU(LN(h))``; the output head is the
token embedding, transposed, without bias.

:class:`SambaYLM` takes the PUBLISHED depth ``n_layers`` (which fixes the
pattern, :func:`layer_kind`) and the list ``layers`` of published layer
indices it runs: a pipeline stage of the model is data, not a second code
path. Matmuls run in ``compute_dtype``; parameters, the scan (delta, A,
state), softmax, norms and the differential weight are float32.
"""

from __future__ import annotations

import math
from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpfl.models.head_loss import head_cross_entropy
from tpfl.parallel.ring_attention import blockwise_attention
from tpfl.parallel.selective_scan import selective_scan

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"


def layer_kind(layer: int, n_layers: int, mb_per_layer: int = 2) -> str:
    """The mixer of published layer ``layer`` of ``n_layers``. A Mamba
    layer every ``mb_per_layer`` up to the middle; attention between them
    (windowed, except the self-decoder's last layer, which is full and
    lends its keys and values); past it a gated memory unit where a
    Mamba layer would be and cross-attention where attention would be."""
    half = n_layers // 2
    if n_layers % (2 * mb_per_layer) or not 0 <= layer < n_layers:
        raise ValueError(
            f"layer {layer} of {n_layers}: the pattern needs a depth that "
            f"is a multiple of {2 * mb_per_layer} and an index inside it"
        )
    on_beat = layer % mb_per_layer == 0
    if layer <= half + 1:
        if on_beat:
            return MAMBA
        return FULL if layer == half + 1 else WINDOW
    return GMU if on_beat else CROSS


def lambda_init(layer: int) -> float:
    """Differential attention's initial weight of the second map, by the
    PUBLISHED layer index."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """Mamba's: ``softplus(bias)`` log-uniform in [1e-3, 1e-1]."""
    dt = jnp.exp(
        jax.random.uniform(key, shape, jnp.float32)
        * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3)
    )
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``A = -(1 .. N)`` for every channel (S4D-real)."""
    return jnp.log(
        jnp.broadcast_to(jnp.arange(1, shape[1] + 1, dtype=jnp.float32), shape)
    ).astype(dtype)


class MambaMixer(nn.Module):
    """Mamba-1 mixer. Returns ``(output [B, S, dim], s)`` with ``s`` the
    scan's output BEFORE the gate — the memory a gated memory unit reads."""

    d_inner: int
    d_state: int
    d_conv: int
    dt_rank: int
    compute_dtype: Any

    @nn.compact
    def __call__(self, y):
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.compute_dtype, name=name
        )
        a, z = jnp.split(dense(2 * self.d_inner, "in_proj")(y), 2, axis=-1)
        # Causal depthwise convolution as d_conv shifted products.
        kernel = self.param(
            "conv_kernel", nn.initializers.lecun_normal(),
            (self.d_conv, self.d_inner),
        ).astype(self.compute_dtype)
        conv_bias = self.param(
            "conv_bias", nn.initializers.zeros, (self.d_inner,)
        ).astype(self.compute_dtype)
        s_len = a.shape[1]
        padded = jnp.pad(a, ((0, 0), (self.d_conv - 1, 0), (0, 0)))
        c = nn.silu(conv_bias + sum(
            padded[:, j:j + s_len] * kernel[j] for j in range(self.d_conv)
        ))
        low, bmat, cmat = jnp.split(
            dense(self.dt_rank + 2 * self.d_state, "x_proj")(c),
            [self.dt_rank, self.dt_rank + self.d_state], axis=-1,
        )
        dt_kernel = self.param(
            "dt_kernel", nn.initializers.lecun_normal(),
            (self.dt_rank, self.d_inner),
        )
        dt_bias = self.param("dt_bias", _dt_bias_init, (self.d_inner,))
        # float32 in and out (on a TPU a default-precision float32 matmul
        # multiplies in bf16 and accumulates in float32: no slower).
        delta = jax.nn.softplus(
            jnp.dot(low.astype(jnp.float32), dt_kernel) + dt_bias
        )
        a_log = self.param("a_log", _a_log_init, (self.d_inner, self.d_state))
        skip = self.param("d_skip", nn.initializers.ones, (self.d_inner,))
        s = selective_scan(c, delta, -jnp.exp(a_log), bmat, cmat, skip)
        return dense(y.shape[-1], "out_proj")(s * nn.silu(z)), s


class DiffAttention(nn.Module):
    """Differential attention over grouped key heads. ``heads`` query
    heads and ``kv_heads`` key/value heads of ``head_dim`` as published
    (query head ``p`` reads key head ``p // (heads // kv_heads)``), paired
    for the difference: key heads ``2g`` and ``2g + 1`` are the two keys of
    group ``g``, their two value heads side by side its ONE value of width
    ``2 head_dim``, and the ``r``-th query head of each of the two key
    heads the two queries of differential head ``g (heads / kv_heads) +
    r``. With ``kv`` given (cross-attention) the layer has no key or value
    projection and reads those. Returns ``(output, (k, v))``."""

    heads: int
    kv_heads: int
    layer: int
    window: Optional[int]
    norm_eps: float
    compute_dtype: Any

    @nn.compact
    def __call__(self, y, kv=None):
        b, s, dim = y.shape
        hd = dim // self.heads
        group = self.heads // self.kv_heads
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, dtype=self.compute_dtype, name=name
        )
        q = dense(dim, "q_proj")(y).reshape(b, s, self.heads, hd)
        if kv is None:
            k = dense(self.kv_heads * hd, "k_proj")(y)
            v = dense(self.kv_heads * hd, "v_proj")(y)
            kv = (
                k.reshape(b, s, self.kv_heads, hd),
                v.reshape(b, s, self.kv_heads // 2, 2 * hd),
            )
        k, v = kv
        # Both keys of a group read the group's one wide value.
        out = blockwise_attention(
            q, k, jnp.repeat(v, 2, axis=2), causal=True, window=self.window
        ).astype(jnp.float32)
        out = out.reshape(b, s, self.kv_heads // 2, 2, group, 2 * hd)
        vec = lambda name: self.param(  # noqa: E731
            name, nn.initializers.normal(0.1), (hd,)
        )
        base = lambda_init(self.layer)
        lam = (
            jnp.exp(jnp.sum(vec("lambda_q1") * vec("lambda_k1")))
            - jnp.exp(jnp.sum(vec("lambda_q2") * vec("lambda_k2"))) + base
        )
        diff = out[:, :, :, 0] - lam * out[:, :, :, 1]  # [b, s, G, group, 2hd]
        scale = self.param("subln_scale", nn.initializers.ones, (2 * hd,))
        normed = diff * jax.lax.rsqrt(
            jnp.mean(diff * diff, axis=-1, keepdims=True) + self.norm_eps
        ) * scale * (1.0 - base)
        normed = normed.reshape(b, s, dim).astype(self.compute_dtype)
        return dense(dim, "o_proj")(normed), kv


class GatedMemoryUnit(nn.Module):
    """``(m * SiLU(y W1)) W2``: a gate on the memory of the same token."""

    compute_dtype: Any

    @nn.compact
    def __call__(self, y, memory):
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.compute_dtype, name=name
        )
        gate = nn.silu(dense(memory.shape[-1], "in_proj")(y))
        return dense(y.shape[-1], "out_proj")(memory * gate)


class SwiGLU(nn.Module):
    """``[g, u] = y W1; (u * SiLU(g)) W2``, no biases."""

    mlp_dim: int
    compute_dtype: Any

    @nn.compact
    def __call__(self, y):
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.compute_dtype, name=name
        )
        gate, up = jnp.split(dense(2 * self.mlp_dim, "gate_up_proj")(y), 2, -1)
        return dense(y.shape[-1], "down_proj")(up * nn.silu(gate))


class SambaYBlock(nn.Module):
    """One published layer. ``shared`` holds what earlier layers lent
    (``memory``, ``kv``); the block returns its output and ``shared``
    with what THIS layer lends added (``lends``: the model asks for it of
    layers ``n/2`` and ``n/2 + 1`` only)."""

    kind: str
    layer: int
    lends: bool
    heads: int
    kv_heads: int
    mlp_dim: int
    window: int
    d_state: int
    d_conv: int
    d_inner: int
    dt_rank: int
    norm_eps: float
    compute_dtype: Any

    @nn.compact
    def __call__(self, x, shared):
        norm = lambda name: nn.LayerNorm(  # noqa: E731
            epsilon=self.norm_eps, dtype=self.compute_dtype, name=name
        )
        y = norm("norm_mixer")(x)
        shared = dict(shared)
        # Plain scopes a trace sums by (not ``tpfl.*``: those are the
        # round body's legs).
        if self.kind == MAMBA:
            with jax.named_scope("mamba"):
                mixed, memory = MambaMixer(
                    self.d_inner, self.d_state, self.d_conv, self.dt_rank,
                    self.compute_dtype, name="mixer",
                )(y)
            if self.lends:
                shared["memory"] = memory
        elif self.kind == GMU:
            with jax.named_scope("gmu"):
                mixed = GatedMemoryUnit(self.compute_dtype, name="mixer")(
                    y, shared["memory"]
                )
        else:
            with jax.named_scope("diff_attention"):
                mixed, kv = DiffAttention(
                    self.heads, self.kv_heads, self.layer,
                    self.window if self.kind == WINDOW else None,
                    self.norm_eps, self.compute_dtype, name="mixer",
                )(y, shared["kv"] if self.kind == CROSS else None)
            if self.lends:
                shared["kv"] = kv
        h = x + mixed
        with jax.named_scope("mlp"):
            out = h + SwiGLU(self.mlp_dim, self.compute_dtype, name="mlp")(
                norm("norm_mlp")(h)
            )
        return out, shared


class SambaYLM(nn.Module):
    """The SambaY language model, or the pipeline stage of it that holds
    the published layers ``layers`` (default: all ``n_layers``). A stage
    must hold the layer that lends what its layers read: a gated memory
    unit needs layer ``n/2`` in the list, cross-attention ``n/2 + 1``.

    ``heads`` / ``kv_heads`` are the published query / key-value head
    counts (``dim / heads`` wide each); the Mamba sizes default as the
    model's configuration class has them (``d_inner = expand x dim``,
    ``dt_rank = ceil(dim / 16)``). Each block is recomputed in the
    backward pass (``nn.remat``): one block's activations live at a time."""

    vocab: int = 512
    dim: int = 64
    heads: int = 4
    kv_heads: int = 2
    mlp_dim: int = 256
    n_layers: int = 8
    layers: Optional[Sequence[int]] = None
    window: int = 512
    mb_per_layer: int = 2
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: Optional[int] = None
    norm_eps: float = 1e-5
    compute_dtype: Any = jnp.bfloat16

    # What the engine reads off a module (docs/parallelism.md): token ids
    # in, a head that owns its loss, and no model-axis sharding rule yet.
    input_dtype = jnp.int32
    owns_cross_entropy = True
    spec_layout = "replicated"

    @nn.compact
    def __call__(self, tokens, train: bool = False, targets=None):
        if self.heads % self.kv_heads or self.kv_heads % 2:
            raise ValueError(
                "differential attention pairs key heads: kv_heads must be "
                "even and divide heads"
            )
        layers = tuple(
            range(self.n_layers) if self.layers is None else self.layers
        )
        half = self.n_layers // 2
        kinds = [layer_kind(l, self.n_layers, self.mb_per_layer) for l in layers]
        for kind, lender in ((GMU, half), (CROSS, half + 1)):
            if kind in kinds and lender not in layers[: kinds.index(kind)]:
                raise ValueError(
                    f"layers {layers} hold a {kind} layer but not layer "
                    f"{lender} before it, which lends what it reads"
                )
        embed = nn.Embed(
            self.vocab, self.dim, dtype=self.compute_dtype, name="embed"
        )
        x = embed(tokens)
        block = nn.remat(SambaYBlock)
        shared: dict = {}
        for layer, kind in zip(layers, kinds):
            x, shared = block(
                kind=kind, layer=layer, lends=layer in (half, half + 1),
                heads=self.heads, kv_heads=self.kv_heads,
                mlp_dim=self.mlp_dim, window=self.window,
                d_state=self.d_state, d_conv=self.d_conv,
                d_inner=self.expand * self.dim,
                dt_rank=self.dt_rank or -(-self.dim // 16),
                norm_eps=self.norm_eps, compute_dtype=self.compute_dtype,
                name=f"layer_{layer}",
            )(x, shared)
        x = nn.LayerNorm(
            epsilon=self.norm_eps, dtype=self.compute_dtype, name="norm_out"
        )(x)
        if targets is None:
            return embed.attend(x).astype(jnp.float32)
        # Tied and bias-free: the head's kernel is the embedding.
        return head_cross_entropy(x, embed.embedding.T, None, targets)
