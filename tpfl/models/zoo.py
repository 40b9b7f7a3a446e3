"""Flax modules: MLP, CNN, ResNet-18, TransformerLM.

TPU notes: every module takes ``compute_dtype`` (default bfloat16; params
stay float32) so the MXU sees bf16 matmuls/convs; logits are always returned float32 for a stable
softmax. Shapes are static; no python control flow depends on data.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from tpfl.learning.model import TpflModel
from tpfl.models.head_loss import head_cross_entropy
from tpfl.models.mellum import MellumLM
from tpfl.models.sambay import SambaYLM
from tpfl.models.zaya import ZayaLM


class MLP(nn.Module):
    """MLP matching the reference example (784-256-128-10,
    lightning_model.py:118 / flax_model.py:171). Flattens any input."""

    hidden_sizes: Sequence[int] = (256, 128)
    out_channels: int = 10
    compute_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.reshape((x.shape[0], -1)).astype(self.compute_dtype)
        for h in self.hidden_sizes:
            x = nn.Dense(h, dtype=self.compute_dtype)(x)
            x = nn.relu(x)
        x = nn.Dense(self.out_channels, dtype=self.compute_dtype)(x)
        return x.astype(jnp.float32)


_CONV_DN = ("NHWC", "HWIO", "NHWC")


def _conv_same(x, w):
    return lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=_CONV_DN
    )


@jax.custom_vjp
def conv_fwd_style(x: jnp.ndarray, w: jnp.ndarray):
    """Stride-1 SAME convolution (NHWC x HWIO) with BOTH backward
    passes expressed as ordinary FORWARD convolutions at the XLA level:

    - ``dx = conv_SAME(dout, rot180(w) io-swapped)`` — the standard
      transposed-conv identity for stride 1 / SAME / odd kernels;
    - ``dW = conv(x, dout)`` with dimension numbers ``CHWN/IHWO/HWNC``
      (Cin as the conv batch, the real batch as the contraction
      feature, dout as a big-window kernel).

    Why: JAX's built-in conv transpose rules emit
    ``batch_group_count``/grouped-transpose convolutions that, once
    vmapped over a nodes axis, lower slower than forward-style grouped
    convs on TPU (``docs/perf_cnn.md``: pre-PR-1 figures, not
    re-measured). Gradients are numerically IDENTICAL to the autodiff
    path (``tests/test_parallel.py::test_conv_fwd_style_grads_match_autodiff``).

    Restrictions: stride 1, SAME padding, odd square kernel."""
    return _conv_same(x, w)


def _fs_fwd(x, w):
    return _conv_same(x, w), (x, w)


def _fs_bwd(res, g):
    x, w = res
    g = g.astype(x.dtype)
    k = w.shape[0]
    assert k == w.shape[1] and k % 2 == 1, "conv_fwd_style: odd square only"
    r = k // 2
    w_flip = jnp.flip(w, (0, 1)).swapaxes(2, 3)  # [k, k, Cout, Cin]
    dx = lax.conv_general_dilated(
        g, w_flip, (1, 1), "SAME", dimension_numbers=_CONV_DN
    )
    dw = lax.conv_general_dilated(
        x, g, (1, 1), [(r, r), (r, r)],
        dimension_numbers=("CHWN", "IHWO", "HWNC"),
    ).astype(w.dtype)
    return dx, dw


conv_fwd_style.defvjp(_fs_fwd, _fs_bwd)


class TpflConv(nn.Conv):
    """``nn.Conv`` whose gradients go through :func:`conv_fwd_style` —
    same forward op, same param layout/init (pass ``name="Conv_i"`` for
    tree/RNG parity with a plain ``nn.Conv`` stack): both backward
    convs are expressed as forward-style convolutions, which vmap into
    XLA's grouped lowering (the per-node federation path); numerically
    identical to autodiff. Only the zoo-CNN case is supported: stride
    1, SAME padding, odd square kernel, no grouping."""

    @nn.compact
    def __call__(self, inputs):
        kh, kw = self.kernel_size
        if (
            (self.strides not in (1, (1, 1), None))
            or self.padding != "SAME"
            or kh != kw
            or kh % 2 == 0
            or self.feature_group_count != 1
            or (self.kernel_dilation not in (1, (1, 1), None))
            or (self.input_dilation not in (1, (1, 1), None))
        ):
            raise NotImplementedError(
                "TpflConv supports stride 1, SAME padding, odd square "
                "kernels, no dilation/grouping — use nn.Conv "
                f"(got strides={self.strides}, padding={self.padding}, "
                f"kernel={self.kernel_size}, "
                f"groups={self.feature_group_count})"
            )
        cin = inputs.shape[-1]
        kernel = self.param(
            "kernel",
            self.kernel_init,
            (kh, kw, cin, self.features),
            self.param_dtype,
        )
        bias = (
            self.param(
                "bias", self.bias_init, (self.features,), self.param_dtype
            )
            if self.use_bias
            else None
        )
        from flax.linen import dtypes as _dtypes

        inputs, kernel, bias = _dtypes.promote_dtype(
            inputs, kernel, bias, dtype=self.dtype
        )
        y = conv_fwd_style(inputs, kernel)
        if bias is not None:
            y = y + bias
        return y


_CONV_IMPLS = {"fwd_bwd": TpflConv, "xla": nn.Conv}


class CNN(nn.Module):
    """Small conv net for 32×32×3 (CIFAR-10 benchmark tier).

    ``conv_impl``: "fwd_bwd" (default) uses :class:`TpflConv` —
    identical forward and params to ``nn.Conv``, with the backward
    convs reformulated as forward-style convs (exact grads;
    ``docs/perf_cnn.md`` has the pre-PR-1 figures, not re-measured);
    "xla" uses plain ``nn.Conv``. Any other value raises ``ValueError``.
    The param tree is identical across the two (explicit Conv_i names),
    so checkpoints and federations mix freely."""

    channels: Sequence[int] = (32, 64)
    dense: int = 128
    out_channels: int = 10
    compute_dtype: Any = jnp.bfloat16
    conv_impl: str = "fwd_bwd"

    @nn.compact
    def __call__(self, x, train: bool = False):
        if self.conv_impl not in _CONV_IMPLS:
            raise ValueError(
                f"CNN.conv_impl must be one of {sorted(_CONV_IMPLS)}, "
                f"got {self.conv_impl!r}"
            )
        conv_cls = _CONV_IMPLS[self.conv_impl]
        if x.ndim == 3:  # grayscale [B, H, W] -> [B, H, W, 1]
            x = x[..., None]
        x = x.astype(self.compute_dtype)
        for i, ch in enumerate(self.channels):
            x = conv_cls(
                ch, (3, 3), dtype=self.compute_dtype, name=f"Conv_{i}"
            )(x)
            x = nn.relu(x)
            x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(self.dense, dtype=self.compute_dtype)(x))
        x = nn.Dense(self.out_channels, dtype=self.compute_dtype)(x)
        return x.astype(jnp.float32)


class ResidualBlock(nn.Module):
    channels: int
    strides: tuple[int, int] = (1, 1)
    compute_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False):
        norm = partial(
            nn.BatchNorm,
            use_running_average=not train,
            momentum=0.9,
            dtype=self.compute_dtype,
        )
        residual = x
        y = nn.Conv(
            self.channels, (3, 3), self.strides, use_bias=False,
            dtype=self.compute_dtype,
        )(x)
        y = nn.relu(norm()(y))
        y = nn.Conv(
            self.channels, (3, 3), use_bias=False, dtype=self.compute_dtype
        )(y)
        y = norm()(y)
        if residual.shape != y.shape:
            residual = nn.Conv(
                self.channels, (1, 1), self.strides, use_bias=False,
                dtype=self.compute_dtype,
            )(residual)
            residual = norm()(residual)
        return nn.relu(residual + y)


class ResNet18(nn.Module):
    """ResNet-18 (CIFAR variant: 3×3 stem, no max-pool) for the
    CIFAR-100 benchmark tier. Uses BatchNorm, so callers must thread
    ``batch_stats`` (TpflModel.aux_state carries it between rounds)."""

    out_channels: int = 100
    stage_sizes: Sequence[int] = (2, 2, 2, 2)
    compute_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False):
        if x.ndim == 3:
            x = x[..., None]
        x = x.astype(self.compute_dtype)
        x = nn.Conv(64, (3, 3), use_bias=False, dtype=self.compute_dtype)(x)
        x = nn.relu(
            nn.BatchNorm(
                use_running_average=not train, momentum=0.9,
                dtype=self.compute_dtype,
            )(x)
        )
        for i, n_blocks in enumerate(self.stage_sizes):
            for b in range(n_blocks):
                strides = (2, 2) if i > 0 and b == 0 else (1, 1)
                x = ResidualBlock(
                    64 * 2**i, strides, compute_dtype=self.compute_dtype
                )(x, train=train)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.out_channels, dtype=self.compute_dtype)(x)
        return x.astype(jnp.float32)


def create_model(
    module: nn.Module | str,
    input_shape: Sequence[int],
    seed: int = 0,
    **module_kwargs: Any,
) -> TpflModel:
    """Initialize a flax module into a :class:`TpflModel`.

    ``module`` may be a module instance or a zoo name ("mlp", "cnn",
    "resnet18", "transformer_lm", "sambay_lm", "mellum_lm", "zaya_lm").
    ``input_shape`` excludes the batch dimension.
    """
    if isinstance(module, str):
        zoo: dict[str, Callable[..., nn.Module]] = {
            "mlp": MLP,
            "cnn": CNN,
            "resnet18": ResNet18,
            "transformer_lm": TransformerLM,
            "sambay_lm": SambaYLM,
            "mellum_lm": MellumLM,
            "zaya_lm": ZayaLM,
        }
        if module not in zoo:
            raise KeyError(f"Unknown model {module!r}; have {sorted(zoo)}")
        module = zoo[module](**module_kwargs)
    # Token models declare input_dtype (e.g. TransformerLM: int32 ids).
    dummy = jnp.zeros(
        (1, *input_shape), getattr(module, "input_dtype", jnp.float32)
    )
    variables = module.init(jax.random.PRNGKey(seed), dummy, train=False)
    params = variables["params"]
    aux = {k: v for k, v in variables.items() if k != "params"} or None
    return TpflModel(module=module, params=params, aux_state=aux)


class TransformerBlock(nn.Module):
    """Pre-norm attention + MLP block. ``attention_fn(q, k, v, causal)``
    defaults to the differentiable flash-style
    :func:`~tpfl.parallel.ring_attention.blockwise_attention`
    (O(block²) score memory; on a TPU its block loop is the Pallas
    kernels of :mod:`tpfl.parallel.flash_kernel`, elsewhere an XLA
    loop); pass a :func:`~tpfl.parallel.ring_attention.ring_attention`
    closure for sequence-sharded training, or
    :func:`~tpfl.parallel.flash_kernel.flash_attention` for the same
    kernels at a block size of your own."""

    dim: int
    heads: int = 4
    mlp_ratio: int = 4
    causal: bool = True
    compute_dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        from tpfl.parallel.ring_attention import blockwise_attention

        attention = self.attention_fn or blockwise_attention
        b, s, _ = x.shape
        h, d = self.heads, self.dim // self.heads
        y = nn.LayerNorm(dtype=self.compute_dtype)(x)
        qkv = nn.Dense(3 * self.dim, use_bias=False, dtype=self.compute_dtype)(y)
        # Split first, then name the heads: attention's kernels read
        # [.., heads * d] (flash_kernel._lanes), and a reshape of a reshape
        # folds away, where one of a [.., heads, d] slice is a copy on a TPU.
        q, k, v = (
            part.reshape(b, s, h, d) for part in jnp.split(qkv, 3, axis=-1)
        )
        attn = attention(q, k, v, causal=self.causal)
        x = x + nn.Dense(self.dim, dtype=self.compute_dtype)(
            attn.reshape(b, s, self.dim)
        )
        y = nn.LayerNorm(dtype=self.compute_dtype)(x)
        y = nn.Dense(self.mlp_ratio * self.dim, dtype=self.compute_dtype)(y)
        y = nn.gelu(y)
        return x + nn.Dense(self.dim, dtype=self.compute_dtype)(y)


class TransformerLM(nn.Module):
    """Small causal language model — the long-context tier of the zoo.

    The reference has no attention models at all (SURVEY §5.7); this is
    the consumer for the sequence-parallel path: single-device training
    uses blockwise attention, and sequence-sharded training swaps in
    :func:`tpfl.parallel.ring_attention.ring_attention` over an ``sp``
    mesh axis (see tests/test_parallel.py).
    """

    vocab: int = 256
    dim: int = 128
    heads: int = 4
    n_layers: int = 2
    max_len: int = 8192
    compute_dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None  # see TransformerBlock

    # create_model inits token models from integer ids (not a dataclass
    # field: architecture metadata, not a hyperparameter).
    input_dtype = jnp.int32

    # Per-leaf model-axis PartitionSpec policy for the engine's 2D
    # ``nodes x model`` mesh (tpfl.parallel.mesh.layout_for_module):
    # embeddings/QKV/FFN shard, LayerNorm/biases-of-row-parallel ride
    # replicated. MLP/CNN/ResNet carry no attribute and default to
    # the replicated layout.
    spec_layout = "transformer"

    # The output head owns its training loss: ``__call__(tokens,
    # targets=y)`` returns the mean over tokens of
    # ``cross_entropy_loss(logits, y)`` as one operation
    # (tpfl.models.head_loss) instead of the logits. The engine's local
    # training asks for it when its loss is that canonical one
    # (docs/parallelism.md, "What the engine reads off a module").
    owns_cross_entropy = True

    @nn.compact
    def __call__(self, tokens, train: bool = False, targets=None):
        if tokens.shape[1] > self.max_len:
            raise ValueError(
                f"Sequence length {tokens.shape[1]} exceeds max_len="
                f"{self.max_len}; raise max_len (positional table size)"
            )
        x = nn.Embed(self.vocab, self.dim, dtype=self.compute_dtype)(tokens)
        pos = nn.Embed(self.max_len, self.dim, dtype=self.compute_dtype)(
            jnp.arange(tokens.shape[1])[None]
        )
        x = x + pos
        for _ in range(self.n_layers):
            x = TransformerBlock(
                self.dim,
                self.heads,
                compute_dtype=self.compute_dtype,
                attention_fn=self.attention_fn,
            )(x, train=train)
        x = nn.LayerNorm(dtype=self.compute_dtype)(x)
        head = nn.Dense(self.vocab, dtype=self.compute_dtype)
        if targets is None:
            return head(x).astype(jnp.float32)
        if self.is_initializing():
            head(x)  # creates Dense_0's kernel and bias under their names
        weights = head.variables["params"]
        return head_cross_entropy(
            x, weights["kernel"], weights["bias"], targets
        )
