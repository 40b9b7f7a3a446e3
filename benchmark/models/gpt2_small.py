"""GPT-2 small (124M; Radford et al. 2019, sizes from the published
``config.json``) as ``tpfl.models.TransformerLM`` runs it: what the
harness needs from the configuration ``gpt2_small``.

- ``build_module`` — the program's own module, default attention;
- ``make_data`` — tokens of a seeded order-1 Markov source, made on
  the device, so that the loss falls;
- ``fwd_mults_per_sample`` — per TOKEN (a "sample" of this
  configuration is a token), the PaLM-appendix count copied from
  ``tpfl.management.profiling.CostModel.analytic_fwd_mults``;
- ``reference_round`` — the PLAIN REFERENCE in float32 ``jax.numpy``
  under ``jax.default_matmul_precision("highest")``: a pre-norm GPT-2
  block (LayerNorm, causal multi-head attention over the full S x S
  score matrix, GELU-tanh MLP) written from the architecture's
  description. It implements the block as the zoo states it — no QKV
  bias, an untied output head with bias, LayerNorm eps 1e-6 — and the
  configuration file lists each of those departures from GPT-2
  (``assumed``). It shares no code with ``tpfl.models``; it reads the
  flax parameter tree only as named arrays.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.models.plain_fedavg import plain_fedavg_round

SAMPLE_UNIT = "tokens"
LN_EPS = 1e-6
#: Engine (bf16 compute) against this reference (float32, "highest"),
#: relative, on the chip at published widths
#: (harness.check_against_reference). bf16 (8 mantissa bits) leaves
#: 6e-5 on a loss and 0.9% on the update (gradients pass 12 blocks
#: twice); the bounds are a few times that. fp8 or int8 matmuls (3 and
#: 7 bits) leave percents on the update and fail.
CHECK_TOLERANCES = {"loss": 1e-3, "update": 3e-2}
#: Size of the Markov source's alphabet (spread over the vocabulary)
#: and successors per token: the loss can fall from ln(vocab) towards
#: ln(SUCCESSORS).
ACTIVE_TOKENS = 512
SUCCESSORS = 4


def build_module(cfg: dict) -> Any:
    from tpfl.models import TransformerLM

    if cfg["n_inner"] != 4 * cfg["n_embd"]:
        raise ValueError("tpfl.models.TransformerBlock fixes the MLP at 4 x dim")
    return TransformerLM(
        vocab=int(cfg["vocab_size"]), dim=int(cfg["n_embd"]),
        heads=int(cfg["n_head"]), n_layers=int(cfg["n_layer"]),
        max_len=int(cfg["n_positions"]),
        compute_dtype=jnp.dtype(cfg["compute_dtype"]),
    )


def input_shape(cfg: dict, traffic: dict) -> tuple:
    return (int(traffic["seq"]),)


def samples_per_round(traffic: dict) -> int:
    return (
        traffic["nodes"] * traffic["local_batches"] * traffic["batch"]
        * traffic["seq"]
    )


def make_data(key: Any, cfg: dict, traffic: dict) -> tuple:
    """(xs, ys) int32 [n, nb, b, seq]: ys is xs shifted by one token.
    Token t+1 is one of ``SUCCESSORS`` fixed successors of token t,
    chosen uniformly. Traced inside one jit by the harness."""
    n, nb, b = traffic["nodes"], traffic["local_batches"], traffic["batch"]
    seq, vocab = int(traffic["seq"]), int(cfg["vocab_size"])
    active = min(ACTIVE_TOKENS, vocab)
    k0, kr = jax.random.split(key)
    start = jax.random.randint(k0, (n, nb, b), 0, active, jnp.int32)
    picks = jax.random.randint(kr, (seq, n, nb, b), 0, SUCCESSORS, jnp.int32)

    def step(cur, pick):
        nxt = (cur * 5 + 3 + pick * 97) % active
        return nxt, nxt

    _, rest = lax.scan(step, start, picks)
    index = jnp.concatenate([start[None], rest], axis=0)  # [seq+1, n, nb, b]
    tokens = jnp.moveaxis(index * (vocab // active), 0, -1)
    return tokens[..., :-1], tokens[..., 1:]


def fwd_mults_per_sample(cfg: dict, traffic: dict) -> int:
    """Per token, forward: per layer QKV 3d^2 + attention output d^2 +
    MLP 8d^2, plus S*d for the causal score and value products (about
    S/2 visible keys each), plus the d*V output head. Embeddings are
    look-ups."""
    d, s = cfg["n_embd"], traffic["seq"]
    per_layer = 4 * d * d + 2 * cfg["n_inner"] * d + s * d
    return int(cfg["n_layer"] * per_layer + d * cfg["vocab_size"])


# --- the plain reference -----------------------------------------------------


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _dense(x, p):
    y = jnp.dot(x, p["kernel"], precision=lax.Precision.HIGHEST)
    return y + p["bias"] if "bias" in p else y


def _gelu_tanh(x):
    return 0.5 * x * (
        1.0 + jnp.tanh(jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x**3))
    )


def _block(x, p, heads):
    b, s, d = x.shape
    dh = d // heads
    # The projection's 3d outputs are [q heads | k heads | v heads].
    qkv = _dense(_layer_norm(x, p["LayerNorm_0"]), p["Dense_0"])
    q, k, v = (
        t.reshape(b, s, heads, dh) for t in jnp.split(qkv, 3, axis=-1)
    )
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, precision=lax.Precision.HIGHEST
    ) / jnp.sqrt(jnp.float32(dh))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    attn = jnp.einsum(
        "bhqk,bkhd->bqhd", probs, v, precision=lax.Precision.HIGHEST
    )
    x = x + _dense(attn.reshape(b, s, d), p["Dense_1"])
    y = _gelu_tanh(_dense(_layer_norm(x, p["LayerNorm_1"]), p["Dense_2"]))
    return x + _dense(y, p["Dense_3"])


def reference_forward(cfg: dict, params: dict, aux: dict, tokens: Any) -> tuple:
    """(logits [b, s, vocab], aux unchanged): the model keeps no state
    besides its parameters."""
    s = tokens.shape[1]
    x = params["Embed_0"]["embedding"][tokens] + params["Embed_1"]["embedding"][:s]
    for layer in range(cfg["n_layer"]):
        x = _block(x, params[f"TransformerBlock_{layer}"], cfg["n_head"])
    x = _layer_norm(x, params["LayerNorm_0"])
    return _dense(x, params["Dense_0"]), aux


def _loss(cfg, params, aux, tokens, targets):
    logits, aux = reference_forward(cfg, params, aux, tokens)
    logp = jax.nn.log_softmax(logits)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(picked), aux


def reference_round(
    cfg: dict, params: dict, aux: dict, xs: Any, ys: Any, weights: Any, lr: float
) -> tuple:
    """One federated round: (per-silo mean local loss [n], folded
    params, aux unchanged). See ``plain_fedavg_round``."""
    return plain_fedavg_round(
        lambda p, a, x, y: _loss(cfg, p, a, x, y), params, aux, xs, ys,
        weights, lr,
    )
