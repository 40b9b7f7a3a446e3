"""Phi-4-mini-flash-reasoning (SambaY; Ren et al. 2025, arXiv:2507.06607;
sizes from the published ``config.json``) as ``tpfl.models.SambaYLM``
runs the stage of it the configuration holds: what the harness needs
from the configuration ``phi4_mini_flash_reasoning``.

- ``build_module`` — the program's own module on the configuration's
  list of published layers;
- ``make_data``, ``input_shape``, ``samples_per_round`` —
  ``gpt2_small``'s own (imported): tokens of a seeded order-1 Markov
  source over 512 active ids, spread over ``cfg["vocab_size"]`` — here
  the vocabulary SLICE — and made on the device;
- ``fwd_mults_per_sample`` — per TOKEN, forward, by layer kind;
  ``scan_min_bytes_per_round`` — the least bytes the selective scan has
  to move, for its roofline share;
- ``reference_round`` — the PLAIN REFERENCE in float32 ``jax.numpy``
  under ``jax.default_matmul_precision("highest")``, written from the
  layer equations of ISSUE 27 (the published config, Gu & Dao 2023 for
  Mamba-1, Ye et al. 2024 for differential attention): a token-by-token
  ``lax.scan`` for the recurrence, the full S x S score matrix, no
  chunking, no kernels. It shares no code with ``tpfl.models``; it reads
  the flax parameter tree only as named arrays. What the configuration
  file lists under ``assumed`` it implements as stated there.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.models.gpt2_small import (  # noqa: F401  (the harness's MODEL_API)
    SAMPLE_UNIT, input_shape, make_data, samples_per_round,
)
from benchmark.models.plain_fedavg import SGD_MOMENTUM

#: Engine (bf16 matmuls, float32 scan / softmax / norms) against this
#: reference (float32, "highest"), relative, on the chip at published
#: widths (harness.check_against_reference); readings in PERF.md §6
#: (PR 27). Loss: 2.6e-5 read, the accepted cells' limit leaves 38x.
#: Update: 2.4% read at every seed, the same on every leaf — the TIED
#: head's bf16 logits: at initialisation a token's own logit is about
#: |LN(x)| |E| large, and exp() of a logit rounded to 8 bits is off by
#: percents (float32 storage with bf16 multiplies still reads 1.7%; a
#: float32 attention or a bf16 scan state move it by 0.05%). Matmul
#: operands rounded to float8_e4m3 read 1.0 (the head's dlogits
#: underflow to zero): the limit sits between, 4x above the reading.
CHECK_TOLERANCES = {"loss": 1e-3, "update": 1e-1}
MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"
HIGHEST = lax.Precision.HIGHEST


def build_module(cfg: dict) -> Any:
    from tpfl.models import SambaYLM

    mamba = cfg["mamba"]
    return SambaYLM(
        vocab=int(cfg["vocab_size"]), dim=int(cfg["hidden_size"]),
        heads=int(cfg["num_attention_heads"]),
        kv_heads=int(cfg["num_key_value_heads"]),
        mlp_dim=int(cfg["intermediate_size"]),
        n_layers=int(cfg["published"]["num_hidden_layers"]),
        layers=tuple(cfg["layers"]), window=int(cfg["sliding_window"]),
        mb_per_layer=int(cfg["mb_per_layer"]), d_state=int(mamba["d_state"]),
        d_conv=int(mamba["d_conv"]), expand=int(mamba["expand"]),
        dt_rank=int(mamba["dt_rank"]), norm_eps=float(cfg["layer_norm_eps"]),
        compute_dtype=jnp.dtype(cfg["compute_dtype"]),
    )


def layer_kind(layer: int, n_layers: int, mb_per_layer: int) -> str:
    """The published pattern (ISSUE 27 §1), written out again here."""
    half = n_layers // 2
    even = layer % mb_per_layer == 0
    if layer == half + 1:
        return FULL
    if layer <= half:
        return MAMBA if even else WINDOW
    return GMU if even else CROSS


def _kinds(cfg: dict) -> list:
    n = int(cfg["published"]["num_hidden_layers"])
    return [layer_kind(l, n, int(cfg["mb_per_layer"])) for l in cfg["layers"]]


def fwd_mults_per_sample(cfg: dict, traffic: dict) -> int:
    """Per token, forward, recomputation not counted. Every layer: the
    SwiGLU MLP ``3 d f``. Mamba: in / out projections ``3 d di``, the
    low-rank ``di (R + 2N) + R di``, the convolution ``di k`` and
    ``3 di N`` for the recurrence (decay x state, input x B, state x C).
    Attention: q and o ``2 d d`` (+ k and v ``2 d d_kv`` unless
    cross-attention reads them), scores ``d`` and values ``2 d`` per
    visible key (two maps over ``d / heads``-wide keys and one
    double-width value: 2560 and 5120 a key), about S/2 keys, or the
    window where it is shorter. GMU ``2 d di``. Head ``d V`` over the
    slice. The embedding is a look-up."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    mamba = cfg["mamba"]
    di, n, r = mamba["expand"] * d, mamba["d_state"], mamba["dt_rank"]
    d_kv = d * cfg["num_key_value_heads"] // cfg["num_attention_heads"]
    s = traffic["seq"]
    per_kind = {
        MAMBA: 3 * d * di + di * (r + 2 * n) + r * di + di * mamba["d_conv"]
        + 3 * di * n,
        GMU: 2 * d * di,
        FULL: 2 * d * d + 2 * d * d_kv + 3 * d * (s // 2),
        CROSS: 2 * d * d + 3 * d * (s // 2),
        WINDOW: 2 * d * d + 2 * d * d_kv
        + 3 * d * min(s // 2, cfg["sliding_window"]),
    }
    mixers = sum(per_kind[kind] for kind in _kinds(cfg))
    return int(mixers + len(cfg["layers"]) * 3 * d * f + d * cfg["vocab_size"])


def scan_min_bytes_per_round(cfg: dict, traffic: dict) -> int:
    """The least bytes ``selective_scan`` has to move in a round,
    forward and backward, every input read once and every output written
    once, in the dtypes the module hands it (c, B, C and the result in
    the compute dtype, delta and the parameters float32). A sequence of
    a Mamba layer, forward: read c, delta, B, C, A, D; write s.
    Backward: read them again and the result's gradient; write the six
    gradients. A LOWER bound: a real schedule re-reads, so the scan's
    roofline share cannot pass 100%."""
    mamba = cfg["mamba"]
    di, n = mamba["expand"] * cfg["hidden_size"], mamba["d_state"]
    cd = jnp.dtype(cfg["compute_dtype"]).itemsize
    s = traffic["seq"]
    tokens = s * (di * cd + di * 4 + 2 * n * cd)  # c, delta, B and C
    params = (di * n + di) * 4  # A, D
    result = s * di * cd
    forward = tokens + params + result
    backward = (tokens + params + result) + (tokens + params)
    sequences = traffic["nodes"] * traffic["local_batches"] * traffic["batch"]
    return int(sequences * _kinds(cfg).count(MAMBA) * (forward + backward))


# --- the plain reference -----------------------------------------------------


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _dense(x, p):
    y = jnp.dot(x, p["kernel"], precision=HIGHEST)
    return y + p["bias"] if "bias" in p else y


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _mamba(y, p):
    """y [S, d] -> (mixer output [S, d], scan output s [S, di])."""
    a, z = jnp.split(_dense(y, p["in_proj"]), 2, axis=-1)
    k = p["conv_kernel"].shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, a.shape[1])), a], axis=0)
    conv = sum(padded[j:j + a.shape[0]] * p["conv_kernel"][j] for j in range(k))
    c = _silu(conv + p["conv_bias"])
    n = p["a_log"].shape[1]
    rank = p["dt_kernel"].shape[0]
    x_dbl = _dense(c, p["x_proj"])
    low, bmat, cmat = x_dbl[:, :rank], x_dbl[:, rank:rank + n], x_dbl[:, rank + n:]
    pre = jnp.dot(low, p["dt_kernel"], precision=HIGHEST) + p["dt_bias"]
    delta = jnp.logaddexp(pre, 0.0)  # softplus
    a_mat = -jnp.exp(p["a_log"])

    def token(h, xs):
        c_t, delta_t, b_t, c_mat_t = xs
        h = jnp.exp(delta_t[:, None] * a_mat) * h + (
            (delta_t * c_t)[:, None] * b_t[None, :]
        )
        return h, jnp.sum(h * c_mat_t[None, :], axis=1) + p["d_skip"] * c_t

    _, s = lax.scan(token, jnp.zeros_like(a_mat), (c, delta, bmat, cmat))
    return _dense(s * _silu(z), p["out_proj"]), s


def _diff_attention(y, p, heads, kv_heads, layer, window, eps, kv=None):
    """y [S, d] -> (output [S, d], (K [S, kv, hd], V [S, kv/2, 2 hd]))."""
    s, d = y.shape
    hd = d // heads
    per = heads // kv_heads  # query heads a key head
    q = _dense(y, p["q_proj"]).reshape(s, heads, hd)
    if kv is None:
        kv = (
            _dense(y, p["k_proj"]).reshape(s, kv_heads, hd),
            _dense(y, p["v_proj"]).reshape(s, kv_heads // 2, 2 * hd),
        )
    k, v = kv
    pos = jnp.arange(s)
    visible = pos[:, None] >= pos[None, :]
    if window is not None:
        visible &= pos[:, None] - pos[None, :] < window
    # Query head p reads key head p // per.
    scores = jnp.einsum(
        "qgrh,kgh->grqk", q.reshape(s, kv_heads, per, hd), k, precision=HIGHEST
    ) / math.sqrt(hd)
    maps = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
    # Key heads 2G and 2G+1 are the two keys of group G.
    maps = maps.reshape(kv_heads // 2, 2, per, s, s)
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lam = (
        jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
        - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam_init
    )
    out = jnp.einsum(
        "Grqk,kGw->qGrw", maps[:, 0] - lam * maps[:, 1], v, precision=HIGHEST
    )
    rms = lax.rsqrt(jnp.mean(out * out, axis=-1, keepdims=True) + eps)
    out = out * rms * p["subln_scale"] * (1.0 - lam_init)
    return _dense(out.reshape(s, d), p["o_proj"]), kv


def _gmu(y, memory, p):
    return _dense(memory * _silu(_dense(y, p["in_proj"])), p["out_proj"])


def _mlp(y, p):
    gate, up = jnp.split(_dense(y, p["gate_up_proj"]), 2, axis=-1)
    return _dense(up * _silu(gate), p["down_proj"])


def _sequence_logits(cfg: dict, params: dict, tokens: Any) -> Any:
    """tokens [S] -> logits [S, vocab]."""
    eps = cfg["layer_norm_eps"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    x = params["embed"]["embedding"][tokens]
    memory = shared_kv = None
    n = int(cfg["published"]["num_hidden_layers"])
    for layer, kind in zip(cfg["layers"], _kinds(cfg)):
        p = params[f"layer_{layer}"]
        y = _layer_norm(x, p["norm_mixer"], eps)
        if kind == MAMBA:
            mixed, s = _mamba(y, p["mixer"])
            if layer == n // 2:
                memory = s
        elif kind == GMU:
            mixed = _gmu(y, memory, p["mixer"])
        else:
            mixed, kv = _diff_attention(
                y, p["mixer"], heads, kv_heads, layer,
                cfg["sliding_window"] if kind == WINDOW else None, eps,
                shared_kv if kind == CROSS else None,
            )
            if kind == FULL:
                shared_kv = kv
        h = x + mixed
        x = h + _mlp(_layer_norm(h, p["norm_mlp"], eps), p["mlp"])
    x = _layer_norm(x, params["norm_out"], eps)
    return jnp.dot(x, params["embed"]["embedding"].T, precision=HIGHEST)


def reference_forward(cfg: dict, params: dict, aux: dict, tokens: Any) -> tuple:
    """(logits [b, s, vocab], aux unchanged): the model keeps no state
    besides its parameters."""
    return jax.vmap(lambda t: _sequence_logits(cfg, params, t))(tokens), aux


def _loss(cfg, params, tokens, targets):
    logits, _ = reference_forward(cfg, params, {}, tokens)
    logp = jax.nn.log_softmax(logits)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(picked)


def reference_round(
    cfg: dict, params: dict, aux: dict, xs: Any, ys: Any, weights: Any, lr: float
) -> tuple:
    """One federated round from ONE global model: (per-silo mean local
    loss [n], folded params, aux unchanged). FedAvg (McMahan et al.
    2017) over local heavy-ball SGD ``t <- g + m t; p <- p - lr t``,
    momentum from zero each round, a silo's loss the mean of its
    batches' losses before each step — ``plain_fedavg_round``'s
    semantics, but a silo's whole local pass is ONE jitted function and
    the fold donates its accumulator: the harness's check already holds
    four models of 1.9 GB when it calls this, and the shared helper's
    leaf-by-leaf updates hold up to seven at once."""
    with jax.default_matmul_precision("highest"):
        grad = jax.value_and_grad(lambda p, x, y: _loss(cfg, p, x, y))
        tree_map = jax.tree_util.tree_map

        @jax.jit
        def local_pass(p, node_xs, node_ys):
            trace, losses = tree_map(jnp.zeros_like, p), []
            for batch in range(node_xs.shape[0]):
                loss, g = grad(p, node_xs[batch], node_ys[batch])
                trace = tree_map(lambda t, gg: gg + SGD_MOMENTUM * t, trace, g)
                p = tree_map(lambda pp, t: pp - lr * t, p, trace)
                losses.append(loss)
            return jnp.mean(jnp.stack(losses)), p

        fold = jax.jit(
            lambda acc, p, w: tree_map(lambda a, leaf: a + w * leaf, acc, p),
            donate_argnums=0,
        )
        wnorm = jnp.asarray(weights, jnp.float32)
        wnorm = wnorm / jnp.sum(wnorm)
        losses, folded = [], tree_map(jnp.zeros_like, params)
        for node in range(xs.shape[0]):
            loss, p = local_pass(params, xs[node], ys[node])
            folded = fold(folded, p, wnorm[node])
            del p
            losses.append(loss)
        return jnp.stack(losses), folded, aux
