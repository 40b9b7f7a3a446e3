"""Mellum2-12B-A2.5B-Instruct (JetBrains; sizes and layer pattern from the
published ``config.json``) as ``tpfl.models.MellumLM`` runs the share of
it the configuration holds: what the harness needs from the configuration
``mellum2_12b_a2p5b``.

- ``build_module`` — the program's own module on the configuration's
  published layers and held experts;
- ``make_data``, ``input_shape``, ``samples_per_round`` —
  ``gpt2_small``'s own (imported): tokens of a seeded order-1 Markov
  source over 512 active ids, spread over ``cfg["vocab_size"]`` — here
  the vocabulary SLICE — and made on the device;
- ``fwd_mults_per_sample`` — per TOKEN, forward: the rows the held
  experts are EXPECTED to receive and the keys a band lets a query see,
  counted exactly; ``expert_flops_per_round`` — the three grouped
  products' operations under balanced routing, for their roofline share;
- ``reference_round`` — the PLAIN REFERENCE in float32 ``jax.numpy``
  under ``jax.default_matmul_precision("highest")``, written from the
  layer equations of ISSUE 32 (the published config; YaRN as Peng et
  al. 2023 and the transformers library state it): the full S x S score
  matrix with the band as a mask, EVERY held expert evaluated on every
  token and masked by the routing, its own rotary tables. It shares no
  code with ``tpfl.models`` or ``tpfl.parallel``; it reads the flax
  parameter tree only as named arrays. What the configuration file lists
  under ``assumed`` it implements as stated there. One departure from
  "no blocking": each layer is a ``jax.checkpoint`` (its activations
  are made again in the backward pass), because the harness's check
  already holds four float32 models beside it (model-configs guide §3:
  "computed in blocks so that it fits").
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
# At import, not inside ``build_module``: a program that lacks the model
# (the parent commit) then fails in ``cells.load_cell``, in milliseconds
# and before anything touches the device.
from tpfl.models import MellumLM

#: Engine (bf16 matmuls; float32 router, softmax, norms, rotary tables)
#: against this reference (float32, "highest"), relative, on the chip at
#: published widths (harness.check_against_reference, 2 silos x 2048
#: tokens); every reading in PERF.md §6 and CHANGES.md (PR 32).
#: - loss: read 2.7e-7 .. 5e-6 over the seeds tried (the loss of an
#:   untrained model barely feels bf16); the accepted cells' limit.
#: - update: read 1.3-1.5%, the bf16 matmuls' rounding through four
#:   blocks and back plus the expert choices that flip (below).
#:   Parameters STORED in bf16 read 645 (a step of lr x gradient is
#:   under half a bf16 ulp of most weights): they fail this limit.
#: - aux (the routers' loads, the program's ROUTING against the
#:   reference's): read 0.4-0.6%. A token's eighth and ninth expert are
#:   ~0.08 apart in logit on average and bf16 hidden states move a logit
#:   by ~0.003, so about one choice in 100-150 flips (PR 31's scratch
#:   count on its own model: 0.6-1.3%); a flip moves 1 / (8 x tokens) of
#:   load from one expert to another, so the loads differ by ~sqrt(flips)
#:   / choices of a ~1/64 share each. The limit leaves four times that.
#:   A router whose LOGITS are rounded to bf16 reads 0.53% against 0.38%
#:   as run (update 1.47% against 1.34%, loss 1.7e-5 against 2.7e-7): it
#:   flips about as many choices again as the bf16 hidden states do
#:   anyway, and NO limit that leaves the seeds room can fail it.
CHECK_TOLERANCES = {"loss": 1e-3, "update": 5e-2, "aux": 2e-2}
HIGHEST = lax.Precision.HIGHEST


def build_module(cfg: dict) -> Any:
    rope = cfg["rope_parameters"]
    yarn = rope["full_attention"]
    held = cfg["experts_held"]
    return MellumLM(
        vocab=int(cfg["vocab_size"]), dim=int(cfg["hidden_size"]),
        heads=int(cfg["num_attention_heads"]),
        kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        n_layers=int(cfg["published"]["num_hidden_layers"]),
        layers=tuple(cfg["layers"]), period=_period(cfg),
        window=int(cfg["sliding_window"]),
        rope_theta=float(rope["sliding_attention"]["rope_theta"]),
        yarn=tuple(sorted(
            (key, float(yarn[key])) for key in (
                "factor", "original_max_position_embeddings", "beta_fast",
                "beta_slow", "attention_factor",
            )
        )),
        n_experts=int(held["router_width"]),
        top_k=int(cfg["num_experts_per_tok"]),
        expert_dim=int(cfg["moe_intermediate_size"]),
        held_experts=int(cfg["num_experts"]), first_expert=int(held["first"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        compute_dtype=jnp.dtype(cfg["compute_dtype"]),
    )


def _period(cfg: dict) -> int:
    """Layers from one full-attention layer to the next."""
    return cfg["layer_types"].index("full_attention") + 1


def _is_full(cfg: dict, layer: int) -> bool:
    return cfg["layer_types"][layer] == "full_attention"


def visible_keys(seq: int, window: "int | None") -> float:
    """Mean over a sequence's queries of the keys each sees: ``t + 1``
    for the query at ``t``, at most ``window``."""
    if window is None or window >= seq:
        return (seq + 1) / 2
    return (window * (window + 1) / 2 + (seq - window) * window) / seq


def routed_rows_per_token(cfg: dict) -> float:
    """Rows a token sends to the experts held here under balanced
    routing: its ``k`` choices times the share of the experts held."""
    held = cfg["experts_held"]
    return cfg["num_experts_per_tok"] * cfg["num_experts"] / held["router_width"]


def fwd_mults_per_sample(cfg: dict, traffic: dict) -> int:
    """Per token, forward, recomputation not counted. A layer: q and o
    ``2 d (heads hd)``, k and v ``2 d (kv hd)``, scores and values
    ``2 heads hd`` per visible key (``visible_keys``: the band counted
    exactly), the router ``d E``, and ``3 d f`` for each row the held
    experts are expected to receive. Head ``d V`` over the slice. The
    embedding is a look-up."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    q_dim = cfg["num_attention_heads"] * cfg["head_dim"]
    kv_dim = cfg["num_key_value_heads"] * cfg["head_dim"]
    s = traffic["seq"]
    total = d * cfg["vocab_size"]
    for layer in cfg["layers"]:
        window = None if _is_full(cfg, layer) else cfg["sliding_window"]
        total += (
            2 * d * q_dim + 2 * d * kv_dim
            + 2 * q_dim * visible_keys(s, window)
            + d * cfg["experts_held"]["router_width"]
            + routed_rows_per_token(cfg) * 3 * d * f
        )
    return int(total)


def expert_flops_per_round(cfg: dict, traffic: dict) -> float:
    """Operations of the experts' three grouped products in a round,
    forward and backward (6 a multiply), over the rows routed here under
    BALANCED routing — the same work whatever implements the products;
    the recompute backward's second forward product is not counted."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = samples_per_round(traffic) * routed_rows_per_token(cfg)
    return 6.0 * 3 * d * f * rows * len(cfg["layers"])


# --- the plain reference -----------------------------------------------------


def _rms_norm(x, p, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * p["scale"]


def _dense(x, p):
    return jnp.dot(x, p["kernel"], precision=HIGHEST)


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def rotary_table(cfg: dict, kind: str, seq: int) -> tuple:
    """``(cos, sin) [seq, head_dim / 2]`` of ``rope_parameters[kind]``,
    from the formulas: plain ``theta^(-2 i / D)``; YaRN's blend of that
    and the same slowed by ``factor``, by a linear ramp over the pairs
    between the correction bounds, the table scaled by
    ``attention_factor``."""
    rope = cfg["rope_parameters"][kind]
    dim, theta = cfg["head_dim"], float(rope["rope_theta"])
    pair = jnp.arange(dim // 2, dtype=jnp.float32)
    inv_freq = theta ** (-2.0 * pair / dim)
    scale = 1.0
    if rope["rope_type"] == "yarn":
        original = rope["original_max_position_embeddings"]

        def correction_dim(turns):
            return dim * math.log(original / (turns * 2 * math.pi)) / (
                2 * math.log(theta)
            )

        low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
        high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
        ramp = jnp.clip((pair - low) / (high - low), 0.0, 1.0)
        inv_freq = ramp * inv_freq / rope["factor"] + (1.0 - ramp) * inv_freq
        scale = float(rope["attention_factor"])
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def _rotate(x, cos, sin):
    """x [S, H, D]: dimension i turns with dimension i + D / 2."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(cfg, y, p, full: bool):
    """y [S, d] -> [S, d]: dense masked softmax over all S x S pairs."""
    s = y.shape[0]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, per = cfg["head_dim"], heads // kv_heads
    cos, sin = rotary_table(
        cfg, "full_attention" if full else "sliding_attention", s
    )
    q = _rotate(_dense(y, p["q_proj"]).reshape(s, heads, hd), cos, sin)
    k = _rotate(_dense(y, p["k_proj"]).reshape(s, kv_heads, hd), cos, sin)
    v = _dense(y, p["v_proj"]).reshape(s, kv_heads, hd)
    pos = jnp.arange(s)
    visible = pos[:, None] >= pos[None, :]
    if not full:
        visible &= pos[:, None] - pos[None, :] < cfg["sliding_window"]
    # Query head h reads key head h // per.
    scores = jnp.einsum(
        "qgrh,kgh->grqk", q.reshape(s, kv_heads, per, hd), k, precision=HIGHEST
    ) / math.sqrt(hd)
    probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("grqk,kgh->qgrh", probs, v, precision=HIGHEST)
    return _dense(out.reshape(s, heads * hd), p["o_proj"])


def _experts(cfg, y, p):
    """y [S, d] -> (the held experts' part of the layer [S, d], the
    router's load [E]): every held expert on every token, weighted by
    the routing (zero where the token did not choose it)."""
    held = cfg["experts_held"]
    k, width = cfg["num_experts_per_tok"], held["router_width"]
    probs = jax.nn.softmax(jnp.dot(y, p["router"], precision=HIGHEST), axis=-1)
    top_p, top_e = lax.top_k(probs, k)
    chosen = top_e[..., None] == jnp.arange(width)  # [S, k, E]
    gates = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # [S, E]: the normalised gate of a chosen expert, else 0.
    weight = jnp.sum(gates[..., None] * chosen, axis=1)
    load = jnp.sum(chosen, axis=(0, 1)) / (chosen.shape[0] * k)
    first, count = held["first"], cfg["num_experts"]
    gate_up = jnp.einsum("sd,edf->sef", y, p["gate_up_proj"], precision=HIGHEST)
    gate, up = jnp.split(gate_up, 2, axis=-1)
    each = jnp.einsum(
        "sef,efd->sed", _silu(gate) * up, p["down_proj"], precision=HIGHEST
    )
    return jnp.einsum("sed,se->sd", each, weight[:, first:first + count]), load


def _sequence(cfg: dict, params: dict, tokens: Any) -> tuple:
    """tokens [S] -> (logits [S, vocab], {layer: load [E]})."""
    eps = cfg["rms_norm_eps"]
    x = params["embed"]["embedding"][tokens]
    loads = {}

    def layer_fn(x, p, full):
        h = x + _attention(cfg, _rms_norm(x, p["norm_attention"], eps),
                           p["attention"], full)
        out, load = _experts(cfg, _rms_norm(h, p["norm_moe"], eps), p["moe"])
        return h + out, load

    for layer in cfg["layers"]:
        x, loads[f"layer_{layer}"] = jax.checkpoint(
            layer_fn, static_argnums=2
        )(x, params[f"layer_{layer}"], _is_full(cfg, layer))
    x = _rms_norm(x, params["norm_out"], eps)
    return _dense(x, params["head"]), loads


def reference_forward(cfg: dict, params: dict, aux: dict, tokens: Any) -> tuple:
    """(logits [b, s, vocab], the routers' loads as the module's
    ``moe_stats`` collection holds them: each expert's share of the
    batch's token-choices, per layer)."""
    logits, loads = jax.vmap(lambda t: _sequence(cfg, params, t))(tokens)
    stats = {
        name: {"moe": {"moe_load": jnp.mean(load, axis=0)}}
        for name, load in loads.items()
    }
    return logits, {"moe_stats": stats}


def _loss(cfg, params, tokens, targets):
    logits, aux = reference_forward(cfg, params, {}, tokens)
    logp = jax.nn.log_softmax(logits)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(picked), aux


def reference_round(
    cfg: dict, params: dict, aux: dict, xs: Any, ys: Any, weights: Any, lr: float
) -> tuple:
    """One federated round from ONE global model: (per-silo mean local
    loss [n], folded params, folded routers' loads). FedAvg (McMahan et
    al. 2017) over local heavy-ball SGD ``t <- g + m t; p <- p - lr t``,
    momentum from zero each round, a silo's loss the mean of its
    batches' losses before each step, its load that of its LAST batch
    (what a mutable collection holds after a step) —
    ``plain_fedavg_round``'s semantics, but a silo's whole local pass is
    ONE jitted function and the fold donates its accumulator
    (``phi4_mini_flash_reasoning.py``'s form: the harness's check holds
    four models of 2.4 GB when it calls this)."""
    with jax.default_matmul_precision("highest"):
        grad = jax.value_and_grad(
            lambda p, x, y: _loss(cfg, p, x, y), has_aux=True
        )
        tree_map = jax.tree_util.tree_map

        @jax.jit
        def local_pass(p, node_xs, node_ys):
            trace, losses, stats = tree_map(jnp.zeros_like, p), [], None
            for batch in range(node_xs.shape[0]):
                (loss, stats), g = grad(p, node_xs[batch], node_ys[batch])
                trace = tree_map(lambda t, gg: gg + SGD_MOMENTUM * t, trace, g)
                p = tree_map(lambda pp, t: pp - lr * t, p, trace)
                losses.append(loss)
            return jnp.mean(jnp.stack(losses)), p, stats

        fold = jax.jit(
            lambda acc, p, w: tree_map(lambda a, leaf: a + w * leaf, acc, p),
            donate_argnums=0,
        )
        wnorm = jnp.asarray(weights, jnp.float32)
        wnorm = wnorm / jnp.sum(wnorm)
        losses = []
        folded, folded_aux = tree_map(jnp.zeros_like, (params, aux))
        for node in range(xs.shape[0]):
            loss, p, stats = local_pass(params, xs[node], ys[node])
            folded = fold(folded, p, wnorm[node])
            folded_aux = fold(folded_aux, stats, wnorm[node])
            del p
            losses.append(loss)
        return jnp.stack(losses), folded, folded_aux
