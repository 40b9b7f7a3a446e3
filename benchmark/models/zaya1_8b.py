"""ZAYA1-8B (Zyphra; sizes from the published ``config.json``, the
mechanisms as arXiv:2511.17127 and arXiv:2510.04476 describe them) as
``tpfl.models.ZayaLM`` runs the share of it the configuration holds:
what the harness needs from the configuration ``zaya1_8b``.

- ``build_module`` — the program's own module on the configuration's
  published layers and held experts;
- ``input_shape``, ``samples_per_round`` — ``gpt2_small``'s own
  (imported); ``make_data`` — its seeded order-1 Markov source with an
  alphabet of this file's own: ``ACTIVE_TOKENS`` = 8,192 ids (a quarter
  of the vocabulary SLICE, spread over it), since with ONE expert a
  token the held experts' share of a step's tokens is a draw over the
  token TYPES in the data, and 512 types leave it a spread of 0.022 a
  layer from seed to seed, which ``rounds_per_s`` follows (PERF.md §6,
  PR 34);
- ``fwd_mults_per_sample`` — per TOKEN, forward, what THIS CHIP
  multiplies under balanced routing (a token's one expert is held half
  the time), the convolutions and the router MLP included: the count
  of the PARTIAL layer, so ``mfu_device_pct`` is of the partial layer;
  ``expert_flops_per_round`` — the three grouped products' operations
  under balanced routing, for their roofline share;
- ``reference_round`` — the PLAIN REFERENCE in float32 ``jax.numpy``
  under ``jax.default_matmul_precision("highest")``, written from the
  layer equations of ISSUE 34 (the configuration file repeats them
  under ``assumed``): a dense S x S masked softmax, the convolutions as
  explicit shifted sums, EVERY held expert evaluated on every token and
  masked by the routing, its own rotary table, the router MLP and its
  state ``z`` written out. It shares no code with ``tpfl.models`` or
  ``tpfl.parallel``; it reads the flax parameter tree only as named
  arrays. Two departures from "no blocking", both for room: each layer
  is a ``jax.checkpoint``, and a silo's local pass ends in the fold (one
  jitted function that donates the running sum), because the harness's
  check already holds two float32 models beside it and 601.7 M
  parameters are 2.4 GB a copy (model-configs guide §3: "computed in
  blocks so that it fits").
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.models.gpt2_small import (  # noqa: F401  (the harness's MODEL_API)
    SAMPLE_UNIT, SUCCESSORS, input_shape, samples_per_round,
)
from benchmark.models.plain_fedavg import SGD_MOMENTUM
from benchmark.cells import BenchmarkFileError

# At import, not inside ``build_module``: a program that lacks the model
# (the parent commit) then fails in ``cells.load_cell``, before anything
# touches the device.
try:
    from tpfl.models import ZayaLM
except ImportError as e:
    raise BenchmarkFileError(
        "the configuration zaya1_8b needs tpfl.models.ZayaLM, which this "
        "program does not have"
    ) from e

#: Engine (bf16 matmuls; float32 router, q / k normalisation, softmax,
#: norms, rotary table) against this reference (float32, "highest"),
#: relative, on the chip at published widths
#: (harness.check_against_reference, 2 silos x 2048 tokens); every
#: reading in PERF.md §6 and CHANGES.md (PR 34). Readings "over 512
#: ids" are of the PR's first round, on ``gpt2_small``'s generator.
#: - loss: read 4.8e-6 .. 1.7e-5 (the loss of an untrained model barely
#:   feels bf16); the accepted cells' limit, fifty times the reading.
#: - update: read 0.887-0.906% over 11 seeds (0.60-0.67% over 512 ids)
#:   — the bf16 matmuls' rounding through five blocks and back, plus the
#:   choices that flip (below); the three leaves that carry most of it are the first
#:   layers' ``o_proj``. The limit leaves 3.3 times that. Parameters
#:   STORED in bf16 read 93% (over 512 ids; at a fine-tuning rate a step
#:   is under half a bf16 ulp of nearly every weight: the update is
#:   lost, and 1 is what a state left unchanged reads): they fail it.
#: - aux (the routers' loads — the program's ROUTING against the
#:   reference's; the balancing bias beside them is zero on both sides):
#:   read 0.533-0.682% (0.51-0.65% over 512 ids).
#:   An untrained router MLP's probabilities differ by ~1e-5 between
#:   experts and bf16 hidden states move them about as much, so of the
#:   check's 2 x 2048 tokens a layer a few in a hundred sit within
#:   rounding of a tie and choose another expert than the reference's
#:   (in float32 alone none does: the CPU tests read 0). ONE flip moves
#:   1 / 2048 of a silo's load (0.8% of an expert's ~1 / 16 share) from
#:   one expert to another, and that token's row from one expert's
#:   gradient to another's; n flips a layer read ~sqrt(2 n) / 2048 x 4.
#:   The limit leaves 2.9 times the worst seed. A router MLP whose
#:   PRODUCTS are rounded to bf16 reads 0.72-0.83% (over 512 ids; update
#:   0.60-0.61%): it flips few choices that bf16 hidden states had not
#:   flipped anyway, and NO limit that leaves the seeds room can fail
#:   it — the router's float32 is held by the CPU tests alone
#:   (``tests/test_zaya.py::test_loss_gradients_and_loads_meet_the_reference``,
#:   ``tests/benchmark/test_benchmark_zaya1.py::test_a_lower_precision_against_the_check``).
CHECK_TOLERANCES = {"loss": 1e-3, "update": 3e-2, "aux": 2e-2}
HIGHEST = lax.Precision.HIGHEST
#: Size of the Markov source's alphabet (spread over the slice): each
#: token type chooses ONE expert a layer, so the held half's share of
#: the tokens averages over the types a round's 32,768 tokens show.
ACTIVE_TOKENS = 8192


def build_module(cfg: dict) -> Any:
    held = cfg["experts_held"]
    return ZayaLM(
        vocab=int(cfg["vocab_size"]), dim=int(cfg["hidden_size"]),
        heads=int(cfg["num_attention_heads"]),
        kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        n_layers=int(cfg["published"]["num_hidden_layers"]),
        layers=tuple(cfg["layers"]),
        conv_time=int(cfg["cca_time0"]), conv_head=int(cfg["cca_time1"]),
        rotary_fraction=float(cfg["partial_rotary_factor"]),
        rope_theta=float(cfg["rope_parameters"]["hybrid"]["rope_theta"]),
        n_experts=int(held["router_width"]),
        expert_dim=int(cfg["moe_intermediate_size"]),
        router_dim=int(cfg["router_hidden_size"]),
        held_experts=int(cfg["num_experts"]), first_expert=int(held["first"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        compute_dtype=jnp.dtype(cfg["compute_dtype"]),
    )


def make_data(key: Any, cfg: dict, traffic: dict) -> tuple:
    """(xs, ys) int32 [n, nb, b, seq]: ys is xs shifted by one token.
    ``gpt2_small.make_data``'s source — token t + 1 is one of
    ``SUCCESSORS`` fixed successors of token t, chosen uniformly — over
    ``ACTIVE_TOKENS`` ids. Traced inside one jit by the harness."""
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


def routed_rows_per_token(cfg: dict) -> float:
    """Rows a token sends to the experts held here under balanced
    routing: its ``k`` choices times the share of the experts held."""
    held = cfg["experts_held"]
    return cfg["num_experts_per_tok"] * cfg["num_experts"] / held["router_width"]


def fwd_mults_per_sample(cfg: dict, traffic: dict) -> int:
    """Per token, forward, recomputation not counted, of what this chip
    holds. A layer: the latent projections ``d (q + 2 kv)`` and ``q d``
    back; the depthwise taps ``t0 (q + kv)`` and the per-head ones ``t1
    hd (q + kv)``; scores and values ``2 q`` per visible key (``(S + 1)
    / 2`` of them, every layer full); the router ``d r + 2 r r + r E``;
    and ``3 d f`` for each row the held experts are expected to receive
    (half a row a token). Head ``d V`` over the slice. The embedding is
    a look-up."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    hd, r = cfg["head_dim"], cfg["router_hidden_size"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    layer = (
        d * (q + 2 * kv) + q * d
        + cfg["cca_time0"] * (q + kv) + cfg["cca_time1"] * hd * (q + kv)
        + 2 * q * (traffic["seq"] + 1) / 2
        + d * r + 2 * r * r + r * cfg["experts_held"]["router_width"]
        + routed_rows_per_token(cfg) * 3 * d * f
    )
    return int(len(cfg["layers"]) * layer + d * cfg["vocab_size"])


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


def _dot(x, w):
    return jnp.dot(x, w, precision=HIGHEST)


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _gelu(x):
    return 0.5 * x * (1.0 + lax.erf(x / math.sqrt(2.0)))


def _previous(u, lag: int = 1):
    """u [S, ...] -> u[t - lag], zeros where t < lag."""
    if lag == 0:
        return u
    return jnp.concatenate([jnp.zeros_like(u[:lag]), u[:-lag]], axis=0)


def rotary_table(cfg: dict, seq: int) -> tuple:
    """``(cos, sin) [seq, rotated / 2]`` of ``rope_parameters.hybrid``:
    the rotated part is the first ``partial_rotary_factor`` of a head,
    and the frequencies ``theta^(-2 i / rotated)`` are over that part."""
    rope = cfg["rope_parameters"]["hybrid"]
    rotated = int(cfg["head_dim"] * rope["partial_rotary_factor"])
    pair = jnp.arange(rotated // 2, dtype=jnp.float32)
    inv_freq = float(rope["rope_theta"]) ** (-2.0 * pair / rotated)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def _rotate_part(x, cos, sin):
    """x [S, H, D]: of the first ``2 x cos.shape[-1]`` dimensions,
    dimension i turns with dimension i + half of that part; the rest of
    the head is left as it is."""
    half = cos.shape[-1]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def _convolve(u, w_time, w_head, heads: int):
    """u [S, heads x hd] -> [S, heads, hd]: the depthwise convolution
    (``w_time [taps, C]``: tap j weighs the token j before), then the
    one grouped by head (``w_head [heads, taps, hd, hd]``)."""
    s = u.shape[0]
    u1 = sum(w_time[j] * _previous(u, j) for j in range(w_time.shape[0]))
    u1 = u1.reshape(s, heads, -1)
    return sum(
        jnp.einsum("shc,hcd->shd", _previous(u1, j), w_head[:, j], precision=HIGHEST)
        for j in range(w_head.shape[1])
    )


def _unit(u):
    return u * math.sqrt(u.shape[-1]) / jnp.sqrt(
        jnp.sum(u * u, axis=-1, keepdims=True)
    )


def _cca(cfg, n, p):
    """n [S, d] (normed) -> [S, d]: steps 1-7 of the issue's CCA."""
    s = n.shape[0]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, per = cfg["head_dim"], heads // kv_heads
    q_lat = _dot(n, p["q_proj"]["kernel"])
    k_lat = _dot(n, p["k_proj"]["kernel"])
    q2 = _convolve(q_lat, p["q_conv_time"], p["q_conv_head"], heads)
    k2 = _convolve(k_lat, p["k_conv_time"], p["k_conv_head"], kv_heads)
    # The q-k mean of the PRE-convolution latents across grouped heads.
    q_lat = q_lat.reshape(s, kv_heads, per, hd)
    k_lat = k_lat.reshape(s, kv_heads, hd)
    q = q2.reshape(s, kv_heads, per, hd) + (q_lat + k_lat[:, :, None, :]) / 2
    k = k2 + (k_lat + jnp.mean(q_lat, axis=2)) / 2
    cos, sin = rotary_table(cfg, s)
    q = _rotate_part(_unit(q).reshape(s, heads, hd), cos, sin)
    k = _rotate_part(_unit(k) * p["k_temperature"][:, None], cos, sin)
    # The value shift: the first half of the key heads read this token,
    # the second half the token before (v_proj holds W_v1 | W_v2).
    w_v1, w_v2 = jnp.split(p["v_proj"]["kernel"], 2, axis=-1)
    v = jnp.concatenate([_dot(n, w_v1), _dot(_previous(n), w_v2)], axis=-1)
    v = v.reshape(s, kv_heads, hd)
    pos = jnp.arange(s)
    visible = pos[:, None] >= pos[None, :]
    scores = jnp.einsum(
        "qgrh,kgh->grqk", q.reshape(s, kv_heads, per, hd), k, precision=HIGHEST
    ) / math.sqrt(hd)
    probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("grqk,kgh->qgrh", probs, v, precision=HIGHEST)
    return _dot(out.reshape(s, heads * hd), p["o_proj"]["kernel"])


def _experts(cfg, n, z_below, p, bias):
    """n [S, d], z_below [S, r], the balancing bias [E] -> (the held
    experts' part of the layer [S, d], this layer's router state [S, r],
    the router's load [E]): every held expert on every token, weighted
    by the routing (zero where the token chose another)."""
    held = cfg["experts_held"]
    width = held["router_width"]
    z = _dot(n, p["router_down"]) + p["router_carry"] * z_below
    hidden = _rms_norm(z, p["router_norm"], cfg["rms_norm_eps"])
    hidden = _gelu(_dot(hidden, p["router_fc1"]))
    hidden = _gelu(_dot(hidden, p["router_fc2"]))
    probs = jax.nn.softmax(_dot(hidden, p["router_out"]), axis=-1)
    # The balancing bias moves the CHOICE; the gate is the chosen
    # expert's probability, which the bias does not enter.
    chosen = jnp.argmax(probs + bias, axis=-1)[:, None] == jnp.arange(width)  # [S, E]
    weight = jnp.where(chosen, probs, 0.0)
    load = jnp.sum(chosen, axis=0) / chosen.shape[0]
    first, count = held["first"], cfg["num_experts"]
    gate_up = jnp.einsum("sd,edf->sef", n, p["gate_up_proj"], precision=HIGHEST)
    gate, up = jnp.split(gate_up, 2, axis=-1)
    each = jnp.einsum(
        "sef,efd->sed", _silu(gate) * up, p["down_proj"], precision=HIGHEST
    )
    return jnp.einsum("sed,se->sd", each, weight[:, first:first + count]), z, load


def _layer(cfg, x, z, p, bias):
    eps = cfg["rms_norm_eps"]
    h = p["attention_residual_scale"] * x + p["attention_update_scale"] * _cca(
        cfg, _rms_norm(x, p["norm_attention"], eps), p["attention"]
    )
    out, z, load = _experts(
        cfg, _rms_norm(h, p["norm_moe"], eps), z, p["moe"], bias
    )
    return p["moe_residual_scale"] * h + p["moe_update_scale"] * out, z, load


def _sequence(cfg: dict, params: dict, biases: dict, tokens: Any) -> tuple:
    """tokens [S], {layer: balancing bias [E]} -> (logits [S, vocab],
    {layer: load [E]})."""
    table = params["embed"]["embedding"]
    x = table[tokens]
    z = jnp.zeros((tokens.shape[0], cfg["router_hidden_size"]), jnp.float32)
    loads = {}
    for layer in cfg["layers"]:
        name = f"layer_{layer}"
        x, z, loads[name] = jax.checkpoint(
            lambda x, z, p, bias: _layer(cfg, x, z, p, bias)
        )(x, z, params[name], biases[name])
    x = _rms_norm(x, params["norm_out"], cfg["rms_norm_eps"])
    return _dot(x, table.T), loads  # tied, no bias


def reference_forward(cfg: dict, params: dict, aux: dict, tokens: Any) -> tuple:
    """(logits [b, s, vocab], the module's ``moe_stats`` collection
    after the step, per layer: each expert's share of the batch's tokens,
    and the balancing bias as it was — frozen state). ``aux`` holds the
    collection before the step (``{}``: a zero bias)."""
    width = cfg["experts_held"]["router_width"]
    before = {
        f"layer_{layer}": (
            aux["moe_stats"][f"layer_{layer}"]["moe"]["balance_bias"] if aux
            else jnp.zeros((width,), jnp.float32)
        )
        for layer in cfg["layers"]
    }
    logits, loads = jax.vmap(lambda t: _sequence(cfg, params, before, t))(tokens)
    stats = {}
    for name, load in loads.items():
        stats[name] = {"moe": {
            "moe_load": jnp.mean(load, axis=0), "balance_bias": before[name],
        }}
    return logits, {"moe_stats": stats}


def _loss(cfg, params, aux, tokens, targets):
    logits, aux = reference_forward(cfg, params, aux, tokens)
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
    batches' losses before each step, its ``moe_stats`` carried from
    batch to batch (the load of its LAST batch: what a mutable
    collection holds after the steps) —
    ``plain_fedavg_round``'s semantics, with a silo's whole local pass
    AND its term of the weighted mean as ONE jitted function that
    donates the running sums (module docstring)."""
    with jax.default_matmul_precision("highest"):
        grad = jax.value_and_grad(
            lambda p, stats, x, y: _loss(cfg, p, stats, x, y), has_aux=True
        )
        tree_map = jax.tree_util.tree_map

        def silo(folded, folded_aux, p, stats, node_xs, node_ys, w):
            trace, losses = tree_map(jnp.zeros_like, p), []
            for batch in range(node_xs.shape[0]):
                (loss, stats), g = grad(p, stats, node_xs[batch], node_ys[batch])
                trace = tree_map(lambda t, gg: gg + SGD_MOMENTUM * t, trace, g)
                p = tree_map(lambda pp, t: pp - lr * t, p, trace)
                losses.append(loss)
            add = lambda acc, leaf: acc + w * leaf  # noqa: E731
            return (
                jnp.mean(jnp.stack(losses)), tree_map(add, folded, p),
                tree_map(add, folded_aux, stats),
            )

        silo = jax.jit(silo, donate_argnums=(0, 1))
        wnorm = jnp.asarray(weights, jnp.float32)
        wnorm = wnorm / jnp.sum(wnorm)
        losses = []
        folded, folded_aux = tree_map(jnp.zeros_like, (params, aux))
        for node in range(xs.shape[0]):
            loss, folded, folded_aux = silo(
                folded, folded_aux, params, aux, xs[node], ys[node], wnorm[node]
            )
            losses.append(loss)
        return jnp.stack(losses), folded, folded_aux
