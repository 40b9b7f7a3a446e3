"""``tpfl.models.MellumLM`` against the plain reference of the
configuration ``mellum2_12b_a2p5b`` (``benchmark/models``) in float32 on
seeded weights: loss and gradients of a whole period, one layer of each
kind alone, the rotary and YaRN tables against the published formulas,
and the band with 8 query heads a key head against a dense masked
softmax on a sequence longer than the window."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from tpfl.models import MellumLM, create_model
from tpfl.models.mellum import apply_rotary, rotary_frequencies
from tpfl.parallel.ring_attention import blockwise_attention

REFERENCE = cells.load_model("mellum2_12b_a2p5b")
YARN = {
    "rope_type": "yarn", "rope_theta": 100.0, "factor": 16,
    "original_max_position_embeddings": 16, "beta_fast": 32, "beta_slow": 1,
    "attention_factor": 1.2772588722239782,
}


def _config(layers, first=0, count=4):
    """A toy configuration in the benchmark file's own keys."""
    return {
        "hidden_size": 32, "num_attention_heads": 8, "num_key_value_heads": 2,
        "head_dim": 8, "moe_intermediate_size": 16, "vocab_size": 64,
        "sliding_window": 8, "num_experts": count, "num_experts_per_tok": 4,
        "rms_norm_eps": 1e-6, "compute_dtype": "float32",
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
        "layers": list(layers), "published": {"num_hidden_layers": 4},
        "rope_parameters": {
            "full_attention": YARN,
            "sliding_attention": {"rope_type": "default", "rope_theta": 100.0},
        },
        "experts_held": {"first": first, "count": count, "router_width": 16},
    }


def _setup(cfg, seq=32):
    module = REFERENCE.build_module(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, seq + 1), 0, 64)
    x, y = tokens[:, :-1], tokens[:, 1:]
    variables = module.init(jax.random.PRNGKey(1), x[:1], train=False)
    # Away from the initial values a wrong reading could hide behind
    # (unit norm scales).
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 100))
    params = jax.tree_util.tree_map(
        lambda v: v + 0.05 * jax.random.normal(next(keys), v.shape),
        variables["params"],
    )
    return module, params, {"moe_stats": variables["moe_stats"]}, x, y


def _max_rel(a, b):
    flat_a, flat_b = map(jax.tree_util.tree_leaves, (a, b))
    assert len(flat_a) == len(flat_b)
    return max(
        float(jnp.abs(u - v).max() / (jnp.abs(v).max() + 1e-30))
        for u, v in zip(flat_a, flat_b)
    )


@pytest.mark.parametrize(
    "first, count", [(0, 4), (8, 8), (0, 16)],
    ids=["experts_0_3", "experts_8_15", "all_16"],
)
def test_loss_gradients_and_loads_meet_the_reference(first, count):
    cfg = _config([0, 1, 2, 3], first, count)
    module, params, aux, x, y = _setup(cfg)

    def owned(p):
        return module.apply(
            {"params": p, **aux}, x, train=True, targets=y, mutable=["moe_stats"]
        )

    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = jax.jit(jax.value_and_grad(owned, has_aux=True))(params)
        (want, want_stats), want_grads = jax.jit(jax.value_and_grad(
            lambda p: REFERENCE._loss(cfg, p, x, y), has_aux=True
        ))(params)
        logits = module.apply({"params": params, **aux}, x)
        want_logits, _ = REFERENCE.reference_forward(cfg, params, {}, x)
    assert abs(loss - want) / want < 1e-5
    assert _max_rel(grads, want_grads) < 2e-4
    assert _max_rel(logits, want_logits) < 2e-5
    # The routers' loads: every layer's sums to one over the 16 experts.
    assert _max_rel(stats, want_stats) < 1e-6
    for load in jax.tree_util.tree_leaves(stats):
        assert load.shape == (16,) and float(load.sum()) == pytest.approx(1.0)


@pytest.mark.parametrize("layer", [1, 3], ids=["banded", "full_yarn"])
def test_one_layer_of_each_kind_meets_the_reference(layer):
    cfg = _config([layer])
    module, params, aux, x, _ = _setup(cfg, seq=40)
    assert sorted(params) == ["embed", "head", f"layer_{layer}", "norm_out"]
    with jax.default_matmul_precision("highest"):
        logits = module.apply({"params": params, **aux}, x)
        want, _ = REFERENCE.reference_forward(cfg, params, {}, x)
        swapped = [
            "sliding_attention" if kind == "full_attention" else "full_attention"
            for kind in cfg["layer_types"]
        ]
        other = REFERENCE.reference_forward(
            dict(cfg, layer_types=swapped), params, {}, x
        )[0]
    assert _max_rel(logits, want) < 2e-5
    # The kinds differ: the other kind's equations give other logits.
    assert _max_rel(other, want) > 1e-3


def test_rotary_tables_are_the_published_formulas():
    """At the published numbers: theta 500,000 on 128 dimensions; YaRN
    factor 16 at 8,192 original positions, beta 32 / 1."""
    dim, theta = 128, 500000.0
    plain, one = rotary_frequencies(dim, theta)
    assert one == 1.0 and plain.shape == (64,)
    np.testing.assert_allclose(
        plain, [theta ** (-2 * i / dim) for i in range(64)], rtol=1e-6
    )
    yarn = {
        "factor": 16.0, "original_max_position_embeddings": 8192,
        "beta_fast": 32.0, "beta_slow": 1.0,
        "attention_factor": 1.2772588722239782,
    }
    scaled, factor = rotary_frequencies(dim, theta, yarn)
    assert factor == pytest.approx(0.1 * math.log(16) + 1)
    # Correction bounds by hand: dim ln(L / (2 pi beta)) / (2 ln theta).
    low = math.floor(128 * math.log(8192 / (32 * 2 * math.pi)) / (2 * math.log(theta)))
    high = math.ceil(128 * math.log(8192 / (1 * 2 * math.pi)) / (2 * math.log(theta)))
    assert (low, high) == (18, 35)
    for i in range(64):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want = ramp * plain[i] / 16 + (1 - ramp) * plain[i]
        assert float(scaled[i]) == pytest.approx(float(want), rel=1e-6)
    assert float(scaled[0]) == 1.0 and float(scaled[63]) == pytest.approx(float(plain[63]) / 16)
    # The reference's own table agrees (it shares no code with the zoo).
    cfg = {"head_dim": dim, "rope_parameters": {"full_attention": dict(
        yarn, rope_type="yarn", rope_theta=theta)}}
    cos, sin = REFERENCE.rotary_table(cfg, "full_attention", 9)
    angles = np.arange(9)[:, None] * np.asarray(scaled)[None, :]
    np.testing.assert_allclose(cos, np.cos(angles) * factor, atol=1e-5)
    # Rotate-half pairing: dimension i turns with i + dim / 2.
    x = jnp.zeros((1, 9, 1, dim)).at[..., 0].set(1.0)
    turned = apply_rotary(x, scaled, factor)[0, :, 0]
    np.testing.assert_allclose(turned[:, 0], cos[:, 0], atol=1e-5)
    np.testing.assert_allclose(turned[:, 64], sin[:, 0], atol=1e-5)
    assert float(jnp.abs(turned[:, 1:64]).max()) == 0.0


def test_band_with_eight_query_heads_a_key_head_meets_a_dense_softmax():
    s, window, heads, kv, hd = 48, 16, 8, 1, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, s, heads, hd))
    k = jax.random.normal(ks[1], (2, s, kv, hd))
    v = jax.random.normal(ks[2], (2, s, kv, hd))

    def dense(q, k, v):
        pos = jnp.arange(s)
        seen = (pos[:, None] >= pos[None, :]) & (pos[:, None] - pos[None, :] < window)
        scores = jnp.einsum("bqhd,bkd->bhqk", q, k[:, :, 0]) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkd->bqhd", probs, v[:, :, 0])

    def banded(q, k, v):
        return blockwise_attention(
            q, k, v, causal=True, window=window, block_size=16
        )

    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))  # noqa: E731
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(loss(banded), argnums=(0, 1, 2))(q, k, v)
        want = jax.value_and_grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    assert _max_rel(got, want) < 1e-5


def test_zoo_builds_it_and_refuses_what_it_cannot_hold():
    model = create_model(
        "mellum_lm", (16,), vocab=32, dim=16, heads=2, kv_heads=1, head_dim=8,
        n_experts=8, top_k=2, expert_dim=8, held_experts=2, first_expert=6,
    )
    assert set(model.aux_state) == {"moe_stats"}
    assert model.get_parameters()["layer_0"]["moe"]["gate_up_proj"].shape == (2, 16, 16)
    with pytest.raises(ValueError, match="must lie among"):
        MellumLM(n_experts=8, held_experts=4, first_expert=6).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )
