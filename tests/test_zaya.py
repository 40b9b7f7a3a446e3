"""``tpfl.models.ZayaLM`` against the plain reference of the
configuration ``zaya1_8b`` (``benchmark/models``) in float32 on seeded
weights: loss, logits, gradients and the routers' loads of three layers;
causality (the convolutions and the value shift are where a leak would
hide); each convolution against an explicit loop; the partial rotary
table; the q-k mean with 4 query heads a key head; and the router state
that a layer hands to the one above, with and without ``nn.remat``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from tpfl.models import ZayaLM, create_model
from tpfl.models import zaya
from tpfl.models.mellum import rotary_frequencies

REFERENCE = cells.load_model("zaya1_8b")
def _config(layers, first=0, count=4):
    """A toy configuration in the benchmark file's own keys."""
    return {
        "hidden_size": 32, "num_attention_heads": 8, "num_key_value_heads": 2,
        "head_dim": 8, "moe_intermediate_size": 16, "router_hidden_size": 12,
        "vocab_size": 64, "num_experts": count, "num_experts_per_tok": 1,
        "cca_time0": 2, "cca_time1": 2, "partial_rotary_factor": 0.5,
        "rms_norm_eps": 1e-5, "compute_dtype": "float32",
        "layers": list(layers), "published": {"num_hidden_layers": 40},
        "rope_parameters": {"hybrid": {
            "partial_rotary_factor": 0.5, "rope_theta": 100.0,
            "rope_type": "default",
        }},
        "experts_held": {"first": first, "count": count, "router_width": 8},
    }


def _setup(cfg, seq=32):
    module = REFERENCE.build_module(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, seq + 1), 0, 64)
    x, y = tokens[:, :-1], tokens[:, 1:]
    variables = module.init(jax.random.PRNGKey(1), x[:1], train=False)
    # Away from the initial values a wrong reading could hide behind
    # (unit scales and temperature, a zero carry of the router state).
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 200))
    params = jax.tree_util.tree_map(
        lambda v: v + 0.05 * jax.random.normal(next(keys), v.shape),
        variables["params"],
    )
    # ... and a balancing bias that moves some choices (probabilities
    # differ by a few thousandths at these widths).
    stats = jax.tree_util.tree_map(
        lambda v: 0.0001 * jax.random.normal(next(keys), v.shape),
        variables["moe_stats"],
    )
    return module, params, {"moe_stats": stats}, x, y


def _max_rel(a, b):
    flat_a, flat_b = map(jax.tree_util.tree_leaves, (a, b))
    assert len(flat_a) == len(flat_b)
    return max(
        float(jnp.abs(u - v).max() / (jnp.abs(v).max() + 1e-30))
        for u, v in zip(flat_a, flat_b)
    )


@pytest.mark.parametrize(
    "first, count", [(0, 4), (4, 4), (0, 8)],
    ids=["experts_0_3", "experts_4_7", "all_8"],
)
def test_loss_gradients_and_loads_meet_the_reference(first, count):
    cfg = _config([0, 1, 2], first, count)
    module, params, aux, x, y = _setup(cfg)

    def owned(p):
        return module.apply(
            {"params": p, **aux}, x, train=True, targets=y, mutable=["moe_stats"]
        )

    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = jax.jit(jax.value_and_grad(owned, has_aux=True))(params)
        (want, want_stats), want_grads = jax.jit(jax.value_and_grad(
            lambda p: REFERENCE._loss(cfg, p, aux, x, y), has_aux=True
        ))(params)
        logits = module.apply({"params": params, **aux}, x)
        want_logits, _ = REFERENCE.reference_forward(cfg, params, aux, x)
    assert abs(loss - want) / want < 1e-5
    assert _max_rel(grads, want_grads) < 2e-4
    assert _max_rel(logits, want_logits) < 2e-5
    # Every leaf learns but the first layer's carry, which weighs zeros.
    dead = [
        jax.tree_util.keystr(path)
        for path, g in jax.tree_util.tree_leaves_with_path(grads)
        if float(jnp.abs(g).max()) == 0.0
    ]
    assert dead == ["['layer_0']['moe']['router_carry']"]
    # The routers' loads (every layer's sums to one over the 8 experts)
    # and the balancing bias, handed back as it came: frozen.
    assert _max_rel(stats, want_stats) < 1e-6
    for name, layer in stats["moe_stats"].items():
        load, bias = layer["moe"]["moe_load"], layer["moe"]["balance_bias"]
        assert load.shape == bias.shape == (8,)
        assert (bias == aux["moe_stats"][name]["moe"]["balance_bias"]).all()
        assert float(load.sum()) == pytest.approx(1.0)


def test_nothing_before_a_perturbed_token_moves():
    """Causality, bit for bit: another token at position t changes no
    logit before t, and changes the logits from t on — through the
    convolutions' and the value shift's previous token, t + 1 too."""
    cfg = _config([0, 1, 2], 0, 8)
    module, params, aux, x, _ = _setup(cfg)
    t = 13
    other = x.at[:, t].set((x[:, t] + 7) % 64)
    apply = jax.jit(lambda tokens: module.apply({"params": params, **aux}, tokens))
    a, b = np.asarray(apply(x)), np.asarray(apply(other))
    assert (a[:, :t] == b[:, :t]).all()
    assert np.abs(a[:, t] - b[:, t]).max() > 1e-3
    assert np.abs(a[:, t + 1] - b[:, t + 1]).max() > 1e-3
    # The reference is causal by the same test.
    ref = lambda tokens: np.asarray(  # noqa: E731
        REFERENCE.reference_forward(cfg, params, aux, tokens)[0]
    )
    assert (ref(x)[:, :t] == ref(other)[:, :t]).all()


def test_depthwise_convolution_is_the_explicit_loop():
    u = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (2, 9, 6)))
    w = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (3, 6)))
    want = np.zeros_like(u)
    for t in range(9):
        for j in range(3):
            if t - j >= 0:
                want[:, t] += w[j] * u[:, t - j]
    got = zaya.depthwise_causal_conv(jnp.asarray(u), jnp.asarray(w))
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert (np.asarray(zaya.shift_time(jnp.asarray(u), 2))[:, :2] == 0).all()


def test_head_convolution_is_the_explicit_loop():
    """Grouped by head: head h's channels mix among themselves only."""
    u = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (2, 7, 3, 4)))
    w = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (3, 2, 4, 4)))
    want = np.zeros_like(u)
    for t in range(7):
        for h in range(3):
            for j in range(2):
                if t - j >= 0:
                    want[:, t, h] += u[:, t - j, h] @ w[h, j]
    with jax.default_matmul_precision("highest"):
        got = zaya.head_causal_conv(jnp.asarray(u), jnp.asarray(w), jnp.float32)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert got.dtype == jnp.float32


def test_partial_rotary_leaves_the_unrotated_half_bit_equal():
    """Half of each head turns (rotate-half pairing INSIDE that half,
    frequencies over that half), the other half passes bit for bit."""
    inv_freq, factor = rotary_frequencies(64, 5000000.0)
    assert inv_freq.shape == (32,) and factor == 1.0
    np.testing.assert_allclose(
        inv_freq, 5000000.0 ** (-np.arange(32) / 32.0), rtol=1e-6
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 128))
    out = np.asarray(zaya.apply_partial_rotary(x, inv_freq))
    assert (out[..., 64:] == np.asarray(x)[..., 64:]).all()
    assert (out[:, 0] == np.asarray(x)[:, 0]).all()  # position 0: no turn
    pos = 3
    a, b = np.asarray(x)[0, pos, 1, :32], np.asarray(x)[0, pos, 1, 32:64]
    cos, sin = np.cos(pos * np.asarray(inv_freq)), np.sin(pos * np.asarray(inv_freq))
    np.testing.assert_allclose(out[0, pos, 1, :32], a * cos - b * sin, atol=1e-6)
    np.testing.assert_allclose(out[0, pos, 1, 32:64], b * cos + a * sin, atol=1e-6)
    # The reference's table is the same table.
    ref_cos, ref_sin = REFERENCE.rotary_table(
        {"head_dim": 128, "rope_parameters": {"hybrid": {
            "partial_rotary_factor": 0.5, "rope_theta": 5000000,
        }}}, 5,
    )
    np.testing.assert_allclose(ref_cos[pos], cos, atol=1e-6)
    np.testing.assert_allclose(ref_sin[pos], sin, atol=1e-6)


def test_qk_mean_with_four_query_heads_a_key_head():
    q = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (1, 3, 8, 5)))
    k = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (1, 3, 2, 5)))
    for_q, for_k = zaya.qk_mean(jnp.asarray(q), jnp.asarray(k))
    for head in range(8):
        np.testing.assert_allclose(
            for_q[:, :, head], (q[:, :, head] + k[:, :, head // 4]) / 2, atol=1e-6
        )
    for head in range(2):
        mean = q[:, :, 4 * head:4 * head + 4].mean(axis=2)
        np.testing.assert_allclose(
            for_k[:, :, head], (k[:, :, head] + mean) / 2, atol=1e-6
        )
    unit = zaya.unit_heads(jnp.asarray(q))
    np.testing.assert_allclose(
        np.linalg.norm(unit, axis=-1), np.sqrt(5.0), rtol=1e-5
    )


def _scale_router_down(params, layer, factor):
    """``z`` of ``layer`` times ``factor``: its own routing reads
    RMSNorm(z), which the factor leaves alone; the layer ABOVE adds the
    scaled state to its own."""
    scaled = jax.tree_util.tree_map(lambda v: v, params)
    moe = dict(scaled[layer]["moe"])
    moe["router_down"] = moe["router_down"] * factor
    scaled[layer] = dict(scaled[layer], moe=moe)
    return scaled


def test_router_reads_the_layer_below_through_remat():
    """In the model (blocks under ``nn.remat``): scaling layer 0's ``z``
    leaves layer 0's routing as it is and moves layer 1's; and the carry
    of layer 1 gets a gradient."""
    cfg = _config([0, 1], 0, 8)
    module, params, aux, x, y = _setup(cfg)
    for layer in ("layer_0", "layer_1"):  # a carry that weighs
        params[layer]["moe"]["router_carry"] = jnp.float32(2.0)

    def loads(p):
        _, stats = module.apply(
            {"params": p, **aux}, x, train=True, targets=y, mutable=["moe_stats"]
        )
        return [
            np.asarray(stats["moe_stats"][f"layer_{i}"]["moe"]["moe_load"])
            for i in (0, 1)
        ]

    with jax.default_matmul_precision("highest"):
        below, above = loads(params)
        below_scaled, above_scaled = loads(_scale_router_down(params, "layer_0", 4.0))
        grads = jax.grad(lambda p: module.apply(
            {"params": p, **aux}, x, train=True, targets=y
        ))(params)
    assert (below == below_scaled).all()
    assert np.abs(above - above_scaled).max() > 0.0
    assert float(jnp.abs(grads["layer_1"]["moe"]["router_carry"])) > 0.0
    assert float(jnp.abs(grads["layer_0"]["moe"]["router_down"]).max()) > 0.0


def test_router_reads_the_layer_below_without_remat():
    """Two blocks applied by hand (no ``nn.remat``): block 1's state is
    its own projection plus its carry times the state it is handed."""
    block = zaya.ZayaBlock(
        heads=8, kv_heads=2, head_dim=8, conv_time=2, conv_head=2,
        rotary_fraction=0.5, theta=100.0, n_experts=8, expert_dim=16,
        router_dim=12, held_experts=8, first_expert=0, norm_eps=1e-5, out_std=0.001,
        compute_dtype=jnp.float32,
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32))
    z = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 12))
    variables = block.init(jax.random.PRNGKey(2), x, z)
    params = dict(variables["params"])
    params["moe"] = dict(params["moe"], router_carry=jnp.float32(0.5))

    def run(z):
        (out, z_out), stats = block.apply(
            {"params": params, "moe_stats": variables["moe_stats"]}, x, z,
            mutable=["moe_stats"],
        )
        return out, z_out, np.asarray(stats["moe_stats"]["moe"]["moe_load"])

    out, z_out, load = run(z)
    out_zero, z_zero, load_zero = run(jnp.zeros_like(z))
    np.testing.assert_allclose(z_out - z_zero, 0.5 * z, atol=1e-5)
    assert np.abs(load - load_zero).max() > 0.0
    assert float(jnp.abs(out - out_zero).max()) > 0.0


def test_balancing_bias_is_frozen_state_that_moves_the_choice():
    """State no gradient reaches and no step moves: zero at the start
    and after training steps; a bias handed in comes back as it was,
    and moves the CHOICE (the loads) while it is there."""
    cfg = _config([0, 1], 0, 8)
    module, params, aux, x, y = _setup(cfg, seq=64)
    fresh = module.init(jax.random.PRNGKey(1), x[:1], train=False)["moe_stats"]
    assert all(
        float(jnp.abs(v).max()) == 0.0 for v in jax.tree_util.tree_leaves(fresh)
    )
    step = jax.jit(lambda v: module.apply(
        v, x, train=True, targets=y, mutable=["moe_stats"]
    ))
    variables = {"params": params, "moe_stats": fresh}
    for _ in range(2):
        _, stats = step(variables)
        variables = {"params": params, **stats}
    loads = {
        name: layer["moe"]["moe_load"]
        for name, layer in stats["moe_stats"].items()
    }
    for layer in stats["moe_stats"].values():
        assert float(jnp.abs(layer["moe"]["balance_bias"]).max()) == 0.0
    # A bias towards expert 3 sends every token there, and stays.
    towards = jnp.zeros((8,)).at[3].set(1.0)
    biased = jax.tree_util.tree_map(lambda v: v, stats["moe_stats"])
    for layer in biased.values():
        layer["moe"]["balance_bias"] = towards
    _, moved = step({"params": params, "moe_stats": biased})
    for name, layer in moved["moe_stats"].items():
        np.testing.assert_array_equal(layer["moe"]["balance_bias"], towards)
        assert float(layer["moe"]["moe_load"][3]) == 1.0
        assert float(loads[name][3]) < 1.0
    # No gradient reaches it, and a forward pass without the mutable
    # collection reads it and leaves it.
    grads = jax.grad(lambda v: module.apply(v, x, train=True, targets=y))(variables)
    assert all(
        float(jnp.abs(g).max()) == 0.0
        for g in jax.tree_util.tree_leaves(grads["moe_stats"])
    )
    assert module.apply(variables, x).shape == (2, 64, 64)


def test_zoo_builds_it_by_name():
    model = create_model(
        "zaya_lm", (16,), vocab=32, dim=16, heads=4, kv_heads=2, head_dim=8,
        n_layers=1, n_experts=4, expert_dim=8, router_dim=8,
        compute_dtype=jnp.float32,
    )
    assert isinstance(model.module, ZayaLM)
    assert sorted(model.aux_state) == ["moe_stats"]
    with pytest.raises(ValueError, match="kv_heads"):
        ZayaLM(heads=4, kv_heads=3).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )
