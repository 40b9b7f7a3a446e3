"""The held-experts layer (``tpfl.parallel.moe.held_experts_moe``): the
shares of the chips that hold the experts add up to the uncut layer, it
drops nothing under any imbalance, it batches under ``jax.vmap`` as two
separate calls, its Pallas form equals its XLA form, the router decides
in float32, and no product over all held experts is ever formed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpfl.parallel import compat, moe

T, D, F, E, K = 64, 32, 48, 16, 4


def _weights(seed=0, silos=None):
    lead = () if silos is None else (silos,)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (
        jax.random.normal(ks[0], (*lead, T, D)),
        jax.random.normal(ks[1], (*lead, D, E)),
        jax.random.normal(ks[2], (*lead, E, D, 2 * F)) / np.sqrt(D),
        jax.random.normal(ks[3], (*lead, E, F, D)) / np.sqrt(F),
    )


def _share(x, router, w_in, w_out, first, count, bias=0.0):
    """The part of the layer experts ``first .. first + count - 1`` give."""
    gate, expert, _ = moe.route_top_k(x @ router + bias, K)
    held = slice(first, first + count)
    return moe.held_experts_moe(
        x, gate, expert, w_in[held], w_out[held], first, E
    )


def _uncut(x, router, w_in, w_out, bias=0.0, only=None):
    """The whole layer, every expert on every token, as the published
    equations state it (``only``: the experts counted)."""
    probs = jax.nn.softmax(x @ router + bias, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)
    gate = top_p / top_p.sum(-1, keepdims=True)
    weight = jnp.sum(gate[..., None] * (top_e[..., None] == jnp.arange(E)), 1)
    if only is not None:
        weight = weight * ((jnp.arange(E) >= only[0]) & (jnp.arange(E) < only[1]))
    g, u = jnp.split(jnp.einsum("td,edf->tef", x, w_in), 2, axis=-1)
    each = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, w_out)
    return jnp.einsum("ted,te->td", each, weight)


def _close(a, b, tol=2e-5):
    for u, v in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        scale = float(jnp.abs(v).max()) + 1e-30
        assert float(jnp.abs(u - v).max()) / scale < tol


def test_the_four_shares_add_up_to_the_uncut_layer():
    args = _weights()
    with jax.default_matmul_precision("highest"):
        shares = [_share(*args, first, 4) for first in (0, 4, 8, 12)]
        whole = _uncut(*args)
    _close(sum(shares), whole)
    # No share is the whole, and each is its own experts' part.
    assert float(jnp.abs(shares[0] - whole).max()) > 1e-2
    with jax.default_matmul_precision("highest"):
        _close(shares[2], _uncut(*args, only=(8, 12)))


@pytest.mark.parametrize(
    "bias_on, zero",
    [(range(4, 8), False), (range(5, 9), False), (range(8, 16), True)],
    ids=["all_choices_held", "three_of_four_held", "none_held"],
)
def test_dropless_under_forced_imbalance(bias_on, zero):
    """Every token forced onto the SAME experts: all its choices held
    (four rows a token, the row buffer's worst case, on four experts),
    one expert's worth outside, or none held — then exactly zero."""
    args = _weights(1)
    bias = jnp.zeros(E).at[jnp.asarray(list(bias_on))].set(50.0)
    with jax.default_matmul_precision("highest"):
        out = _share(*args, 4, 4, bias=bias)
        want = _uncut(*args, bias=bias, only=(4, 8))
    if zero:
        assert float(jnp.abs(out).max()) == 0.0
    else:
        _close(out, want)
    # One held expert takes every token.
    one = jnp.zeros(E).at[6].set(80.0)
    with jax.default_matmul_precision("highest"):
        _close(_share(*args, 4, 4, bias=one), _uncut(*args, bias=one, only=(4, 8)))


def _loss(x, router, w_in, w_out, bias=0.0):
    return jnp.sum(_share(x, router, w_in, w_out, 4, 8, bias=bias) ** 2)


@pytest.mark.parametrize("held_bias", [0.0, 50.0, -50.0], ids=["balanced", "all", "none"])
def test_buffer_past_its_head_is_entered_when_it_is_live(monkeypatch, held_bias):
    """The layer always works on the head of the row buffer (one and a
    half times a balanced load: three quarters of the slots with half
    the experts held) and enters the rest only when a live row lies
    there: with every choice of every token held it does, with none held
    (or few) it does not — value and gradients equal the uncut layer's
    either way, under vmap (2 x 256 slots, a head of 384)."""
    monkeypatch.setattr(moe, "_TILE_ROWS", 8)
    assert moe._head_rows(2 * T * K, (8, E)) == 384
    args = _weights(5, silos=2)
    bias = jnp.where((jnp.arange(E) >= 4) & (jnp.arange(E) < 12), held_bias, 0.0)
    gate, expert, _ = moe.route_top_k(args[0][0] @ args[1][0] + bias, K)
    live = int(((expert >= 4) & (expert < 12)).sum())
    assert {0.0: 64 < live < 192, 50.0: live == 256, -50.0: live == 0}[held_bias]
    grads = (0, 1, 2, 3)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.vmap(
            jax.value_and_grad(lambda *a: _loss(*a, bias=bias), argnums=grads)
        ))(*args)
        want = jax.vmap(jax.value_and_grad(
            lambda *a: jnp.sum(_uncut(*a, bias=bias, only=(4, 12)) ** 2),
            argnums=grads,
        ))(*args)
    if held_bias < 0:
        assert all(float(jnp.abs(g).max()) == 0.0 for g in jax.tree_util.tree_leaves(got))
    else:
        _close(got, want, 1e-4)


def test_vmap_over_two_silos_equals_two_separate_calls():
    args = _weights(2, silos=2)
    grad = jax.value_and_grad(_loss, argnums=(0, 1, 2, 3))
    with jax.default_matmul_precision("highest"):
        batched = jax.jit(jax.vmap(grad))(*args)
        apart = [jax.jit(grad)(*(a[s] for a in args)) for s in range(2)]
        dense = jax.vmap(jax.value_and_grad(
            lambda *a: jnp.sum(_uncut(*a, only=(4, 12)) ** 2), argnums=(0, 1, 2, 3)
        ))(*args)
    stacked = jax.tree_util.tree_map(lambda a, b: jnp.stack([a, b]), *apart)
    _close(batched, stacked, 1e-5)
    _close(batched, dense, 1e-4)  # and both are the published layer's
    # Experts that are not held get no gradient; the router does.
    assert float(jnp.abs(batched[1][2][:, :4]).max()) == 0.0
    assert float(jnp.abs(batched[1][1]).max()) > 0.0


def test_pallas_form_equals_the_xla_form(monkeypatch):
    """The TPU branch (the Pallas grouped-matmul kernels, here in the
    emulator) against the XLA branch, value and gradients, under vmap:
    2 silos x 64 tokens x 4 choices = 512 rows, four row tiles."""
    args = _weights(3, silos=2)
    grad = jax.vmap(jax.value_and_grad(_loss, argnums=(0, 1, 2, 3)))
    with jax.default_matmul_precision("highest"):
        xla = jax.jit(grad)(*args)
        monkeypatch.setattr(compat, "on_tpu", lambda: True)
        monkeypatch.setattr(compat, "pallas_interpret", lambda _: True)
        monkeypatch.setattr(moe, "_TILE_ROWS", 128)
        assert moe._pallas(2 * T * K)
        pallas = jax.jit(lambda *a: grad(*a))(*args)
    _close(pallas, xla, 1e-5)


#: The way back to tokens as the Pallas kernel against the gather, a
#: case: (tokens a silo, choices a token, first held expert, held
#: experts, ``_TILE_ROWS``, ``TOKEN_TILE``, the buffer's parts, bias).
_HELD = (jnp.arange(E) >= 4) & (jnp.arange(E) < 12)
_RETURN_CASES = {
    # 8 tiles of 16 tokens over 16 groups: a run is ~4 rows of a block.
    "balanced": (64, K, 4, 8, 128, 16, 1, 0.0),
    # Every token on expert 6: a tile's run there is 256 rows, two or
    # three blocks long; the buffer has a rest, not entered.
    "one_expert": (256, K, 4, 8, 128, 256, 2, jnp.zeros(E).at[6].set(80.0)),
    # Expert 5 is chosen by no token (an empty group in every tile), and
    # the tokens of each silo's second tile choose no held expert at all.
    "empty_group_and_tile": (
        64, K, 4, 8, 128, 16, 1,
        jnp.zeros(E).at[5].set(-80.0)
        + jnp.where(
            ((jnp.arange(64) // 16 == 1)[:, None]) & _HELD, -80.0, 0.0
        ),
    ),
    # Every choice of every token held: 512 live rows, a head of 384.
    "past_the_head": (64, K, 4, 8, 8, 16, 2, jnp.where(_HELD, 50.0, 0.0)),
    # ZAYA's share: one expert a token, half the experts held.
    "top1_half_held": (256, 1, 0, 8, 128, 32, 1, 0.0),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("case", list(_RETURN_CASES))
def test_return_kernel_equals_the_gather(monkeypatch, case, dtype):
    """``_rows_to_tokens`` as the Pallas kernel (``moe_kernel``, here in
    the emulator) against the gather it replaces on a TPU: value and all
    four gradients of the layer under ``vmap`` over 2 silos, everything
    else the same (the grouped products are the Pallas ones on both
    sides). float32 rows: the 0 / 1 product runs at the highest
    precision, so both forms make the same float32 sum in another order
    — 1e-6. bf16 rows: a product by 0 or 1 is exact and the float32 sums
    differ by their order alone, but the layer then rounds that sum to
    bf16, where a difference in the last float32 bit can flip a rounding:
    one bf16 step is 2^-8 of an element, so 2^-7 of the largest (nearly
    every element is equal bit for bit)."""
    from tpfl.parallel import moe_kernel

    tokens, k, first, count, tile_rows, token_tile, n_parts, bias = _RETURN_CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    d, f = 128, 16
    args = (
        jax.random.normal(ks[0], (2, tokens, d)).astype(dtype),
        jax.random.normal(ks[1], (2, d, E)),
        jax.random.normal(ks[2], (2, E, d, 2 * f)) / np.sqrt(d),
        jax.random.normal(ks[3], (2, E, f, d)) / np.sqrt(f),
    )

    def loss(x, router, w_in, w_out):
        logits = x.astype(jnp.float32) @ router + bias
        gate, expert, _ = (
            moe.route_by_probability(logits, 1) if k == 1
            else moe.route_top_k(logits, k)
        )
        held = slice(first, first + count)
        out = moe.held_experts_moe(
            x, gate, expert, w_in[held], w_out[held], first, E
        )
        return jnp.sum(out.astype(jnp.float32) ** 2)

    monkeypatch.setattr(compat, "on_tpu", lambda: True)
    monkeypatch.setattr(compat, "pallas_interpret", lambda _: True)
    monkeypatch.setattr(moe, "_TILE_ROWS", tile_rows)
    monkeypatch.setattr(moe_kernel, "TOKEN_TILE", token_tile)
    monkeypatch.setattr(moe, "_RUN_SLOTS", 1)  # toy tiles: runs of a few slots
    grad = jax.vmap(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)))

    def kernels(fn):
        return sum(
            e.primitive.name == "pallas_call"
            and e.params["name"] == "moe_rows_to_tokens"
            for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
        )

    with jax.default_matmul_precision("highest"):
        # Forward and backward, each part of the buffer.
        assert kernels(lambda *a: grad(*a)) == 2 * n_parts
        through_kernel = jax.jit(lambda *a: grad(*a))(*args)
        monkeypatch.setattr(moe_kernel, "tiles", lambda *a: 0)
        assert kernels(lambda *a: grad(*a)) == 0
        through_gather = jax.jit(lambda *a: grad(*a))(*args)
    assert float(jnp.abs(through_gather[1][0]).max()) > 0.0
    _close(
        jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), through_kernel),
        jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), through_gather),
        2.0 ** -7 if dtype == jnp.bfloat16 else 1e-6,
    )


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_return_kernel_reads_no_value_outside_its_runs(monkeypatch, dtype):
    """Past the groups a row buffer holds whatever its kernels left
    there (here NaN), and a staged block holds other tiles' and other
    groups' rows beside the run: the kernel zeroes them by ``where``
    before its 0 / 1 product — never ``0 x NaN`` — and counts no row
    twice. Against the gather on the same buffer: bf16 rows, products
    by 0 and 1 exact, the float32 sums of at most ``K`` rows differ by
    their order alone (1e-6 of the largest); float32 rows the same at
    the highest precision."""
    from tpfl.parallel import moe_kernel

    monkeypatch.setattr(compat, "on_tpu", lambda: True)
    monkeypatch.setattr(compat, "pallas_interpret", lambda _: True)
    monkeypatch.setattr(moe_kernel, "TOKEN_TILE", 32)
    monkeypatch.setattr(moe, "_RUN_SLOTS", 1)
    tokens, d, groups = 256, 128, 8
    k_route, k_rows = jax.random.split(jax.random.PRNGKey(12))
    _, expert = jax.lax.top_k(jax.random.normal(k_route, (tokens, E)), K)
    key = jnp.where(expert < groups, expert, groups).astype(jnp.int32)
    order, pos, sizes = moe._plan(key, groups)
    live = int(sizes.sum())
    _, parts = moe._parts(
        order, pos.reshape(key.shape), sizes, key < groups, (groups, E)
    )
    assert len(parts) == 1 and 256 < live < tokens * K - 128
    rows = jnp.where(
        jnp.arange(tokens * K)[:, None] < live,
        jax.random.normal(k_rows, (tokens * K, d)), jnp.nan,
    ).astype(dtype)
    x = jax.ShapeDtypeStruct((tokens, d), dtype)
    (by_kernel,) = moe._with_runs(parts, x, key, (groups, E))
    assert by_kernel[-1] is not None
    got = moe._rows_to_tokens(rows, by_kernel, K)
    want = moe._rows_to_tokens(rows, (*parts[0], None), K)
    assert got.dtype == want.dtype == jnp.float32
    assert bool(jnp.isfinite(got).all())
    _close(got, want, 1e-6)
    # Off a TPU, and for rows the kernel does not take (no whole lane
    # tile; a dtype the MXU would round), the gather stays.
    assert moe_kernel.tiles((1024, 96), dtype, tokens, groups) == 0
    assert moe_kernel.tiles((1000, d), dtype, tokens, groups) == 0
    assert moe_kernel.tiles((1024, d), jnp.float16, tokens, groups) == 0
    monkeypatch.setattr(compat, "on_tpu", lambda: False)
    assert moe._with_runs(parts, x, key, (groups, E))[0][-1] is None


@pytest.mark.parametrize(
    "k, share, d, by_kernel",
    [(8, (16, 64), 2304, True), (1, (8, 16), 2048, False)],
    ids=["mellum2", "zaya1"],
)
def test_way_back_follows_the_slots_a_run(monkeypatch, k, share, d, by_kernel):
    """Which form the way back takes on a TPU is read off shapes: the
    slots the gather fetches for each run the kernel copies, ``k`` x the
    token tile / the experts held. At the cells' shapes (2 silos x 16384
    tokens, bf16): Mellum 2's 8 choices over 16 held — 256 slots a run —
    take the kernel, head and rest; ZAYA1's one choice over 8 held — 64
    — keeps the gather (measured both ways on the v5e: ``_RUN_SLOTS``)."""
    monkeypatch.setattr(compat, "on_tpu", lambda: True)
    tokens, groups = 2 * 16384, 2 * share[0]
    x = jax.ShapeDtypeStruct((tokens, d), jnp.bfloat16)
    forms = []

    def plan(key):
        order, pos, sizes = moe._plan(key, groups)
        _, parts = moe._parts(
            order, pos.reshape(key.shape), sizes, key < groups, share
        )
        forms.extend(
            part[-1] is not None
            for part in moe._with_runs(parts, x, key, share)
        )
        return sizes

    jax.eval_shape(plan, jax.ShapeDtypeStruct((tokens, k), jnp.int32))
    assert forms == [by_kernel, by_kernel]


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def test_no_product_over_all_held_experts_is_formed():
    """The layer's products are grouped ones over the row buffer — two
    forward (gate and up side by side as one, down), five backward (the
    first made again, two input and two weight gradients); no array carries a token
    axis AND a held-expert axis, as a dense evaluation of every held
    expert on every token would."""
    args = _weights(4)
    jaxpr = jax.make_jaxpr(jax.grad(_loss, argnums=(0, 2, 3)))(*args).jaxpr
    eqns = list(_eqns(jaxpr))
    grouped = [e for e in eqns if e.primitive.name == "ragged_dot_general"]
    assert len(grouped) == 2 + 5
    rows = {tuple(v.aval.shape) for e in grouped for v in e.invars[:1]}
    assert {shape[0] for shape in rows} == {T * K}
    for eqn in eqns:
        for var in eqn.outvars:
            shape = tuple(getattr(var.aval, "shape", ()))
            assert not (T in shape and 8 in shape and len(shape) >= 3), eqn
    # Rows move by gather both ways: no scatter-add of hidden-width rows
    # (top_k's own gradient scatters [T, E] probabilities: not rows).
    assert not [
        e for e in eqns if e.primitive.name == "scatter-add"
        and e.outvars[0].aval.shape[-1] in (D, F, 2 * F)
    ]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_router_is_float32_whatever_the_compute_dtype(dtype):
    gate, expert, load = moe.route_top_k(
        jax.random.normal(jax.random.PRNGKey(0), (T, E)).astype(dtype), K
    )
    assert gate.dtype == load.dtype == jnp.float32
    assert float(jnp.abs(gate.sum(-1) - 1).max()) < 1e-6
    assert float(load.sum()) == pytest.approx(1.0) and load.shape == (E,)
    # In the zoo model the router's product has float32 operands at the
    # highest precision, under a bf16 compute dtype too.
    from tpfl.models import MellumLM

    model = MellumLM(
        vocab=32, dim=16, heads=2, kv_heads=1, head_dim=8, n_layers=1,
        period=1, n_experts=E, top_k=K, expert_dim=8, held_experts=4,
        compute_dtype=dtype,
    )
    tokens = jnp.zeros((1, 8), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    jaxpr = jax.make_jaxpr(lambda v: model.apply(v, tokens))(variables).jaxpr
    routers = [
        e for e in _eqns(jaxpr) if e.primitive.name == "dot_general"
        and tuple(e.outvars[0].aval.shape) == (8, E)
    ]
    assert len(routers) == 1
    assert {v.aval.dtype for v in routers[0].invars} == {jnp.dtype("float32")}
    assert "HIGHEST" in str(routers[0].params["precision"])


# --- one expert a token, half the experts held (ZAYA1's layer) ----------------


def _share_top1(x, router, w_in, w_out, first, count, bias=0.0):
    """Experts ``first .. first + count - 1``'s part of a top-1 layer
    whose gate is the chosen expert's probability."""
    gate, expert, _ = moe.route_by_probability(x @ router, 1, bias)
    held = slice(first, first + count)
    return moe.held_experts_moe(
        x, gate, expert, w_in[held], w_out[held], first, E
    )


def _uncut_top1(x, router, w_in, w_out, bias=0.0):
    """The whole top-1 layer as the published equations state it: the
    most probable expert (``bias`` moves the choice only) on each token,
    weighted by its probability under the softmax over all experts."""
    probs = jax.nn.softmax(x @ router, axis=-1)
    chosen = jnp.argmax(probs + bias, axis=-1)[:, None] == jnp.arange(E)
    g, u = jnp.split(jnp.einsum("td,edf->tef", x, w_in), 2, axis=-1)
    each = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, w_out)
    return jnp.einsum("ted,te->td", each, jnp.where(chosen, probs, 0.0))


def test_the_two_top1_shares_add_up_to_the_uncut_layer():
    args = _weights(6)
    with jax.default_matmul_precision("highest"):
        low, high = (_share_top1(*args, first, 8) for first in (0, 8))
        whole = _uncut_top1(*args)
    _close(low + high, whole)
    # A token's one expert is on one chip: its row of the other is zero.
    on_low = np.asarray(jnp.abs(low).max(-1) > 0)
    on_high = np.asarray(jnp.abs(high).max(-1) > 0)
    assert (on_low ^ on_high).all() and 8 < on_low.sum() < T - 8


def test_top1_with_every_token_on_one_held_expert():
    """A selection bias sends every token to expert 5: one group holds
    the whole buffer, the gate stays the probability the bias does not
    see, and the other chip's share is exactly zero."""
    args = _weights(7)
    bias = jnp.zeros(E).at[5].set(10.0)
    with jax.default_matmul_precision("highest"):
        out = _share_top1(*args, 0, 8, bias=bias)
        want = _uncut_top1(*args, bias=bias)
        other = _share_top1(*args, 8, 8, bias=bias)
    _close(out, want)
    assert float(jnp.abs(other).max()) == 0.0
    gate, expert, load = moe.route_by_probability(args[0] @ args[1], 1, bias)
    assert (np.asarray(expert) == 5).all() and float(load[5]) == 1.0
    np.testing.assert_allclose(
        gate[:, 0], jax.nn.softmax(args[0] @ args[1], axis=-1)[:, 5], rtol=1e-6
    )


def test_top1_gate_is_the_probability_and_reaches_the_router():
    """At k = 1 a gate normalised over the chosen is 1 for every token
    and its gradient to the router is zero; the probability is neither."""
    x, router, w_in, w_out = _weights(8)
    logits = x @ router
    gate, expert, load = moe.route_by_probability(logits, 1)
    probs = jax.nn.softmax(logits, axis=-1)
    np.testing.assert_allclose(gate[:, 0], probs.max(-1), rtol=1e-6)
    assert (np.asarray(expert[:, 0]) == np.asarray(probs.argmax(-1))).all()
    assert float(gate.max()) < 1.0 and float(load.sum()) == pytest.approx(1.0)
    normalised, same_expert, _ = moe.route_top_k(logits, 1)
    assert (np.asarray(normalised) == 1.0).all()
    assert (np.asarray(same_expert) == np.asarray(expert)).all()

    def loss(router, route):
        gate, expert, _ = route(x @ router, 1)
        out = moe.held_experts_moe(x, gate, expert, w_in[:8], w_out[:8], 0, E)
        return jnp.sum(out ** 2)

    with jax.default_matmul_precision("highest"):
        grad = jax.grad(loss)(router, moe.route_by_probability)
        cut_off = jax.grad(loss)(router, moe.route_top_k)
        # Experts 8..15 zeroed: the uncut layer is then the held share.
        want = jax.grad(
            lambda r: jnp.sum(_uncut_top1(x, r, w_in.at[8:].set(0.0), w_out) ** 2)
        )(router)
    _close(grad, want, 1e-4)
    # p / p has a gradient of rounding alone.
    assert float(jnp.abs(cut_off).max()) < 1e-4 * float(jnp.abs(grad).max())


def test_head_follows_the_held_share():
    """The row buffer's head is one and a half balanced loads: three
    eighths of the slots with a quarter of the experts held (Mellum 2, as
    before this was an argument), three quarters with half of them held
    (ZAYA1), everything where all are."""
    slots = 2 * 16384 * 8
    assert moe._head_rows(slots, (16, 64)) == 3 * slots // 8 == 98304
    slots = 2 * 16384
    assert moe._head_rows(slots, (8, 16)) == 3 * slots // 4 == 24576
    assert moe._head_rows(slots, (16, 16)) == slots
    assert moe._head_rows(slots + 512, (4, 16)) == 12800  # whole row tiles
    assert moe._head_rows(4096, (1, 64)) == 4096  # a small buffer is all head


def test_balanced_half_held_step_stays_in_the_head(monkeypatch):
    """Balanced top-1 routing with 8 of 16 experts held fills HALF the
    slots: inside a head of three quarters (the rest is not entered),
    past one of three eighths — the size that was Mellum's."""
    monkeypatch.setattr(moe, "_TILE_ROWS", 8)
    tokens = 512
    expert = jnp.arange(tokens, dtype=jnp.int32).reshape(tokens, 1) % E
    key = jnp.where(expert < 8, expert, 8)
    order, pos, sizes = moe._plan(key, 8)
    assert int(sizes.sum()) == tokens // 2
    held = key < 8
    overflows, parts = moe._parts(order, pos.reshape(held.shape), sizes, held, (8, E))
    assert not bool(overflows)
    assert parts[0][0].shape == (3 * tokens // 4,) and len(parts) == 2
    past_mellums, _ = moe._parts(
        order, pos.reshape(held.shape), sizes, held, (4, E)
    )
    assert bool(past_mellums)
    # Two thirds more than balanced still fits; every token held does not.
    crowded = jnp.where(jnp.arange(tokens)[:, None] % 4 < 3, expert % 8, 8)
    order, pos, sizes = moe._plan(crowded, 8)
    assert not bool(moe._parts(
        order, pos.reshape(held.shape), sizes, crowded < 8, (8, E)
    )[0])
    order, pos, sizes = moe._plan(expert % 8, 8)
    assert bool(moe._parts(
        order, pos.reshape(held.shape), sizes, expert % 8 < 8, (8, E)
    )[0])
