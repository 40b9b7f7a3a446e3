"""What PR 27 added beneath ``tpfl.models.SambaYLM``: the selective scan
against the token-by-token recurrence, grouped / wide-value / banded
``blockwise_attention`` against the S x S form, the tied bias-free
``head_cross_entropy`` against logits + ``cross_entropy_loss``, the
published layer pattern, and the model through ``create_model``. (The
model against its plain reference, and one engine round against
``reference_round``: ``tests/benchmark/test_benchmark_phi4flash.py``.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from tpfl.learning.jax_learner import cross_entropy_loss
from tpfl.models import SambaYLM, create_model
from tpfl.models.head_loss import head_cross_entropy
from tpfl.models.sambay import layer_kind
from tpfl.parallel.ring_attention import blockwise_attention
from tpfl.parallel.selective_scan import selective_scan


def _rel(a, b):
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


# --- selective scan ----------------------------------------------------------


def _token_by_token(c, delta, a, bmat, cmat, dskip):
    def step(h, xs):
        c_t, d_t, b_t, cm_t = xs
        h = jnp.exp(d_t[:, None] * a) * h + (d_t * c_t)[:, None] * b_t[None, :]
        return h, h @ cm_t + dskip * c_t

    return lax.scan(step, jnp.zeros(a.shape), (c, delta, bmat, cmat))[1]


def _scan_inputs(batch, s, d, n):
    k = jax.random.split(jax.random.PRNGKey(0), 7)
    return (
        jax.random.normal(k[0], (*batch, s, d)),
        jax.nn.softplus(jax.random.normal(k[1], (*batch, s, d))),
        -jnp.exp(jax.random.normal(k[2], (d, n))),
        jax.random.normal(k[3], (*batch, s, n)),
        jax.random.normal(k[4], (*batch, s, n)),
        jax.random.normal(k[5], (d,)),
    ), jax.random.normal(k[6], (*batch, s, d))


@pytest.mark.parametrize(
    "impl, batch, s, chunk",
    [
        ("xla", (2,), 37, 8),   # chunk does not divide S: 5 chunks, 3 pad tokens
        ("xla", (), 37, 64),    # one chunk, shorter than the default
        ("xla", (2, 2), 16, 8),  # two batch axes, two whole chunks
        # The Pallas kernels in the emulator: S padded to one 128-token
        # grid step, the 6 channels to one 1024-channel block ...
        ("kernel", (2,), 37, None),
        # ... and two grid steps along the sequence, state carried.
        ("kernel", (), 150, None),
    ],
)
def test_selective_scan_values_and_all_six_gradients(impl, batch, s, chunk):
    args, w = _scan_inputs(batch, s, 6, 4)
    ref = _token_by_token
    for _ in batch:
        ref = jax.vmap(ref, in_axes=(0, 0, None, 0, 0, None))
    sizes = {} if impl == "kernel" else {"chunk": chunk}
    mine = lambda *x: selective_scan(*x, impl=impl, **sizes)  # noqa: E731
    assert _rel(jax.jit(mine)(*args), ref(*args)) < 1e-5
    grads = [
        jax.jit(jax.grad(
            lambda *x: jnp.sum(f(*x) * w), argnums=tuple(range(6))
        ))(*args)
        for f in (mine, ref)
    ]
    for name, g, g_ref in zip(("c", "delta", "A", "B", "C", "D"), *grads):
        assert _rel(g, g_ref) < 1e-5, name


def test_scan_kernels_carry_state_across_channel_blocks_and_chunks():
    """The kernels called as the scan calls them, at a block size of their
    own: 2 channel blocks x 3 chunks of 16 tokens, against the XLA form."""
    from tpfl.parallel import scan_kernel

    (c, delta, a, bmat, cmat, dskip), w = _scan_inputs((), 48, 2048, 4)
    y, starts = scan_kernel.scan_forward(c, delta, a, bmat, cmat, dskip, 16, True)
    want, vjp = jax.vjp(
        lambda *x: selective_scan(*x, impl="xla"), c, delta, a, bmat, cmat, dskip
    )
    assert _rel(y, want) < 1e-5 and starts.shape == (3, 4, 16, 128)
    grads = scan_kernel.scan_backward(
        c, delta, a, bmat, cmat, dskip, starts, w, 16, True
    )
    for name, g, g_ref in zip(("c", "delta", "A", "B", "C", "D"), grads, vjp(w)):
        assert _rel(g, g_ref) < 1e-5, name
    with pytest.raises(ValueError, match="multiple"):
        scan_kernel.scan_forward(c[:40], delta[:40], a, bmat[:40], cmat[:40], dskip, 16, True)


def test_selective_scan_under_the_engine_s_vmap_and_in_bfloat16():
    """Per-silo parameters (A and D batched too), and a bf16 ``c`` keeps
    its dtype while the state stays float32."""
    args, _ = _scan_inputs((3,), 20, 6, 4)
    a = jnp.stack([args[2], 2 * args[2], 3 * args[2]])
    d = jnp.stack([args[5]] * 3)
    vmapped = jax.vmap(lambda c, dl, a_, b, cm, d_: selective_scan(
        c, dl, a_, b, cm, d_, chunk=8
    ))(args[0], args[1], a, args[3], args[4], d)
    in_kernels = jax.vmap(lambda *x: selective_scan(*x, impl="kernel"))(
        args[0], args[1], a, args[3], args[4], d
    )
    assert _rel(in_kernels, vmapped) < 1e-5
    for i in range(3):
        want = _token_by_token(args[0][i], args[1][i], a[i], args[3][i], args[4][i], d[i])
        assert _rel(vmapped[i], want) < 1e-5
    low = selective_scan(args[0].astype(jnp.bfloat16), *args[1:])
    assert low.dtype == jnp.bfloat16
    assert _rel(low.astype(jnp.float32), jax.vmap(
        _token_by_token, in_axes=(0, 0, None, 0, 0, None)
    )(*args)) < 2e-2


# --- attention ---------------------------------------------------------------


def _full_attention(q, k, v, window, causal=True, precision=None):
    """S x S scores; query head h reads key head h // groups."""
    groups = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, groups, axis=2) for t in (k, v))
    s = q.shape[1]
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, precision=precision
    ) / jnp.sqrt(q.shape[-1])
    pos = jnp.arange(s)
    mask = (pos[:, None] >= pos[None, :]) | (not causal)
    if window is not None:
        mask &= pos[:, None] - pos[None, :] < window
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=precision)


@pytest.mark.parametrize(
    "hq, hkv, d, dv, window, s, block",
    [
        (4, 4, 8, 8, None, 40, 16),   # the equal-head call, padded tail
        (8, 4, 8, 16, None, 40, 16),  # grouped heads, a wider value
        (6, 2, 8, 4, 12, 48, 8),      # a band that skips blocks behind it
        (4, 2, 8, 16, 8, 40, 16),     # band narrower than a block, padded
        (4, 4, 8, 8, 1, 32, 8),       # every token sees itself alone
    ],
)
def test_blockwise_attention_groups_value_width_and_band(hq, hkv, d, dv, window, s, block):
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(k[0], (2, s, hq, d))
    kk = jax.random.normal(k[1], (2, s, hkv, d))
    v = jax.random.normal(k[2], (2, s, hkv, dv))
    w = jax.random.normal(k[3], (2, s, hq, dv))
    mine = lambda *x: blockwise_attention(  # noqa: E731
        *x, causal=True, block_size=block, window=window
    )
    ref = lambda *x: _full_attention(*x, window)  # noqa: E731
    out, want = jax.jit(mine)(q, kk, v), ref(q, kk, v)
    assert out.shape == (2, s, hq, dv) and _rel(out, want) < 1e-5
    grads = [
        jax.jit(jax.grad(lambda *x: jnp.sum(f(*x) * w), argnums=(0, 1, 2)))(q, kk, v)
        for f in (mine, ref)
    ]
    for name, g, g_ref in zip("qkv", *grads):
        # (window 1: dq and dk are exactly zero, so an absolute floor.)
        np.testing.assert_allclose(g, g_ref, rtol=1e-4, atol=1e-5, err_msg=name)


# What the one-sweep backward (PR 28) has to keep: name -> (hq, hkv, d, dv,
# window, s, block, causal).
_BACKWARD_SHAPES = {
    "equal_heads": (4, 4, 8, 8, None, 64, 16, True),
    "grouped_heads": (8, 4, 8, 8, None, 64, 16, True),
    "wide_value": (4, 2, 8, 16, None, 64, 16, True),
    "band": (6, 2, 8, 4, 20, 64, 8, True),
    "ragged_padding": (4, 2, 8, 16, None, 40, 16, True),
    "non_causal_ragged": (4, 2, 8, 8, None, 40, 16, False),
}
# float32: today's tolerance (every step in float32; only the order of the
# sums differs from the S x S form). bfloat16: the tile matmuls read P and
# dS rounded to bf16's 8 bits beside bf16 q, k, v, dO, so the error is the
# inputs' own rounding, ~2^-8; ISSUE 28's desk check read 3.4e-3-4.6e-3 of
# the gradient's norm, and a dropped term or a wrong mask reads 1e-1 and up.
_BACKWARD_TOLERANCE = {jnp.float32: 1e-5, jnp.bfloat16: 1e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=lambda d: d.__name__)
@pytest.mark.parametrize("shape", list(_BACKWARD_SHAPES))
def test_blockwise_attention_gradients_match_the_full_form(shape, dtype):
    hq, hkv, d, dv, window, s, block, causal = _BACKWARD_SHAPES[shape]
    keys = jax.random.split(jax.random.PRNGKey(28), 4)
    q = jax.random.normal(keys[0], (2, s, hq, d)).astype(dtype)
    k = jax.random.normal(keys[1], (2, s, hkv, d)).astype(dtype)
    v = jax.random.normal(keys[2], (2, s, hkv, dv)).astype(dtype)
    w = jax.random.normal(keys[3], (2, s, hq, dv)).astype(dtype)

    def mine(q, k, v):
        out = blockwise_attention(
            q, k, v, causal=causal, block_size=block, window=window
        )
        assert out.dtype == dtype
        return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32))

    def ref(q, k, v):
        out = _full_attention(q, k, v, window, causal, precision="highest")
        return jnp.sum(out * w.astype(jnp.float32))

    got = jax.jit(jax.grad(mine, argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(ref, argnums=(0, 1, 2))(
        *(t.astype(jnp.float32) for t in (q, k, v))
    )
    for name, g, g_ref in zip("qkv", got, want):
        assert g.dtype == dtype and g.shape == g_ref.shape
        err = jnp.linalg.norm(g.astype(jnp.float32) - g_ref) / jnp.linalg.norm(g_ref)
        assert float(err) < _BACKWARD_TOLERANCE[dtype], (name, float(err))


def _dot_generals(jaxpr):
    """Every ``dot_general`` equation of a jaxpr, inner jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _dot_generals(inner)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=lambda d: d.__name__)
def test_blockwise_attention_backward_is_one_sweep_on_input_dtype_operands(dtype):
    """The gradient's jaxpr: two tile matmuls forward (scores, P V) and
    five backward (scores, dP, dV, dK, dQ) — a second sweep would show as
    seven. No operand is wider than the inputs: P, dS, q, k, v and dO
    enter as bf16 when the inputs are bf16. The two score matmuls round
    to the inputs' dtype (``ring_attention._scores`` says why); the other
    five keep their float32 accumulator."""
    hq, hkv, d, dv, s, block = 4, 2, 8, 16, 64, 16
    q = jnp.zeros((1, s, hq, d), dtype)
    k = jnp.zeros((1, s, hkv, d), dtype)
    v = jnp.zeros((1, s, hkv, dv), dtype)

    def loss(q, k, v):
        out = blockwise_attention(q, k, v, causal=True, block_size=block)
        return jnp.sum(out.astype(jnp.float32))

    dots = list(_dot_generals(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v).jaxpr
    ))
    assert len(dots) == 2 + 5
    tile = (hq // hkv * block, block)
    accumulators = []
    for eqn in dots:
        operands = [x.aval for x in eqn.invars]
        assert all(x.dtype == dtype for x in operands), operands
        # Each is a matmul of the block pair: a tile comes out of it or
        # goes into it.
        shapes = [x.shape[-2:] for x in operands] + [eqn.outvars[0].aval.shape[-2:]]
        assert tile in shapes or tile[::-1] in shapes, shapes
        accumulators.append(eqn.outvars[0].aval.dtype)
        if accumulators[-1] == jnp.float32:
            assert eqn.params["preferred_element_type"] in (None, jnp.float32)
    narrow = [a for a in accumulators if a != jnp.float32]
    assert len(narrow) == (2 if dtype == jnp.bfloat16 else 0), accumulators


def test_blockwise_attention_backward_steps_through_the_heads(monkeypatch):
    """A step of the backward takes the most key heads whose float32
    score tile is within ``_STEP_TILE_BYTES``; heads never mix, so the
    gradient does not depend on how many a step takes."""
    import importlib

    # (``tpfl.parallel`` re-exports a function under the module's name.)
    ring_attention = importlib.import_module("tpfl.parallel.ring_attention")
    heads = ring_attention._heads_per_step
    limit = ring_attention._STEP_TILE_BYTES
    tile = 4 * 2 * 32 * 16  # float32 [B = 2, one head, rows = 32, block = 16]
    assert tile * 4 <= limit and heads(2, 4, 32, 16) == 4  # all of them
    monkeypatch.setattr(ring_attention, "_STEP_TILE_BYTES", 2 * tile)
    assert heads(2, 4, 32, 16) == 2
    assert heads(2, 6, 32, 16) == 2 and heads(2, 7, 32, 16) == 1  # a divisor
    assert heads(2, 4, 64, 16) == 1 and heads(64, 4, 32, 16) == 1  # never none
    monkeypatch.setattr(ring_attention, "_STEP_TILE_BYTES", limit)

    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(keys[0], (2, 40, 8, 8))
    k = jax.random.normal(keys[1], (2, 40, 4, 8))
    v = jax.random.normal(keys[2], (2, 40, 4, 16))
    w = jax.random.normal(keys[3], (2, 40, 8, 16))

    def grads(q):
        return jax.grad(lambda *x: jnp.sum(blockwise_attention(
            *x, causal=True, block_size=16, window=20
        ) * w), argnums=(0, 1, 2))(q, k, v)

    whole = grads(q)
    for limit in (2 * tile, 1):  # two heads a step, one
        monkeypatch.setattr(ring_attention, "_STEP_TILE_BYTES", limit)
        # ... alone and under vmap, as the engine runs it (float32: only
        # the matmuls' own summation order may differ).
        for got in (grads(q), jax.tree.map(lambda x: x[0], jax.vmap(grads)(q[None]))):
            for name, g, g_whole in zip("qkv", got, whole):
                np.testing.assert_allclose(
                    g, g_whole, rtol=1e-5, atol=1e-6, err_msg=name
                )


def test_blockwise_attention_refuses_what_it_cannot_mean():
    x = jnp.zeros((1, 8, 4, 8))
    with pytest.raises(ValueError, match="multiple"):
        blockwise_attention(x, x[:, :, :3], x[:, :, :3])
    with pytest.raises(ValueError, match="causal"):
        blockwise_attention(x, x, x, causal=False, window=4)
    with pytest.raises(ValueError, match="impl"):
        selective_scan(x[0, :, 0], x[0, :, 0], x[0, 0], x[0, :, 0], x[0, :, 0], x[0, 0, 0], impl="fast")


# --- the tied, bias-free head ------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_tied_bias_free_head_cross_entropy(dtype):
    k = jax.random.split(jax.random.PRNGKey(5), 3)
    hidden = jax.random.normal(k[0], (2, 12, 16)).astype(dtype)
    embedding = jax.random.normal(k[1], (40, 16)) * 0.3
    targets = jax.random.randint(k[2], (2, 12), 0, 40)

    def two_steps(h, e):
        logits = jnp.dot(h, e.T.astype(dtype)).astype(jnp.float32)
        return cross_entropy_loss(logits, targets).mean()

    owned = lambda h, e: head_cross_entropy(h, e.T, None, targets)  # noqa: E731
    (l1, g1), (l2, g2) = (
        jax.value_and_grad(f, argnums=(0, 1))(hidden, embedding)
        for f in (owned, two_steps)
    )
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    assert abs(l1 - l2) <= tol * abs(l2)
    assert g1[1].shape == embedding.shape and g1[0].dtype == dtype
    for a, b in zip(g1, g2):
        assert _rel(a.astype(jnp.float32), b.astype(jnp.float32)) < 10 * tol
    # No bias, no bias matmul: the ones block never enters the program.
    text = jax.jit(jax.grad(owned, argnums=(0, 1))).lower(hidden, embedding).as_text()
    with_bias = jax.jit(jax.grad(
        lambda h, e, b: head_cross_entropy(h, e.T, b, targets), argnums=(0, 1, 2)
    )).lower(hidden, embedding, jnp.zeros(40)).as_text()
    assert text.count("dot_general") == 3
    assert with_bias.count("dot_general") == 4


# --- the pattern and the zoo -------------------------------------------------


def test_layer_kind_is_the_published_pattern():
    kinds = [layer_kind(l, 32) for l in range(32)]
    count = {k: kinds.count(k) for k in set(kinds)}
    assert count == {"mamba": 9, "window": 8, "full": 1, "gmu": 7, "cross": 7}
    assert kinds[16:20] == ["mamba", "full", "gmu", "cross"]
    assert kinds[14:16] == ["mamba", "window"] and kinds[30:] == ["gmu", "cross"]
    assert [layer_kind(l, 8) for l in range(8)] == [
        "mamba", "window", "mamba", "window", "mamba", "full", "gmu", "cross",
    ]
    for bad in ((8, 8), (-1, 8), (0, 6)):
        with pytest.raises(ValueError):
            layer_kind(*bad)


@pytest.mark.parametrize(
    "layers, kernels",
    [
        # The benchmark's cut in little: Mamba, full attention (lends K / V),
        # a GMU, cross-attention: both attention layers take the kernels ...
        ((4, 5, 6, 7), 2 * 2 + 2),
        # ... and so does a sliding-window layer: the band is in the kernels.
        ((1,), 2 + 1),
    ],
)
def test_sambay_attention_runs_the_kernels_on_the_tpu_branch(monkeypatch, layers, kernels):
    """``DiffAttention`` through ``blockwise_attention``'s TPU branch
    (Pallas's emulator here): grouped query rows, a value twice the
    keys' width, borrowed keys and values — the loss and every
    parameter's gradient agree with the XLA loop's, and the program
    holds a forward kernel twice a layer (each block is recomputed) and
    ONE backward kernel."""
    from tpfl.parallel import compat

    model = SambaYLM(window=4, layers=layers, compute_dtype=jnp.float32)
    # 128 tokens: one block that is a whole lane tile (a shorter sequence
    # is one block of its own length, which stays with the XLA loop).
    tokens = jax.random.randint(jax.random.PRNGKey(0), (1, 128), 0, 512)
    params = model.init(jax.random.PRNGKey(1), tokens)

    def loss_and_grads():
        loss = lambda p: model.apply(p, tokens, train=True, targets=tokens)  # noqa: E731
        text = str(jax.make_jaxpr(jax.grad(loss))(params))
        # (a fresh function each call: jit cannot hand back the other path)
        return (*jax.jit(jax.value_and_grad(loss))(params), text.count("pallas_call"))

    loop_loss, loop_grads, none = loss_and_grads()
    monkeypatch.setattr(compat, "on_tpu", lambda: True)
    monkeypatch.setattr(compat, "pallas_interpret", lambda interpret: True)
    loss, grads, calls = loss_and_grads()
    # (the selective scan's kernels are in the program too, on this branch)
    scans = 3 * sum(layer_kind(l, 8) == "mamba" for l in layers)
    assert none == 0 and calls - scans == kernels
    assert abs(loss - loop_loss) < 1e-5 * abs(loop_loss)
    # (the key bias moves every score of a row alike, so its gradient is
    # rounding noise around 0: compared on the scale of the weights')
    worst = jax.tree.map(
        lambda g, want: float(jnp.abs(g - want).max() / max(jnp.abs(want).max(), 1e-3)),
        grads, loop_grads,
    )
    assert max(jax.tree.leaves(worst)) < 2e-4, worst


def test_sambay_through_create_model_and_its_stage_rule():
    model = create_model("sambay_lm", (16,), window=4, compute_dtype=jnp.float32)
    assert sorted(model.get_parameters()) == (
        ["embed"] + [f"layer_{l}" for l in range(8)] + ["norm_out"]
    )
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 512)
    module, variables = model.module, {"params": model.get_parameters()}
    forward = jax.jit(module.apply)
    logits = forward(variables, tokens)
    assert logits.shape == (2, 16, 512) and logits.dtype == jnp.float32
    loss = jax.jit(
        lambda v, t: module.apply(v, t, train=True, targets=t)
    )(variables, tokens)
    want = cross_entropy_loss(logits, tokens).mean()
    assert abs(loss - want) < 1e-5 * want
    # Cross-attention has no key or value projection of its own, and only
    # the layers past the middle read what layers 4 and 5 lend.
    assert "k_proj" not in variables["params"]["layer_7"]["mixer"]
    assert "k_proj" in variables["params"]["layer_5"]["mixer"]
    # Causal: a later token cannot move an earlier one's logits.
    moved = forward(variables, tokens.at[:, 9].set(3))
    assert np.allclose(logits[:, :9], moved[:, :9], atol=1e-5)
    assert not np.allclose(logits[:, 9:], moved[:, 9:], atol=1e-5)
    with pytest.raises(ValueError, match="lends"):
        SambaYLM(layers=(5, 6, 7)).init(jax.random.PRNGKey(0), tokens)
