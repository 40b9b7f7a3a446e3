"""The LM head that owns its loss: the ``head_cross_entropy`` operation
against ``Dense`` + ``optax`` cross-entropy, and the engine seam that
reaches it (a window through the module's own loss against the same
window forced down the logits path).

Tolerances, and why:

- float32 compute: 1e-5 of each gradient's largest entry. Both paths
  do the same float32 arithmetic; they differ in ``exp(l - lse)``
  against ``exp(l - max) / sum`` (an ulp or two) and in summation order.
- bfloat16 compute: the loss and the input gradient repeat today's
  arithmetic rounding for rounding (the same bf16 logits, the same one
  cast of ``dlogits`` to bf16, a bf16 result of the input matmul), so
  they get the float32 tolerance. The kernel and bias gradients do
  NOT: autodiff of a bf16 ``Dense`` rounds the kernel gradient to bf16
  (2**-8 relative an entry) and accumulates the bias gradient in bf16;
  the operation keeps both in the matmul's float32 accumulators. So
  against today's path they sit within a few bf16 roundings (2e-2 of
  the largest entry), and against a float32 contraction of the same
  bf16 ``dlogits`` the operation is a hundred times closer (2e-4) —
  precision went up, not sideways.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpfl.learning.jax_learner import cross_entropy_loss
from tpfl.models import CNN, MLP, ResNet18, TransformerLM
from tpfl.models.head_loss import head_cross_entropy
from tpfl.parallel import FederationEngine, create_mesh

F32_TOL = 1e-5
BF16_ROUNDINGS_TOL = 2e-2
BF16_AGAINST_F32_CONTRACTION_TOL = 2e-4


def _dense_then_optax(hidden, kernel, bias, targets):
    """Today's path: ``nn.Dense(dtype=hidden.dtype)``, float32 logits,
    the canonical loss."""
    dtype = hidden.dtype
    logits = jnp.dot(hidden, kernel.astype(dtype)) + bias.astype(dtype)
    return optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), targets
    ).mean()


def _operands(vocab, dtype, nodes=0, seed=0):
    lead = (nodes,) if nodes else ()
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    hidden = jax.random.normal(k[0], (*lead, 2, 16, 32)).astype(dtype)
    kernel = jax.random.normal(k[1], (*lead, 32, vocab)) * 0.2
    bias = jax.random.normal(k[2], (*lead, vocab)) * 0.1
    targets = jax.random.randint(k[3], (*lead, 2, 16), 0, vocab)
    return hidden, kernel, bias, targets


def _rel(got, want):
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    return np.max(np.abs(got - want)) / np.max(np.abs(want))


@pytest.mark.parametrize("nodes", [0, 3], ids=["plain", "vmap"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("vocab", [257, 256])
def test_head_cross_entropy_matches_dense_then_optax(vocab, dtype, nodes):
    args = _operands(vocab, dtype, nodes)

    def both(fn):
        fn = jax.value_and_grad(fn, argnums=(0, 1, 2))
        return jax.jit(jax.vmap(fn) if nodes else fn)(*args)

    (loss, (d_hidden, d_kernel, d_bias)) = both(head_cross_entropy)
    (want, (w_hidden, w_kernel, w_bias)) = both(_dense_then_optax)
    assert loss.dtype == jnp.float32
    assert (d_hidden.dtype, d_kernel.dtype, d_bias.dtype) == (
        dtype, jnp.float32, jnp.float32
    )
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    assert _rel(d_hidden, w_hidden) <= F32_TOL
    weights_tol = F32_TOL if dtype == jnp.float32 else BF16_ROUNDINGS_TOL
    assert _rel(d_kernel, w_kernel) <= weights_tol
    assert _rel(d_bias, w_bias) <= weights_tol


@pytest.mark.parametrize("vocab", [257, 256])
def test_bf16_weight_gradients_are_the_float32_contraction(vocab):
    """Precision goes up: against a float32 contraction of the SAME
    bf16 ``dlogits`` the operation's kernel and bias gradients are
    exact to accumulation order, where today's path carries its bf16
    roundings."""
    hidden, kernel, bias, targets = _operands(vocab, jnp.bfloat16)
    logits = (
        jnp.dot(hidden, kernel.astype(jnp.bfloat16)) + bias.astype(jnp.bfloat16)
    ).astype(jnp.float32)
    dlogits = (
        (jax.nn.softmax(logits) - jax.nn.one_hot(targets, vocab)) / targets.size
    ).astype(jnp.bfloat16).astype(jnp.float32)
    want_kernel = jnp.einsum("bsd,bsv->dv", hidden.astype(jnp.float32), dlogits)
    want_bias = dlogits.sum((0, 1))

    def grads(fn):
        return jax.grad(fn, argnums=(1, 2))(hidden, kernel, bias, targets)

    d_kernel, d_bias = grads(head_cross_entropy)
    t_kernel, t_bias = grads(_dense_then_optax)
    assert _rel(d_kernel, want_kernel) <= BF16_AGAINST_F32_CONTRACTION_TOL
    assert _rel(d_bias, want_bias) <= BF16_AGAINST_F32_CONTRACTION_TOL
    assert _rel(t_kernel, want_kernel) > 4 * _rel(d_kernel, want_kernel)
    assert _rel(t_bias, want_bias) > 4 * _rel(d_bias, want_bias)


def test_targets_carry_no_gradient():
    args = _operands(257, jnp.float32)
    d_targets = jax.grad(head_cross_entropy, argnums=3, allow_int=True)(*args)
    assert d_targets.dtype == jax.dtypes.float0
    assert d_targets.shape == args[3].shape


# --- the engine seam ---------------------------------------------------------

VOCAB = 61  # not a multiple of anything the tiling likes


def _lm(dtype=jnp.float32):
    return TransformerLM(
        vocab=VOCAB, dim=32, heads=4, n_layers=2, max_len=64, compute_dtype=dtype
    )


def _logits_path_loss(logits, labels):
    """The canonical loss under another identity: the engine then sees
    a loss it cannot assume the head owns, and takes the logits path."""
    return cross_entropy_loss(logits, labels)


def _lm_window(mesh_axes, algorithm, loss_fn, dtype=jnp.float32):
    """Two rounds of an 8-node LM federation with a masked train set;
    (engine, losses, folded parameters)."""
    n = 8
    rng = np.random.default_rng(0)
    xs = rng.integers(0, VOCAB, (n, 2, 2, 16)).astype(np.int32)
    ys = rng.integers(0, VOCAB, (n, 2, 2, 16)).astype(np.int32)
    weights = np.asarray([1, 1, 0, 1, 0, 1, 1, 0], np.float32)
    mesh = create_mesh(mesh_axes) if mesh_axes else None
    eng = FederationEngine(
        _lm(dtype), n, mesh=mesh, seed=0, learning_rate=0.05,
        algorithm=algorithm, loss_fn=loss_fn,
    )
    params = eng.init_params((16,))
    dx, dy = eng.shard_data(xs, ys)
    kw = {}
    if algorithm == "scaffold":
        kw["scaffold_state"] = eng.init_scaffold_state(params)
    out = eng.run_rounds(params, dx, dy, weights=weights, n_rounds=2, **kw)
    return eng, np.asarray(out[-1]), jax.tree_util.tree_leaves(out[0])


@pytest.mark.parametrize("algorithm", ["fedavg", "fedprox", "scaffold"])
@pytest.mark.parametrize(
    "mesh_axes", [None, {"nodes": 8}, {"nodes": 4, "model": 2}],
    ids=["one_device", "nodes8", "nodes4_model2"],
)
def test_engine_window_through_the_head_matches_the_logits_path(
    mesh_axes, algorithm
):
    """Same window, the module's own loss against the logits path:
    float32 compute, so only summation order differs (a GSPMD matmul
    over the sharded vocabulary on the 2D mesh; 5e-5 covers it, the
    meshes' own parity tests allow 5e-4)."""
    eng, losses, params = _lm_window(mesh_axes, algorithm, cross_entropy_loss)
    ref, want_losses, want_params = _lm_window(
        mesh_axes, algorithm, _logits_path_loss
    )
    assert eng.head_owns_loss and not ref.head_owns_loss
    np.testing.assert_allclose(losses, want_losses, rtol=5e-5)
    for got, want in zip(params, want_params):
        np.testing.assert_allclose(got, want, atol=5e-5)


def test_engine_window_through_the_head_bf16_matches_the_logits_path():
    """bfloat16 compute: the head's kernel and bias updates shed their
    bf16 roundings (see the top of this file), and one local step at
    lr 0.05 turns a 2**-8 relative difference of a gradient into less
    than 1e-3 of a parameter."""
    _, losses, params = _lm_window(None, "fedavg", cross_entropy_loss, jnp.bfloat16)
    _, want_losses, want_params = _lm_window(
        None, "fedavg", _logits_path_loss, jnp.bfloat16
    )
    np.testing.assert_allclose(losses, want_losses, rtol=2e-3)
    for got, want in zip(params, want_params):
        np.testing.assert_allclose(got, want, atol=1e-3)


def test_program_name_records_the_head_owned_loss():
    """A module takes the path in every round or in none, so the record
    is the window program's observatory name: ``:hl``."""
    from tpfl.management import profiling
    from tpfl.settings import Settings

    Settings.PROFILING_ENABLED = True
    profiling.observatory.reset()
    try:
        _lm_window(None, "fedavg", cross_entropy_loss)
        _lm_window(None, "fedavg", _logits_path_loss)
        names = sorted(profiling.observatory.signature_counts())
    finally:
        Settings.PROFILING_ENABLED = False
        profiling.observatory.reset()
    plain, owned = [n for n in names if n.startswith("engine_round:plainx2")]
    assert ":hl:" in owned and owned.replace(":hl:", ":") == plain, names


def test_logits_keep_their_names_and_values():
    """``__call__`` without targets, evaluation and checkpoints are
    untouched: the same parameter names, and the loss the head computes
    is the canonical loss of the logits it returns."""
    lm = _lm()
    tokens = jnp.arange(32, dtype=jnp.int32).reshape(2, 16) % VOCAB
    variables = lm.init(jax.random.PRNGKey(0), tokens, train=False)
    with_targets = lm.init(jax.random.PRNGKey(0), tokens, train=False, targets=tokens)
    assert sorted(variables["params"]) == [
        "Dense_0", "Embed_0", "Embed_1", "LayerNorm_0",
        "TransformerBlock_0", "TransformerBlock_1",
    ]
    assert sorted(variables["params"]["Dense_0"]) == ["bias", "kernel"]
    for a, b in zip(*map(jax.tree_util.tree_leaves, (variables, with_targets))):
        np.testing.assert_array_equal(a, b)
    logits = lm.apply(variables, tokens)
    assert logits.shape == (2, 16, VOCAB) and logits.dtype == jnp.float32
    np.testing.assert_allclose(
        lm.apply(variables, tokens, targets=tokens),
        cross_entropy_loss(logits, tokens).mean(), rtol=1e-6,
    )


# --- the classifier path is the parent's, to the letter -----------------------

CLASSIFIERS = {
    "mlp": (lambda: MLP(hidden_sizes=(16,)), (28, 28)),
    "cnn": (lambda: CNN(out_channels=10), (32, 32, 3)),
    "resnet18": (lambda: ResNet18(out_channels=100), (32, 32, 3)),
}
#: sha256 of the lowered text of each module's two-round FedAvg window
#: (8 nodes x 2 batches x 4, one device, not donating) AT THE PARENT
#: COMMIT of the PR that gave the LM head its loss (PR 26), taken with
#: the jax named beside them. A change that means to alter the round
#: program of a module that offers no loss of its own re-takes them
#: (the assertion prints the new value); one that does not mean to has
#: just been caught.
PINNED_WITH_JAX = "0.9.0"
PARENT_WINDOW_DIGESTS = {
    "mlp": "adf3b77686ee3bb70fd37e8290be9bfe5e546f1a55490d3cee37d3affd4403eb",
    "cnn": "e316e714625743bf6f3177a41cda82eea0234d252d3486f235e17661cc39d97b",
    "resnet18": "b52db18eb0b6a11d3fd91a5bffc99868f430f6efb40cb802b0e0c11c7e04f02a",
}


def _window_text_digest(name):
    import hashlib

    build, shape = CLASSIFIERS[name]
    eng = FederationEngine(build(), 8, seed=0)
    assert not eng.head_owns_loss
    variables = jax.eval_shape(
        lambda: eng.module.init(
            jax.random.PRNGKey(0), jnp.zeros((1, *shape)), train=False
        )
    )

    def stacked(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((8, *x.shape), x.dtype), tree
        )

    aux = {k: v for k, v in variables.items() if k != "params"}
    vec = jax.ShapeDtypeStruct((8,), jnp.float32)
    fn = eng.program("aux" if aux else "plain", 1, 2, 1, donate=False)
    lowered = fn.lower(
        stacked(variables["params"]), {}, {}, stacked(aux),
        jax.ShapeDtypeStruct((8, 2, 4, *shape), jnp.float32),
        jax.ShapeDtypeStruct((8, 2, 4), jnp.int32), vec, vec,
    )
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()


@pytest.mark.parametrize("name", sorted(CLASSIFIERS))
def test_classifier_window_lowers_to_the_parents_text(name):
    """Separation, not adaptation: a module that offers no loss of its
    own goes through exactly the parent's ``logits -> loss_fn`` program."""
    if jax.__version__ != PINNED_WITH_JAX:
        pytest.skip(
            f"digests were taken with jax {PINNED_WITH_JAX}; lowered text "
            f"is not comparable across versions ({jax.__version__} here)"
        )
    assert _window_text_digest(name) == PARENT_WINDOW_DIGESTS[name]
