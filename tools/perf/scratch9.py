"""Scratch 9: decompose the vmapped bwd cost by grad subset.
dense-only -> +conv2 dW -> full (adds conv2-dx + conv1-dW)."""
import os
import time

import jax


import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

rng = np.random.default_rng(0)
PEAK = 197e12
N, BS = 100, 128
R = 20
DN = ("NHWC", "HWIO", "NHWC")


def rtt():
    @jax.jit
    def run(x):
        return lax.fori_loop(0, 100, lambda i, a: a + x * (1 + i), jnp.float32(0))

    float(run(jnp.float32(1)))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        float(run(jnp.float32(1)))
        best = min(best, time.perf_counter() - t0)
    return best


BASE = rtt()
print(f"RTT baseline: {BASE*1e3:.1f} ms", flush=True)


def conv_plain(x, w):
    return lax.conv_general_dilated(x, w, (1, 1), "SAME", dimension_numbers=DN)


def init_params():
    k = jax.random.PRNGKey(0)
    ks = jax.random.split(k, 6)
    p1 = {
        "w1": jax.random.normal(ks[0], (3, 3, 3, 32), jnp.bfloat16) * 0.1,
        "b1": jnp.zeros((32,), jnp.bfloat16),
        "w2": jax.random.normal(ks[1], (3, 3, 32, 64), jnp.bfloat16) * 0.05,
        "b2": jnp.zeros((64,), jnp.bfloat16),
        "wd": jax.random.normal(ks[2], (4096, 128), jnp.bfloat16) * 0.02,
        "bd": jnp.zeros((128,), jnp.bfloat16),
        "wo": jax.random.normal(ks[3], (128, 10), jnp.bfloat16) * 0.1,
        "bo": jnp.zeros((10,), jnp.bfloat16),
    }
    return jax.tree_util.tree_map(
        lambda q: jnp.broadcast_to(q[None], (N, *q.shape)) + 0, p1
    )


x_dev = jnp.asarray(rng.normal(size=(N, BS, 32, 32, 3)), jnp.bfloat16)
y_dev = jnp.asarray(rng.integers(0, 10, (N, BS)), jnp.int32)


def make_subset_step(grad_keys):
    conv = conv_plain
    pool = lambda y: lax.reduce_window(
        y, -jnp.inf, lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")

    def net(params, x):
        y = conv(x, params["w1"])
        y = pool(jax.nn.relu(y + params["b1"]))
        y = conv(y, params["w2"])
        y = pool(jax.nn.relu(y + params["b2"]))
        y = y.reshape(y.shape[0], -1)
        y = jax.nn.relu(y @ params["wd"] + params["bd"])
        return (y @ params["wo"] + params["bo"]).astype(jnp.float32)

    opt = optax.sgd(0.1, momentum=0.9)

    def one(pp, oo, xx, yy):
        live = {k: pp[k] for k in grad_keys}
        frozen = {k: jax.lax.stop_gradient(pp[k]) for k in pp if k not in grad_keys}

        def loss_of(q):
            logits = net({**frozen, **q}, xx)
            return optax.softmax_cross_entropy_with_integer_labels(logits, yy).mean()

        loss, grads = jax.value_and_grad(loss_of)(live)
        full_grads = {k: grads.get(k, jnp.zeros_like(pp[k])) for k in pp}
        up, oo = opt.update(full_grads, oo, pp)
        return optax.apply_updates(pp, up), oo

    def step(t, i):
        p, o = t
        return jax.vmap(one)(p, o, x_dev, y_dev)

    return step, opt


def measure(tag, grad_keys):
    step, opt = make_subset_step(grad_keys)
    params = init_params()
    opt_state = jax.vmap(opt.init)(params)

    @jax.jit
    def run(t):
        return lax.fori_loop(0, R, lambda i, t: step(t, i), t)

    out = run((params, opt_state))
    float(jnp.asarray(jax.tree_util.tree_leaves(out)[0]).ravel()[0])
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = run((params, opt_state))
        float(jnp.asarray(jax.tree_util.tree_leaves(out)[0]).ravel()[0])
        best = min(best, time.perf_counter() - t0)
    per = (best - BASE) / R
    print(f"{tag}: {per*1e3:.2f} ms", flush=True)


measure("dense-only grads ", ["wd", "bd", "wo", "bo"])
measure("+conv2 dW        ", ["w2", "b2", "wd", "bd", "wo", "bo"])
measure("+conv1 dW (no b1)", ["w1", "w2", "b2", "wd", "bd", "wo", "bo"])
measure("full grads       ", ["w1", "b1", "w2", "b2", "wd", "bd", "wo", "bo"])
