"""Scratch 12: custom VJP with fwd-style XLA bwd convs + shared-weight
parity check. 3 compiles max."""
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


@jax.custom_vjp
def conv_fb(x, w):
    return lax.conv_general_dilated(x, w, (1, 1), "SAME", dimension_numbers=DN)


def _fb_fwd(x, w):
    return conv_fb(x, w), (x, w)


def _fb_bwd(res, g):
    x, w = res
    g = g.astype(x.dtype)
    k = w.shape[0]
    r = k // 2
    # dx: plain SAME conv of g with the flipped, io-swapped kernel.
    w_flip = jnp.flip(w, (0, 1)).swapaxes(2, 3)  # [k,k,Cout,Cin]
    dx = lax.conv_general_dilated(
        g, w_flip, (1, 1), "SAME", dimension_numbers=DN
    )
    # dW: conv with Cin as batch, B as contraction feature, g as kernel.
    dw = lax.conv_general_dilated(
        x, g, (1, 1), [(r, r), (r, r)],
        dimension_numbers=("CHWN", "IHWO", "HWNC"),
    ).astype(w.dtype)
    return dx, dw


conv_fb.defvjp(_fb_fwd, _fb_bwd)

# correctness spot-check on-chip (f32)
xt = jnp.asarray(rng.normal(size=(2, 8, 8, 3)), jnp.float32)
wt = jnp.asarray(rng.normal(size=(3, 3, 3, 5)), jnp.float32)
ref = lambda x, w: lax.conv_general_dilated(x, w, (1, 1), "SAME", dimension_numbers=DN)
ga = jax.grad(lambda w: jnp.sum(conv_fb(xt, w) ** 2))(wt)
gb = jax.grad(lambda w: jnp.sum(ref(xt, w) ** 2))(wt)
gxa = jax.grad(lambda x: jnp.sum(conv_fb(x, wt) ** 2))(xt)
gxb = jax.grad(lambda x: jnp.sum(ref(x, wt) ** 2))(xt)
print("dW err:", float(jnp.abs(ga - gb).max()), "dx err:",
      float(jnp.abs(gxa - gxb).max()), flush=True)

x_dev = jnp.asarray(rng.normal(size=(N, BS, 32, 32, 3)), jnp.bfloat16)
y_dev = jnp.asarray(rng.integers(0, 10, (N, BS)), jnp.int32)
fs = (32 * 32 * 9 * 3 * 32 + 16 * 16 * 9 * 32 * 64 + 4096 * 128 + 128 * 10) * 2
f_step = 3 * fs * N * BS


def measure(tag, conv, shared=False):
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
    opt = optax.sgd(0.1, momentum=0.9)

    def one(pp, oo, xx, yy):
        def loss_of(q):
            logits = net(q, xx)
            return optax.softmax_cross_entropy_with_integer_labels(logits, yy).mean()

        loss, grads = jax.value_and_grad(loss_of)(pp)
        up, oo = opt.update(grads, oo, pp)
        return optax.apply_updates(pp, up), oo

    if shared:
        params = p1
        opt_state = opt.init(params)
        xbig = x_dev.reshape(N * BS, 32, 32, 3)
        ybig = y_dev.reshape(N * BS)

        def step(t, i):
            p, o = t
            return one(p, o, xbig, ybig)
    else:
        params = jax.tree_util.tree_map(
            lambda q: jnp.broadcast_to(q[None], (N, *q.shape)) + 0, p1)
        opt_state = jax.vmap(opt.init)(params)

        def step(t, i):
            p, o = t
            return jax.vmap(one)(p, o, x_dev, y_dev)

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
    print(f"{tag}: {per*1e3:.2f} ms  ({f_step/per/PEAK*100:.1f}% MFU)", flush=True)


measure("fwd-style-bwd vjp step", conv_fb)
measure("shared-weight step    ", lambda x, w: lax.conv_general_dilated(
    x, w, (1, 1), "SAME", dimension_numbers=DN), shared=True)
