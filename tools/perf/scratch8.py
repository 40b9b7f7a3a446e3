"""Scratch 8: end-to-end vmapped train-step variants.

Baseline (XLA grouped conv fwd+bwd): 22.03 ms / 10.8% MFU (measured).
B) custom-VJP conv: XLA conv fwd, GEMM dW, GEMM+col2im dx.
C) im2col fwd, plain autodiff.
"""
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


# --- custom-VJP conv ---
@jax.custom_vjp
def node_conv(x, w):
    return lax.conv_general_dilated(x, w, (1, 1), "SAME", dimension_numbers=DN)


def _nc_fwd(x, w):
    return node_conv(x, w), (x, w)


def _nc_bwd(res, g):
    x, w = res
    B, H, W, Cin = x.shape
    kh, kw, _, Cout = w.shape
    M = B * H * W
    P = Cin * kh * kw
    g = g.astype(x.dtype)
    # dW = patches(x)^T @ g   [P, M] x [M, Cout] — K is huge, MXU-friendly
    p = lax.conv_general_dilated_patches(x, (kh, kw), (1, 1), "SAME", dimension_numbers=DN)
    pm = p.reshape(M, P)
    gm = g.reshape(M, Cout)
    dwm = lax.dot_general(pm, gm, (((0,), (0,)), ((), ())))  # [P, Cout]
    dw = dwm.reshape(Cin, kh, kw, Cout).transpose(1, 2, 0, 3).astype(w.dtype)
    # dx: dpatches = g @ wm^T  [M, Cout] x [Cout, P], then col2im shifts
    wm = w.transpose(2, 0, 1, 3).reshape(P, Cout)
    dp = lax.dot_general(gm, wm, (((1,), (1,)), ((), ())))  # [M, P]
    dp = dp.reshape(B, H, W, Cin, kh, kw)
    ph, pw = (kh - 1) // 2, (kw - 1) // 2
    dx = jnp.zeros_like(x)
    for di in range(kh):
        for dj in range(kw):
            piece = dp[:, :, :, :, di, dj]
            padded = jnp.pad(
                piece, ((0, 0), (di, kh - 1 - di), (dj, kw - 1 - dj), (0, 0))
            )
            dx = dx + padded[:, ph:ph + H, pw:pw + W, :]
    return dx, dw


node_conv.defvjp(_nc_fwd, _nc_bwd)


def conv_plain(x, w):
    return lax.conv_general_dilated(x, w, (1, 1), "SAME", dimension_numbers=DN)


def conv_im2col(x, w):
    kh, kw, cin, cout = w.shape
    p = lax.conv_general_dilated_patches(x, (kh, kw), (1, 1), "SAME", dimension_numbers=DN)
    wm = w.transpose(2, 0, 1, 3).reshape(cin * kh * kw, cout)
    return lax.dot_general(p, wm, (((3,), (0,)), ((), ())))


def make_step(conv):
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
        def loss_of(q):
            logits = net(q, xx)
            return optax.softmax_cross_entropy_with_integer_labels(logits, yy).mean()

        loss, grads = jax.value_and_grad(loss_of)(pp)
        up, oo = opt.update(grads, oo, pp)
        return optax.apply_updates(pp, up), oo

    def step(t, i):
        p, o = t
        return jax.vmap(one)(p, o, x_dev, y_dev)

    return step, opt


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

fs = (32 * 32 * 9 * 3 * 32 + 16 * 16 * 9 * 32 * 64 + 4096 * 128 + 128 * 10) * 2
f_step = 3 * fs * N * BS


def measure(tag, conv):
    step, opt = make_step(conv)
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
    print(f"{tag}: {per*1e3:.2f} ms  ({f_step/per/PEAK*100:.1f}% MFU)", flush=True)


# numeric check first (tiny, grads close to plain autodiff)
xt = jnp.asarray(rng.normal(size=(2, 8, 8, 3)), jnp.float32)
wt = jnp.asarray(rng.normal(size=(3, 3, 3, 5)), jnp.float32)
g_custom = jax.grad(lambda w: jnp.sum(node_conv(xt, w) ** 2))(wt)
g_ref = jax.grad(lambda w: jnp.sum(conv_plain(xt, w) ** 2))(wt)
gx_custom = jax.grad(lambda x: jnp.sum(node_conv(x, wt) ** 2))(xt)
gx_ref = jax.grad(lambda x: jnp.sum(conv_plain(x, wt) ** 2))(xt)
print("dW err:", float(jnp.abs(g_custom - g_ref).max()),
      "dx err:", float(jnp.abs(gx_custom - gx_ref).max()), flush=True)

measure("B custom-vjp step", node_conv)
measure("C im2col fwd step", conv_im2col)
