"""Scratch 11: standalone Pallas kernel timings (vmapped over nodes) +
single-step grad parity vs XLA on TPU."""
import os
import time

import jax


import jax.numpy as jnp
import numpy as np
from jax import lax

from tpfl.parallel.conv_kernel import _DN, node_conv

rng = np.random.default_rng(0)
PEAK = 197e12
N, BS = 100, 128
R = 20


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


def devloop(fn, tree0, tag, flops=None):
    @jax.jit
    def run(t):
        return lax.fori_loop(0, R, lambda i, t: fn(t, i), t)

    out = run(tree0)
    float(jnp.asarray(jax.tree_util.tree_leaves(out)[0]).ravel()[0])
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = run(tree0)
        float(jnp.asarray(jax.tree_util.tree_leaves(out)[0]).ravel()[0])
        best = min(best, time.perf_counter() - t0)
    per = (best - BASE) / R
    msg = f"{tag}: {per*1e3:.2f} ms"
    if flops:
        msg += f"  ({flops/per/PEAK*100:.1f}% MFU)"
    print(msg, flush=True)


def vgrad(conv):
    def per_node(x, w, d):
        _, vjp = jax.vjp(lambda ww: conv(x, ww), w)
        return vjp(d)[0]

    return jax.vmap(per_node)


conv_k = lambda x, w: node_conv(x, w, False)
conv_x = lambda x, w: lax.conv_general_dilated(
    x, w, (1, 1), "SAME", dimension_numbers=_DN)

# conv2 shapes
x2 = jnp.asarray(rng.normal(size=(N, BS, 16, 16, 32)), jnp.bfloat16)
w2 = jnp.asarray(rng.normal(size=(N, 3, 3, 32, 64)), jnp.bfloat16) * 0.1
d2 = jnp.asarray(rng.normal(size=(N, BS, 16, 16, 64)), jnp.bfloat16)
f2 = 2 * N * BS * 256 * 288 * 64

gk = jax.jit(vgrad(conv_k))
gx = jax.jit(vgrad(conv_x))

# full vjp x-grad parity
def vgrad_x(conv):
    def per_node(x, w, d):
        _, vjp = jax.vjp(lambda xx: conv(xx, w), x)
        return vjp(d)[0]

    return jax.vmap(per_node)


def time1(tag, fn, x, w, d, flops):
    def step(t, i):
        out = fn(x, w * (1 + 1e-6 * i), d)
        return (t[0] + out.astype(jnp.float32).ravel()[0],)

    devloop(step, (jnp.float32(0),), tag, flops)


time1("pallas conv2 dW", gk, x2, w2, d2, f2)
time1("pallas conv2 dx", vgrad_x(conv_k), x2, w2, d2, f2)

# conv1 shapes
x1 = jnp.asarray(rng.normal(size=(N, BS, 32, 32, 3)), jnp.bfloat16)
w1 = jnp.asarray(rng.normal(size=(N, 3, 3, 3, 32)), jnp.bfloat16) * 0.1
d1 = jnp.asarray(rng.normal(size=(N, BS, 32, 32, 32)), jnp.bfloat16)
f1 = 2 * N * BS * 1024 * 27 * 32
time1("pallas conv1 dW", gk, x1, w1, d1, f1)
