"""Scratch 7: breakdown of the vmapped round + candidate GEMM shapes."""
import os
import time

import jax


import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

from tpfl.models import CNN
from tpfl.parallel.federation import _diffuse

rng = np.random.default_rng(0)
PEAK = 197e12
N, BS = 100, 128


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


def devtime(fn, tree0, tag="", flops=None, R=20):
    """fn: tree -> tree (same structure); serialized fori on device."""

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
    return per


module = CNN(out_channels=10)
variables = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)), train=False)
p1 = variables["params"]
params = jax.tree_util.tree_map(lambda p: jnp.broadcast_to(p[None], (N, *p.shape)) + 0, p1)
x = jnp.asarray(rng.normal(size=(N, BS, 32, 32, 3)), jnp.bfloat16)
y = jnp.asarray(rng.integers(0, 10, (N, BS)), jnp.int32)

fs = (32 * 32 * 9 * 3 * 32 + 16 * 16 * 9 * 32 * 64 + 4096 * 128 + 128 * 10) * 2
f_batch = fs * N * BS

# 1) vmapped fwd one batch
def fwd(t, i):
    p, acc = t
    logits = jax.vmap(lambda pp, xx: module.apply({"params": pp}, xx, train=False))(p, x * (1 + 1e-6 * i))
    return p, acc + logits.mean()

# fwd measured: 2.95 ms / 27.0% MFU

# 2) vmapped fwd+bwd+sgd one step
opt = optax.sgd(0.1, momentum=0.9)
opt_state = jax.vmap(opt.init)(params)

def step(t, i):
    p, o = t

    def one(pp, oo, xx, yy):
        def loss_of(q):
            logits = module.apply({"params": q}, xx, train=False)
            return optax.softmax_cross_entropy_with_integer_labels(logits, yy).mean()

        loss, g = jax.value_and_grad(loss_of)(pp)
        up, oo = opt.update(g, oo, pp)
        return optax.apply_updates(pp, up), oo

    p, o = jax.vmap(one)(p, o, x, y)
    return p, o

devtime(step, (params, opt_state), tag="vmapped train step   ", flops=3 * f_batch)

# 3) aggregation alone
w = jnp.ones((N,), jnp.float32)

def agg(t, i):
    p = t
    return _diffuse(jax.tree_util.tree_map(lambda q: q * (1 + 1e-6 * i), p), w)

devtime(agg, params, tag="fedavg diffuse       ")

