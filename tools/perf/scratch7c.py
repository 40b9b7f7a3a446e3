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

# 4) conv2 backward GEMM shapes (batched)
M2, P2, C2 = BS * 16 * 16, 9 * 32, 64
A_dx = jnp.asarray(rng.normal(size=(N, M2, C2)), jnp.bfloat16)   # dout
B_dx = jnp.asarray(rng.normal(size=(N, C2, P2)), jnp.bfloat16)   # w^T
fb = 2 * N * M2 * P2 * C2

def g_dx(t, i):
    a, b, acc = t
    out = lax.dot_general(a * (1 + 1e-6 * i), b, (((2,), (1,)), ((0,), (0,))))
    return a, b, acc + out.mean()

devtime(g_dx, (A_dx, B_dx, jnp.float32(0)), tag="GEMM dx  [M,64]x[64,288] ", flops=fb)

A_dw = jnp.asarray(rng.normal(size=(N, P2, M2)), jnp.bfloat16)   # patches^T
B_dw = jnp.asarray(rng.normal(size=(N, M2, C2)), jnp.bfloat16)   # dout
devtime(g_dx, (A_dw, B_dw, jnp.float32(0)), tag="GEMM dW  [288,M]x[M,64]  ", flops=fb)

# 5) conv1 s2d GEMM: [N, B*256, 48] @ [N, 48, 128] (4 output pixels x 32ch)
M1s, P1s, C1s = BS * 16 * 16, 48, 128
A_s2d = jnp.asarray(rng.normal(size=(N, M1s, P1s)), jnp.bfloat16)
B_s2d = jnp.asarray(rng.normal(size=(N, P1s, C1s)), jnp.bfloat16)
f_s2d_useful = 2 * N * BS * 32 * 32 * 27 * 32  # useful conv1 flops
devtime(g_dx, (A_s2d, B_s2d, jnp.float32(0)), tag="GEMM s2d [M,48]x[48,128] ", flops=f_s2d_useful)

# 6) patches extraction cost, conv2 (node-folded layout)
x2 = jnp.asarray(rng.normal(size=(N * BS, 16, 16, 32)), jnp.bfloat16)

def patches(t, i):
    xx, acc = t
    p = lax.conv_general_dilated_patches(
        xx * (1 + 1e-6 * i), (3, 3), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return xx, acc + p.mean()

devtime(patches, (x2, jnp.float32(0)), tag="patches conv2        ")
