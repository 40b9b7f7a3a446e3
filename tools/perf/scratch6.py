"""Scratch 6: device-side timing of the REAL VmapFederation round and
its pieces. One TPU process at a time!"""
import os
import time

import jax


import jax.numpy as jnp
import numpy as np
from jax import lax

from tpfl.models import CNN
from tpfl.parallel import VmapFederation

rng = np.random.default_rng(0)
PEAK = 197e12
N, NBATCH, BS = 100, 4, 128


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

fed = VmapFederation(CNN(out_channels=10), n_nodes=N, learning_rate=0.1, seed=0)
params = fed.init_params((32, 32, 3))
xs = jnp.asarray(rng.normal(size=(N, NBATCH, BS, 32, 32, 3)), jnp.bfloat16)
ys = jnp.asarray(rng.integers(0, 10, (N, NBATCH, BS)), jnp.int32)
w = jnp.ones((N,), jnp.float32)

round_fn = fed._build_round()

# flops: per-sample fwd model flops (conv1+conv2+dense1+dense2) x3 for bwd
fs = (32 * 32 * 9 * 3 * 32 + 16 * 16 * 9 * 32 * 64 + 4096 * 128 + 128 * 10) * 2
round_flops = 3 * fs * N * NBATCH * BS
print(f"analytic round flops: {round_flops/1e12:.3f} TF", flush=True)

R = 10


@jax.jit
def many_rounds(p, xs, ys, w):
    def body(i, p):
        p2, losses = round_fn(p, xs, ys, w, 1)
        return p2

    return lax.fori_loop(0, R, body, p)


out = many_rounds(params, xs, ys, w)
float(jnp.asarray(jax.tree_util.tree_leaves(out)[0]).ravel()[0])  # compile+sync
best = float("inf")
for _ in range(3):
    t0 = time.perf_counter()
    out = many_rounds(params, xs, ys, w)
    float(jnp.asarray(jax.tree_util.tree_leaves(out)[0]).ravel()[0])
    best = min(best, time.perf_counter() - t0)
per_round = (best - BASE) / R
print(
    f"device round: {per_round*1e3:.1f} ms  "
    f"({round_flops/per_round/PEAK*100:.1f}% MFU)  "
    f"[{N*NBATCH*BS/per_round:.0f} samples/s]",
    flush=True,
)

# host-loop comparison (bench.py's current method): 10 dispatches + 1 sync
compiled = round_fn.lower(params, xs, ys, w, 1).compile()
p2, losses = compiled(params, xs, ys, w)
float(np.asarray(losses).mean())
t0 = time.perf_counter()
for _ in range(10):
    p2, losses = compiled(p2, xs, ys, w)
float(np.asarray(losses).mean())
host_per_round = (time.perf_counter() - t0) / 10
print(
    f"host-loop round: {host_per_round*1e3:.1f} ms  "
    f"({round_flops/host_per_round/PEAK*100:.1f}% MFU)",
    flush=True,
)
