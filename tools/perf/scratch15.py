"""Scratch 15: where does the 32k TransformerLM train step lose 25x?
Device-side fori timing of: full step, attention-swap variants, and a
no-attention ablation."""
import os
import time

import jax


import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

from tpfl.models import TransformerLM
from tpfl.parallel.flash_kernel import flash_attention

rng = np.random.default_rng(0)
S = 32768
toks = jnp.asarray(rng.integers(0, 256, (1, S)), jnp.int32)


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
print(f"RTT: {BASE*1e3:.0f} ms", flush=True)


def measure(tag, attention_fn, R=5):
    lm = TransformerLM(
        vocab=256, dim=512, heads=8, n_layers=4, max_len=S,
        attention_fn=attention_fn,
    )
    variables = lm.init(jax.random.PRNGKey(0), toks[:, :128], train=False)
    tx = optax.sgd(1e-2, momentum=0.9)
    p0 = variables["params"]
    o0 = tx.init(p0)

    def one(p, o):
        def loss_of(pp):
            logits = lm.apply({"params": pp}, toks, train=True)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], toks[:, 1:]
            ).mean()

        loss, grads = jax.value_and_grad(loss_of)(p)
        up, o = tx.update(grads, o, p)
        return optax.apply_updates(p, up), o

    @jax.jit
    def run(p, o):
        return lax.fori_loop(0, R, lambda i, t: one(*t), (p, o))

    out = run(p0, o0)
    float(jnp.asarray(jax.tree_util.tree_leaves(out)[0]).ravel()[0])
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        out = run(p0, o0)
        float(jnp.asarray(jax.tree_util.tree_leaves(out)[0]).ravel()[0])
        best = min(best, time.perf_counter() - t0)
    per = (best - BASE) / R
    print(f"{tag}: {per*1e3:.0f} ms/step  ({S/per:.0f} toks/s)", flush=True)
    return per


def no_attention(q, k, v, causal=True):
    return v  # ablation: attention replaced by identity on values


measure("no-attention ablation ", no_attention)
measure("flash block=1024      ", flash_attention)
