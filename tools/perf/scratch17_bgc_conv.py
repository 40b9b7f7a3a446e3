"""Re-open the per-node-conv formulation question with the r5 honest
floor. The r4 investigation measured the vmapped (grouped-conv
lowering) round at ~10.8% MFU and called it within noise of a 12.0%
shared-weight floor — but that floor was measured with the broken
sync (44 ms of device work vs ~90+/-15 ms subtracted RTT); the r5
floor is 16.3%, so there is a real 1.55x formulation gap.

Hypothesis worth one experiment: express the per-node conv as ONE
conv_general_dilated with ``batch_group_count=N`` (nodes ride the
batch dim, weights stack on the output-channel dim) instead of
vmap's feature_group_count lowering (groups of cin=3 input channels
— hopeless MXU tiles).

Times the full 2-conv train step (the scratch8 net) per formulation,
device fori_loop, scalar sync, RTT subtracted, best of 3.
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


def _sync(out):
    float(np.asarray(jax.tree_util.tree_leaves(out)[-1]).ravel()[0])


def best_of(fn, *args, n=3):
    out = fn(*args)
    _sync(out)
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        out = fn(*args)
        _sync(out)
        best = min(best, time.perf_counter() - t0)
    return best, out


@jax.jit
def empty_call(x):
    return lax.fori_loop(0, 100, lambda i, a: a + x * (1 + i), jnp.float32(0))


RTT, _ = best_of(empty_call, jnp.float32(1))
print(f"rtt={RTT * 1e3:.0f}ms", flush=True)


def conv_vmap(x, w):
    """x [N, BS, H, W, cin], w [N, 3, 3, cin, cout] — vmap lowering."""
    return jax.vmap(
        lambda xx, ww: lax.conv_general_dilated(
            xx, ww, (1, 1), "SAME", dimension_numbers=DN
        )
    )(x, w)


def conv_bgc(x, w):
    """Same math via ONE batch_group_count conv: [N*BS, H, W, cin] x
    [3, 3, cin, N*cout] with batch_group_count=N -> [BS', H, W, N*cout]
    ... batch groups convolve with their own output-channel block."""
    n, bs, h, ww_, cin = x.shape
    cout = w.shape[-1]
    xf = x.reshape(n * bs, h, ww_, cin)
    wf = jnp.moveaxis(w, 0, 3).reshape(3, 3, cin, n * cout)
    y = lax.conv_general_dilated(
        xf, wf, (1, 1), "SAME", dimension_numbers=DN, batch_group_count=n
    )
    # y: [BS, H, W, N*cout] with batch collapsed per group -> back to
    # [N, BS, H, W, cout]
    y = y.reshape(bs, h, ww_, n, cout)
    return jnp.moveaxis(y, 3, 0)


def make_step(conv):
    pool = lambda y: lax.reduce_window(
        y, -jnp.inf, lax.max, (1, 1, 2, 2, 1), (1, 1, 2, 2, 1), "VALID"
    )

    def net(params, x):
        y = conv(x, params["w1"])
        y = pool(jax.nn.relu(y + params["b1"][:, None, None, None, :]))
        y = conv(y, params["w2"])
        y = pool(jax.nn.relu(y + params["b2"][:, None, None, None, :]))
        y = y.reshape(y.shape[0], y.shape[1], -1)
        y = jax.nn.relu(jnp.einsum("nbf,nfd->nbd", y, params["wd"]) + params["bd"][:, None, :])
        return (
            jnp.einsum("nbd,ndo->nbo", y, params["wo"]) + params["bo"][:, None, :]
        ).astype(jnp.float32)

    opt = optax.sgd(0.1, momentum=0.9)

    def step(t):
        p, o = t

        def loss_of(q):
            logits = net(q, x_dev)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y_dev
            ).mean()

        loss, grads = jax.value_and_grad(loss_of)(p)
        up, o = opt.update(grads, o, p)
        return optax.apply_updates(p, up), o

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

# numeric check: both formulations agree
xt = jnp.asarray(rng.normal(size=(4, 2, 8, 8, 3)), jnp.float32)
wt = jnp.asarray(rng.normal(size=(4, 3, 3, 3, 5)), jnp.float32)
err = float(jnp.abs(conv_vmap(xt, wt) - conv_bgc(xt, wt)).max())
print("bgc-vs-vmap fwd err:", err, flush=True)
assert err < 1e-3


def measure(tag, conv):
    step, opt = make_step(conv)
    params = init_params()
    opt_state = jax.vmap(opt.init)(params)

    @jax.jit
    def run(t):
        out = lax.fori_loop(0, R, lambda i, tt: step(tt), t)
        leaves = jax.tree_util.tree_leaves(out)
        return sum(x.ravel()[0].astype(jnp.float32) for x in leaves)

    best, _ = best_of(run, (params, opt_state))
    per = (best - RTT) / R
    print(
        f"{tag}: {per * 1e3:.2f} ms  ({f_step / per / PEAK * 100:.1f}% MFU)",
        flush=True,
    )


measure("A vmap grouped conv ", conv_vmap)
measure("B batch_group_count ", conv_bgc)
