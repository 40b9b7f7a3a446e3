"""A/B the tpfl flash kernel against jax's reference TPU flash kernel
and the XLA blockwise path — fwd-only and fwd+bwd — with the bench's
device-side fori_loop timing (RTT-subtracted, best of 3).

Receipts for the r5 attention-tier investigation: r4's host-loop
numbers (496k/374k toks/s) were irreproducible; honest timing measured
the r4 kernel at 42k toks/s @8k — SLOWER than XLA blockwise (67k).
Prime suspect: every kernel matmul upcast operands to f32 (fraction of
bf16 MXU rate). This harness measures the fix and the remaining gap to
the reference kernel.

Run on the real chip: python tools/perf/scratch16_flash_ab.py
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tpfl.parallel.flash_kernel import flash_attention
from tpfl.parallel.ring_attention import blockwise_attention

try:
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes,
        flash_attention as jax_flash,
    )

    HAVE_REF = True
except Exception:
    HAVE_REF = False


def _sync(out):
    # Scalar host sync (the profiling._sync_scalar discipline): a
    # device->host copy of one element of the last leaf.
    leaf = jax.tree_util.tree_leaves(out)[-1]
    float(np.asarray(leaf).ravel()[0])


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


def timed_loop(step, carry, n_iters, rtt):
    @jax.jit
    def run(c):
        out = lax.fori_loop(0, n_iters, lambda i, cc: step(cc), c)
        # Scalar out: syncing on an array carry copies tens of MB to
        # the host — a transfer that dwarfs the device time measured.
        return sum(
            x.ravel()[0].astype(jnp.float32)
            for x in jax.tree_util.tree_leaves(out)
        )

    total, out = best_of(run, carry)
    return max(total - rtt, 1e-9) / n_iters


def main():
    rtt, _ = best_of(empty_call, jnp.float32(1))
    print(f"rtt={rtt * 1e3:.1f}ms")
    B, H, D = 1, 8, 128
    rng = np.random.default_rng(0)
    for S, iters in ((8192, 96), (32768, 16)):
        q, k, v = (
            jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.bfloat16)
            for _ in range(3)
        )
        # jax reference kernel wants [B, H, S, D]
        qh, kh, vh = (jnp.moveaxis(x, 2, 1) for x in (q, k, v))

        variants = {
            "tpfl_flash": lambda q=q, k=k, v=v: flash_attention(
                q, k, v, causal=True
            ),
            "xla_blockwise": lambda q=q, k=k, v=v: blockwise_attention(
                q, k, v, causal=True
            ),
        }
        if HAVE_REF:

            def ref(qh=qh, kh=kh, vh=vh):
                return jax_flash(qh, kh, vh, causal=True)

            variants["jax_ref_flash"] = ref

        for name, fn in variants.items():
            # fwd only
            try:
                arg0 = q if name != "jax_ref_flash" else qh

                def fwd_step(c, fn=fn, name=name):
                    o = fn()
                    return c + o.astype(jnp.float32).sum()

                per = timed_loop(
                    lambda c, fn=fn: c + fn().astype(jnp.float32).sum(),
                    jnp.float32(0),
                    iters,
                    rtt,
                )
                print(
                    f"S={S} {name:14s} fwd      {B * S / per / 1e3:9.1f}k toks/s"
                )
            except Exception as e:
                print(f"S={S} {name:14s} fwd      ERROR {str(e)[:100]}")
            # fwd+bwd
            try:
                if name == "jax_ref_flash":

                    def loss(qx, kx, vx):
                        return jnp.sum(
                            jax_flash(qx, kx, vx, causal=True).astype(
                                jnp.float32
                            )
                            ** 2
                        )

                    def step(c):
                        qx, kx, vx = c
                        dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(
                            qx, kx, vx
                        )
                        return (
                            qx - 1e-6 * dq.astype(qx.dtype),
                            kx - 1e-6 * dk.astype(kx.dtype),
                            vx - 1e-6 * dv.astype(vx.dtype),
                        )

                    carry = (qh, kh, vh)
                else:
                    f = (
                        flash_attention
                        if name == "tpfl_flash"
                        else lambda a, b, c_, causal: blockwise_attention(
                            a, b, c_, causal=causal
                        )
                    )

                    def loss(qx, kx, vx, f=f):
                        return jnp.sum(
                            f(qx, kx, vx, causal=True).astype(jnp.float32) ** 2
                        )

                    def step(c, loss=loss):
                        qx, kx, vx = c
                        dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(
                            qx, kx, vx
                        )
                        return (
                            qx - 1e-6 * dq.astype(qx.dtype),
                            kx - 1e-6 * dk.astype(kx.dtype),
                            vx - 1e-6 * dv.astype(vx.dtype),
                        )

                    carry = (q, k, v)
                per = timed_loop(step, carry, iters, rtt)
                print(
                    f"S={S} {name:14s} fwd+bwd  {B * S / per / 1e3:9.1f}k toks/s"
                )
            except Exception as e:
                print(f"S={S} {name:14s} fwd+bwd  ERROR {str(e)[:100]}")


if __name__ == "__main__":
    main()
