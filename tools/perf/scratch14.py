import os, time
import jax
import jax.numpy as jnp, numpy as np
from tpfl.parallel.flash_kernel import flash_attention

rng = np.random.default_rng(0)
B, H, D, S = 1, 8, 128, int(os.environ.get("S_LEN", 32768))
q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.bfloat16) for _ in range(3))
for blk in (512, 1024, 2048):
    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block=blk).astype(jnp.float32) ** 2)
    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    try:
        out = g(q, k, v)
        float(jnp.asarray(out[0]).ravel()[0])
        n = 5
        t0 = time.perf_counter()
        for _ in range(n):
            out = g(q, k, v)
        float(jnp.asarray(out[0]).ravel()[0])
        print(f"block={blk}: {B*S*n/(time.perf_counter()-t0):.0f} toks/s fwd+bwd", flush=True)
    except Exception as e:
        print(f"block={blk}: FAILED {str(e)[:120]}", flush=True)
