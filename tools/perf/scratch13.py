import os, time
import jax
import jax.numpy as jnp, numpy as np
from tpfl.parallel.ring_attention import blockwise_attention

rng = np.random.default_rng(0)
B, H, D = 1, 8, 128
for S in (8192, 32768):
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.bfloat16) for _ in range(3))
    def loss(q, k, v):
        return jnp.sum(blockwise_attention(q, k, v, causal=True).astype(jnp.float32) ** 2)
    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    t0 = time.perf_counter()
    out = g(q, k, v)
    float(jnp.asarray(out[0]).ravel()[0])
    print(f"S={S}: compile+1st {time.perf_counter()-t0:.1f}s", flush=True)
    n = 5
    t0 = time.perf_counter()
    for _ in range(n):
        out = g(q, k, v)
    float(jnp.asarray(out[0]).ravel()[0])
    print(f"S={S}: {B*S*n/(time.perf_counter()-t0):.0f} toks/s fwd+bwd", flush=True)
