"""Drive: the 2D nodes x model mesh in the engine round program
(ISSUE 15). Run from the repo root under the CPU-mesh env:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python - < tools/verify/drive_mesh2d_verify.py

Covers: SHARD_MODEL auto-mesh resolution, the federated TransformerLM
end-to-end on 4x2 (parity vs single device, per-device shard-bytes
drop, ring attention active, clean donation), the 1D HLO byte-identity
pin, fixed-mesh-shape determinism, the device codec on 2D, and the
transformer_fed bench tier.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from tpfl.models import MLP, TransformerLM
from tpfl.parallel import FederationEngine, create_mesh, layout_for_module
from tpfl.settings import Settings

Settings.set_test_settings()
assert len(jax.devices()) == 8, jax.devices()

n, nb, bs, S = 8, 1, 2, 16
module = TransformerLM(
    vocab=64, dim=32, heads=4, n_layers=2, max_len=64,
    compute_dtype=jnp.float32,
)
rng = np.random.default_rng(0)
xs = rng.integers(0, 64, (n, nb, bs, S)).astype(np.int32)
ys = rng.integers(0, 64, (n, nb, bs, S)).astype(np.int32)
w = np.asarray([1, 1, 0, 1, 0, 1, 1, 1], np.float32)

# 1. SHARD_MODEL auto-mesh resolution.
Settings.SHARD_NODES, Settings.SHARD_MODEL = True, 2
eng_auto = FederationEngine(module, n, mesh="auto", seed=0)
assert eng_auto.mesh.shape == {"nodes": 4, "model": 2}, eng_auto.mesh.shape
assert eng_auto.model_axes == 2 and eng_auto.layout.name == "transformer"
Settings.SHARD_NODES, Settings.SHARD_MODEL = False, 1
print("[1] SHARD_MODEL=2 auto mesh -> 4x2, transformer layout")

# 2. End-to-end federated TransformerLM: 4x2 vs single device.
def run(mesh):
    eng = FederationEngine(module, n, mesh=mesh, seed=0, learning_rate=0.05)
    p = eng.init_params((S,))
    dx, dy = eng.shard_data(xs, ys)
    p, losses = eng.run_rounds(p, dx, dy, weights=w, n_rounds=2)
    return eng, p, losses

mesh42 = create_mesh({"nodes": 4, "model": 2})
eng1, p1, l1 = run(None)
eng2, p2, l2 = run(mesh42)
# Ring attention was swapped in (the module clone seam).
assert eng2.module is not module and eng2.module.attention_fn is not None
assert eng1.module is module
for a, b in zip(jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p2)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)
np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=5e-4)
leaves = jax.tree_util.tree_leaves(p2)
total = sum(x.nbytes for x in leaves)
per_dev = sum(x.addressable_shards[0].data.nbytes for x in leaves)
assert total / per_dev > 6, (total, per_dev)  # > nodes-only 4x
print(f"[2] 4x2 LM parity OK, ring attention active, "
      f"per-device bytes 1/{total / per_dev:.2f} of stacked")

# 3. 1D HLO byte-identity pin (model=1 engages zero 2D machinery).
def digest(mesh):
    eng = FederationEngine(
        MLP(hidden_sizes=(16,), compute_dtype=jnp.float32), n, mesh=mesh,
        seed=0,
    )
    fn = eng.program("plain", 1, 2, 1, donate=False,
                     model_axes=eng.model_axes, layout=eng.layout.name)
    p = eng.init_params((28, 28))
    mx = rng.random((n, nb, 4, 28, 28)).astype(np.float32)
    my = rng.integers(0, 10, (n, nb, 4)).astype(np.int32)
    dx, dy = eng.shard_data(mx, my)
    low = fn.lower(p, {}, {}, {}, dx, dy, eng.pad_weights(None), eng.valid)
    return hashlib.sha256(low.as_text().encode()).hexdigest()

assert digest(create_mesh({"nodes": 8})) == digest(
    create_mesh({"nodes": 8, "model": 1})
)
print("[3] nodes=8 x model=1 HLO digest == 1D nodes=8 mesh")

# 4. Fixed-mesh-shape same-seed byte determinism.
def model_bytes():
    _, p, _ = run(create_mesh({"nodes": 4, "model": 2}))
    return b"".join(
        np.asarray(x).tobytes() for x in jax.tree_util.tree_leaves(p)
    )

assert model_bytes() == model_bytes()
print("[4] same-seed 4x2 runs byte-identical")

# 5. Donation clean + device codec parity on the 2D program.
engD = FederationEngine(module, n, mesh=mesh42, seed=0, learning_rate=0.05)
pD = engD.init_params((S,))
dxD, dyD = engD.shard_data(xs, ys)
rep = engD.donation_report(pD, dxD, dyD, n_rounds=2)
assert rep["clean"], rep
Settings.ENGINE_WIRE_CODEC = "quant8"
try:
    _, q1, _ = run(None)
    _, q2, _ = run(mesh42)
    for a, b in zip(
        jax.tree_util.tree_leaves(q1), jax.tree_util.tree_leaves(q2)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)
finally:
    Settings.ENGINE_WIRE_CODEC = "dense"
print(f"[5] 2D donation clean ({rep['output_aliases']}/"
      f"{rep['donated_leaves']} aliased), quant8 gossip parity OK")

# 6. Layout policy sanity (replicated default for MLP).
assert layout_for_module(MLP()).name == "replicated"

# 7. The transformer_fed bench tier, single-tier drive.
import bench

e = {}
bench._transformer_fed_tier(e)
t = e["transformer_fed"]
assert t["parity_within_2pct"] and t["determinism_byte_identical"]
assert t["donation_clean"] and t["shard_bytes_ratio"] >= 1.5, t
print(f"[7] transformer_fed tier: rps 1x1={t['rps_1x1']} "
      f"4x2={t['rps_4x2']}, shard drop {t['shard_bytes_ratio']}x")

print("DRIVE OK: 2D nodes x model mesh verified end-to-end")
