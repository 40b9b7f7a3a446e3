"""Drive script: elastic engine + kill-and-resume (ISSUE 17).

Run from the repo root under the CPU-mesh env:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python - < tools/verify/drive_elastic_verify.py

Covers, end to end on an 8-virtual-device mesh:
  1. churn storm through a MembershipView with ZERO recompiles at a
     fixed capacity tier (CompileObservatory receipt), the one tier
     promotion compiling exactly one new program;
  2. masked capacity-8 run (4 live) byte-identical to a fresh exact
     n=4 run on the same mesh;
  3. kill-and-resume: EngineCheckpointer disk round trip onto a FRESH
     engine — byte-identical to uninterrupted on the same mesh, and a
     cross-mesh (1-device -> 8-device) restore at numeric tolerance —
     with AsyncController + QuarantineEngine state surviving;
  4. SIGTERM -> final checkpoint on disk (handler chains + restores);
  5. WindowPipeline cadence snapshots (published through the
     checkpointer, rounds pinned) and interrupt_for() abandon;
  6. COMPILE_CACHE_DIR knob: persistent jax compilation cache armed,
     tpfl_compile_cache_warm_total counter registered.
"""
import os
import signal
import tempfile

import jax
import numpy as np

from tpfl.learning.async_control import AsyncController
from tpfl.management import profiling
from tpfl.management.checkpoint import (
    EngineCheckpointer,
    install_sigterm_checkpoint,
)
from tpfl.management.quarantine import QuarantineEngine
from tpfl.models import MLP
from tpfl.parallel import FederationEngine, WindowPipeline, create_mesh
from tpfl.parallel.membership import MembershipView
from tpfl.parallel.window_pipeline import interrupt_for
from tpfl.settings import Settings

Settings.set_test_settings()
assert jax.device_count() >= 8, "run under the 8-virtual-device env"
mesh8 = create_mesh({"nodes": 8})


def data(n, nb=1, bs=32, seed=13):
    rng = np.random.default_rng(seed)
    return (rng.random((n, nb, bs, 28, 28), np.float32),
            rng.integers(0, 10, (n, nb, bs)).astype(np.int32))


def engine(n, mesh=None):
    return FederationEngine(MLP(hidden_sizes=(16,)), n, mesh=mesh,
                            learning_rate=0.1, seed=0)


def tree_bytes(t):
    return b"".join(np.asarray(x).tobytes()
                    for x in jax.tree_util.tree_leaves(t))


# 1. churn storm, zero recompiles -------------------------------------
view = MembershipView([f"n{i}" for i in range(4)], capacity_min=4)
eng = engine(4)
eng.attach_membership(view)
p = eng.init_params((28, 28))
xs8, ys8 = data(8)
dx, dy = eng.shard_data(xs8[:4], ys8[:4])
Settings.PROFILING_ENABLED = True
profiling.observatory.reset()
events = [("leave", "n1"), ("join", "n1"), ("crash", "n2"), ("join", "n2"),
          ("quarantine", "n3"), ("readmit", "n3"), ("join", "n4")]
for r in range(12):
    if r < len(events):
        kind, addr = events[r]
        getattr(view, kind)(addr)
    u = eng.unpad(p)
    if eng.sync_membership():
        p = eng.pad_stacked(u)
        dx, dy = eng.shard_data(xs8[:eng.n_nodes], ys8[:eng.n_nodes])
    p, _ = eng.run_rounds(p, dx, dy, weights=view.weights(), n_rounds=1,
                          donate=False)
counts = {k: v for k, v in profiling.observatory.signature_counts().items()
          if k.startswith("engine_round")}
Settings.PROFILING_ENABLED = False
assert counts and all(v == 1 for v in counts.values()), counts
assert sum(counts.values()) - 1 == view.promotions() == 1, counts
print("1. churn storm: zero recompiles, 1 promotion ->", sorted(counts))

# 2. masked capacity-8 == exact n=4 on the same mesh ------------------
xs4, ys4 = data(4)
exact = engine(4, mesh=mesh8)
pe = exact.init_params((28, 28))
dxe, dye = exact.shard_data(xs4, ys4)
out_e, _ = exact.run_rounds(pe, dxe, dye, n_rounds=2, donate=False)
v8 = MembershipView([f"n{i}" for i in range(4)], capacity_min=8)
el = engine(8, mesh=mesh8)
el.attach_membership(v8)
pad = lambda a: np.concatenate([a, np.broadcast_to(a[:1], (4, *a.shape[1:]))])
dx8, dy8 = el.shard_data(pad(xs4), pad(ys4))
out_8, _ = el.run_rounds(el.pad_stacked(exact.unpad(pe)), dx8, dy8,
                         weights=v8.weights(), n_rounds=2, donate=False)
live = lambda t: jax.tree_util.tree_map(lambda x: np.asarray(x)[:4], t)
assert tree_bytes(live(out_8)) == tree_bytes(live(out_e))
print("2. masked capacity-8 run byte-identical to exact n=4")

# 3. kill-and-resume (same mesh bytes, cross-mesh tolerance) ----------
eng_a = engine(4)
pa = eng_a.init_params((28, 28))
dxa, dya = eng_a.shard_data(xs4, ys4)
pa, _ = eng_a.run_rounds(pa, dxa, dya, n_rounds=6, donate=False)
eng_b = engine(4)
pb = eng_b.init_params((28, 28))
dxb, dyb = eng_b.shard_data(xs4, ys4)
pb, _ = eng_b.run_rounds(pb, dxb, dyb, n_rounds=3, donate=False)
ctl = AsyncController(node_name="drive")
ctl.state_import({"tau_mean": 1.5, "k": 3,
                  "trajectory": [{"round": 3, "k": 2, "deadline": 1.0}]})
q = QuarantineEngine("drive")
q.state_import({
    "state": {"bad": {"active": True, "since_round": 2,
                      "last_flag_round": 2, "probation": 0}},
    "actions": [], "last": {"bad": [2, {"exclude": True}]},
})
eng_b.controller = ctl
with tempfile.TemporaryDirectory() as td:
    ck = EngineCheckpointer(td, node="drive")
    ck.save(eng_b.export_state(pb, quarantine=q), step=3)
    state, meta = ck.restore()
eng_c = engine(4)
ctl2, q2 = AsyncController(node_name="drive2"), QuarantineEngine("drive2")
eng_c.controller = ctl2
out = eng_c.import_state(state, quarantine=q2)
dxc, dyc = eng_c.shard_data(xs4, ys4)
pc, _ = eng_c.run_rounds(out["params"], dxc, dyc, n_rounds=3, donate=False)
assert tree_bytes(eng_a.unpad(pa)) == tree_bytes(eng_c.unpad(pc))
assert meta["step"] == 3 and eng_c._rounds_done == 6
restored = ctl2.state_export()
assert restored["tau_mean"] == 1.5 and restored["k"] == 3
assert restored["trajectory"][0]["round"] == 3
assert q2.quarantined() == {"bad"}
# cross-mesh: restore the same snapshot onto the 8-device mesh
eng_m = engine(4, mesh=mesh8)
out_m = eng_m.import_state(state)
dxm, dym = eng_m.shard_data(xs4, ys4)
pm, _ = eng_m.run_rounds(out_m["params"], dxm, dym, n_rounds=3, donate=False)
for a, b in zip(jax.tree_util.tree_leaves(eng_a.unpad(pa)),
                jax.tree_util.tree_leaves(eng_m.unpad(pm))):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)
print("3. kill-and-resume: same-mesh bytes, cross-mesh allclose,"
      " controller/quarantine state restored")

# 4. SIGTERM -> final checkpoint --------------------------------------
with tempfile.TemporaryDirectory() as td:
    ck = EngineCheckpointer(td, node="drive")
    prev = install_sigterm_checkpoint(
        ck, lambda: eng_b.export_state(pb), node="drive")
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        state, meta = ck.restore()
        assert meta["reason"] == "sigterm" and meta["step"] == 3
    finally:
        signal.signal(signal.SIGTERM, prev)
print("4. SIGTERM handler published a final checkpoint (step 3)")

# 5. pipeline cadence snapshots + interrupt ---------------------------
eng_s = engine(4)
ps = eng_s.init_params((28, 28))
dxs, dys = eng_s.shard_data(xs4, ys4)
snaps = []
pipe = WindowPipeline(eng_s)
res, done = pipe.run(ps, dxs, dys, n_rounds=6, window=2, donate=False,
                     snapshot_every=1, snapshot_to=lambda r, s:
                     snaps.append((r, s)))
assert done == 6 and [r for r, _ in snaps] == [2, 4, 6]
assert tree_bytes(snaps[-1][1]["params"]) == tree_bytes(eng_s.unpad(res[0]))
eng_i = engine(4)
pi = eng_i.init_params((28, 28))
dxi, dyi = eng_i.shard_data(xs4, ys4)
hits = []

def wf(widx):
    hits.append(widx)
    if widx == 1:
        assert interrupt_for("drive-addr")
    return None

pipe_i = WindowPipeline(eng_i)
res_i, done_i = pipe_i.run(pi, dxi, dyi, n_rounds=6, window=2,
                           donate=False, weights_for=wf,
                           owner="drive-addr")
assert res_i is None and done_i == 4 and hits == [0, 1]
assert not interrupt_for("drive-addr")  # registry cleaned
print("5. cadence snapshots pinned + interrupt_for abandoned cleanly")

# 6. persistent compile cache knob ------------------------------------
from tpfl.management.telemetry import metrics

with tempfile.TemporaryDirectory() as td:
    Settings.COMPILE_CACHE_DIR = td
    for _ in range(2):  # 2nd identical program warms from the dir
        eng_k = engine(2)
        pk = eng_k.init_params((28, 28))
        dxk, dyk = eng_k.shard_data(*data(2))
        eng_k.run_rounds(pk, dxk, dyk, n_rounds=1, donate=False)
    assert profiling._COMPILE_CACHE_DIR == td
    assert jax.config.jax_compilation_cache_dir == td
    Settings.COMPILE_CACHE_DIR = ""
warm = {k: v for k, v in metrics.fold()["counters"].items()
        if "compile_cache_warm" in k[0]}
assert warm and all(v > 0 for v in warm.values()), \
    "tpfl_compile_cache_warm_total never counted"
print("6. COMPILE_CACHE_DIR armed; warm counter ->", warm)

print("ELASTIC DRIVE OK")
