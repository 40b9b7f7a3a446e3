"""Drive: free-running engine (PR 16) — WindowPipeline + FedBuffSchedule.

Run from the repo root under the virtual 8-device CPU mesh:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python - < tools/verify/drive_engine_async_verify.py

Checks, end-to-end as a consumer would drive them:
  1. pipelined == sequential, byte for byte, on the 8-device mesh —
     plain AND fedbuff AND with telemetry on; donation report clean.
  2. a 10x-skewed TrainerSpeedPlan lowered to a FedBuffSchedule: the
     staleness fan-out (gauge, ledger staleness/version stamps,
     AsyncController feed) and the τ=0 ≡ sync bit-parity receipt.
  3. FederationLearner rides ENGINE_PREFETCH perf-only: on/off byte
     identity, no leaked prefetch threads.
  4. the bench engine_async tier booleans (throughput under skew,
     idle-gap cut, determinism).
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np

from tpfl.communication.faults import TrainerSpeedPlan
from tpfl.learning.async_control import AsyncController
from tpfl.management import ledger
from tpfl.management.telemetry import flight, metrics
from tpfl.models import MLP
from tpfl.parallel import (
    FederationEngine,
    FedBuffSchedule,
    WindowPipeline,
    create_mesh,
)
from tpfl.settings import Settings

Settings.set_test_settings()

assert jax.device_count() >= 8, jax.devices()
mesh = create_mesh({"nodes": 8})
N, R, W = 8, 6, 2


def data(seed=0):
    rng = np.random.default_rng(seed)
    return (
        rng.random((N, 2, 8, 28, 28)).astype(np.float32),
        rng.integers(0, 10, (N, 2, 8)).astype(np.int32),
    )


def tree_bytes(t):
    return b"".join(
        np.asarray(x).tobytes() for x in jax.tree_util.tree_leaves(t)
    )


def engine():
    return FederationEngine(
        MLP(hidden_sizes=(16,), compute_dtype=jnp.float32),
        N, mesh=mesh, seed=0,
    )


def sched():
    return FedBuffSchedule.from_periods([1, 1, 1, 1, 2, 2, 3, 3], R)


# --- 1. pipelined == sequential on the mesh, donation clean ---------------
for label, use_sched, tele in (
    ("plain", False, False),
    ("fedbuff", True, False),
    ("fedbuff+telemetry", True, True),
):
    Settings.ENGINE_TELEMETRY = tele
    outs = []
    for pipelined in (False, True):
        eng = engine()
        p = eng.init_params((28, 28))
        dx, dy = eng.shard_data(*data())
        s = sched() if use_sched else None
        if pipelined:
            (p, losses), done = WindowPipeline(eng).run(
                p, dx, dy, n_rounds=R, window=W, schedule=s
            )
            assert done == R
        else:
            done = 0
            while done < R:
                k = min(W, R - done)
                p, losses = eng.run_rounds(
                    p, dx, dy, n_rounds=k,
                    schedule=None if s is None else s.window(done, k),
                )
                done += k
        outs.append((tree_bytes(p), tree_bytes(losses)))
    assert outs[0] == outs[1], f"pipelined != sequential ({label})"
    print(f"[1] pipelined == sequential bytes @8dev ({label}): OK")
Settings.ENGINE_TELEMETRY = False

eng = engine()
p = eng.init_params((28, 28))
dx, dy = eng.shard_data(*data())
rep = eng.donation_report(p, dx, dy, n_rounds=2)
assert rep["clean"], rep
print("[1] donation report clean @8dev: OK")

# --- 2. skewed plan -> schedule -> staleness fan-out ----------------------
addrs = [f"engine-node-{i}" for i in range(N)]
plan = TrainerSpeedPlan.skewed(
    addrs, slow_frac=0.25, base_delay=0.05, skew=10.0, seed=7
)
R2 = 20  # enough rounds for the 10x-slow tail to actually arrive
ps = FedBuffSchedule.from_plan(plan, addrs, R2)
ps2 = FedBuffSchedule.from_plan(plan, addrs, R2)
assert np.array_equal(ps.arrivals, ps2.arrivals)
assert np.array_equal(ps.taus, ps2.taus)
assert (ps.arrivals.sum(axis=1) > 0).all()
assert ps.taus.max() > 0, "skewed tail produced no stale arrivals"
print(f"[2] speed-plan lowering deterministic (max tau {ps.taus.max():.0f}): OK")

# τ=0 all-arrive schedule ≡ sync program, bit for bit.
eng = engine()
p0 = eng.init_params((28, 28))
dx, dy = eng.shard_data(*data())
allin = FedBuffSchedule.from_periods([1] * N, 3)
a, _ = eng.run_rounds(p0, dx, dy, n_rounds=3, donate=False)
b, _ = eng.run_rounds(p0, dx, dy, n_rounds=3, donate=False, schedule=allin)
assert tree_bytes(a) == tree_bytes(b)
print("[2] tau=0 fedbuff == sync bytes: OK")

Settings.ENGINE_TELEMETRY = True
Settings.LEDGER_ENABLED = True
Settings.ASYNC_ADAPTIVE = True
ledger.contrib.reset()
eng = engine()
ctrl = AsyncController("drive")
eng.controller = ctrl
p = eng.init_params((28, 28))
dx, dy = eng.shard_data(*data())
eng.run_rounds(p, dx, dy, n_rounds=R2, schedule=ps)
prom = metrics.render_prometheus()
assert "tpfl_engine_staleness" in prom
entries = [
    e for e in ledger.contrib.entries()
    if str(e.get("peer", "")).startswith("engine-node-")
]
assert entries and all("staleness" in e and "version" in e for e in entries)
assert all(e["version"] == e["round"] - e["staleness"] for e in entries)
assert int(ps.arrivals.sum()) == len(entries)
assert ctrl._last_arrivals == int(ps.arrivals[-1].sum())
assert ctrl._tau_mean is not None
print(
    f"[2] staleness fan-out ({len(entries)} ledger entries == "
    f"{int(ps.arrivals.sum())} arrivals, controller fed): OK"
)
ledger.contrib.reset()
flight.clear()
Settings.set_test_settings()

# --- 3. FederationLearner ENGINE_PREFETCH perf-only -----------------------
from tpfl.learning.dataset import synthetic_mnist
from tpfl.models import create_model
from tpfl.parallel import FederationLearner

ds = synthetic_mnist(n_train=640, n_test=128, seed=0, noise=0.4)


def fit_bytes(prefetch):
    Settings.ENGINE_PREFETCH = prefetch
    Settings.SHARD_ROUNDS_PER_DISPATCH = 2
    fl = FederationLearner(
        model=create_model("mlp", (28, 28), seed=7, hidden_sizes=(16,)),
        data=ds,
        n_local_nodes=N,
        local_rounds=R,
        batch_size=16,
        seed=0,
        mesh=mesh,
    )
    model = fl.fit()
    return tree_bytes(model.get_parameters())


b_off = fit_bytes(False)
b_on = fit_bytes(True)
assert b_off == b_on, "ENGINE_PREFETCH changed bytes"
leaked = [t for t in threading.enumerate() if "prefetch" in t.name]
assert not leaked, leaked
print("[3] FederationLearner ENGINE_PREFETCH on/off byte-identical, no leaked threads: OK")
Settings.set_test_settings()

# --- 4. bench engine_async tier booleans ----------------------------------
import bench

e = {}
bench._engine_async_tier(e)
assert "engine_async_error" not in e, e.get("engine_async_error")
t = e["engine_async_throughput"]
assert t["fedbuff_holds_0_8x"] and t["sync_degrades"], t
pl = e["engine_async_pipeline"]
assert pl["gap_cut_2x"] and pl["bytes_identical"], pl
d = e["engine_async_determinism"]
assert d["byte_identical_1dev"] and d["byte_identical_8dev"], d
print(
    f"[4] bench tier: fedbuff {t['fedbuff_vs_unskewed']}x unskewed "
    f"(sync {t['sync_vs_unskewed']}x), gap {pl['seq_idle_gap_s']}s -> "
    f"{pl['pipeline_idle_gap_s']}s, determinism 1+8dev: OK"
)

print("ALL ENGINE-ASYNC DRIVE CHECKS PASSED")
