"""Drive: asynchronous buffered rounds (PR 10) — run from the repo root:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python - < tools/verify/drive_async_verify.py

Covers: (1) the async aggregator data path over REAL wire bytes
(staleness-weighted fold of encode->decode round-tripped models,
buffer-full + deadline close reasons, empty-deadline fail-open),
(2) a free-running 4-node async federation e2e (decoupled trainer
loops, learns, trainer threads drain), (3) the serialized
byte-determinism receipt (two same-seed runs, speed-skewed fleet,
AsyncSchedule discipline), (4) the ring_attention flash SPMD fix
under the 8-device mesh, (5) deadline observability counters.
"""

import threading
import time

import jax.numpy as jnp
import numpy as np

from tpfl.learning.aggregators import FedAvg
from tpfl.learning.aggregators.aggregator import staleness_weight
from tpfl.learning.model import TpflModel
from tpfl.management.logger import logger
from tpfl.settings import Settings

Settings.set_test_settings()
Settings.LOG_LEVEL = "ERROR"
logger.set_level("ERROR")

# --- (1) async aggregator over real wire bytes ---------------------------


def mk(value, n, contributors):
    params = {
        "w": jnp.full((4, 4), float(value), jnp.float32),
        "b": jnp.full((4,), float(value), jnp.float32),
    }
    return TpflModel(params=params, num_samples=n, contributors=contributors)


tmpl = mk(0.0, 1, ["tmpl"])
agg = FedAvg("drive")
agg.set_nodes_to_aggregate(["a", "b", "c"], async_k=2, round_ordinal=9)
# Contributions arrive as WIRE BYTES (encode -> build_copy), like a peer's.
for addr, val, ver in (("a", 2.0, 9), ("b", 6.0, 6)):
    m = mk(val, 50, [addr])
    wire = m.encode_parameters()
    rx = tmpl.build_copy(params=wire, contributors=[addr], num_samples=50)
    agg.add_model(rx, start_version=ver)
assert not agg.is_open() and agg.close_reason() == "buffer_full"
out = agg.wait_and_get_aggregation(timeout=2.0)
w_a, w_b = 50 * staleness_weight(0), 50 * staleness_weight(3)
want = (2.0 * w_a + 6.0 * w_b) / (w_a + w_b)
got = float(np.asarray(out.get_parameters()["w"])[0, 0])
assert abs(got - want) < 1e-5, (got, want)
agg.clear()
print(f"[1] async wire-bytes staleness fold OK (got {got:.4f} == {want:.4f})")

# Deadline semantics + counters.
agg.set_nodes_to_aggregate(["a", "b", "c"], async_k=3, round_ordinal=10)
assert agg.async_deadline_close() is False and agg.is_open()  # empty: fail open
agg.add_model(mk(1.0, 10, ["a"]), start_version=10)
assert agg.async_deadline_close() is True
assert agg.close_reason() == "deadline"
agg.wait_and_get_aggregation(timeout=2.0)
agg.clear()
folded = logger.metrics.fold()
dl = {
    dict(k[1]).get("outcome"): v
    for k, v in folded["counters"].items()
    if k[0] == "tpfl_agg_deadline_total"
}
assert dl.get("empty", 0) >= 1 and dl.get("closed", 0) >= 1, dl
print(f"[1] deadline fail-open + close + counters OK ({dl})")

# --- (2) free-running 4-node async federation ----------------------------

from tpfl.attacks import metric_table, run_seeded_experiment  # noqa: E402

Settings.ASYNC_ROUNDS = True
Settings.ASYNC_BUFFER_K = 3
Settings.ASYNC_SERIALIZED = False
t0 = time.monotonic()
exp = run_seeded_experiment(
    1207, 4, 5, epochs=3, samples_per_node=100, batch_size=20, timeout=240.0
)
el = time.monotonic() - t0
tbl = metric_table(exp)
accs = [tbl[n]["test_metric"][-1][1] for n in sorted(tbl)]
acc = sum(accs) / len(accs)
assert acc > 0.25, accs
deadline = time.monotonic() + 10.0
while time.monotonic() < deadline and any(
    t.name.startswith("async-trainer-") for t in threading.enumerate()
):
    time.sleep(0.1)
assert not any(
    t.name.startswith("async-trainer-") and t.is_alive()
    for t in threading.enumerate()
), "trainer loops must drain at experiment end"
print(f"[2] free-running 4-node e2e OK (acc {acc:.2f}, {el:.1f}s, loops drained)")

# --- (3) serialized byte-determinism receipt ------------------------------

from tpfl.attacks.harness import final_model_digests  # noqa: E402
from tpfl.communication.faults import TrainerSpeedPlan  # noqa: E402

Settings.ASYNC_SERIALIZED = True
Settings.DISABLE_SIMULATION = True


def det_run():
    plan = TrainerSpeedPlan.skewed(
        [f"seed1209-n{i}" for i in range(4)],
        slow_frac=0.25, base_delay=0.05, skew=10.0, seed=1209,
    )
    e = run_seeded_experiment(
        1209, 4, 3, epochs=1, speed_plan=plan,
        samples_per_node=60, batch_size=20, timeout=240.0,
    )
    return final_model_digests(e)


d1, d2 = det_run(), det_run()
assert d1 == d2, "same-seed serialized runs must be byte-identical"
assert len(set(d1.values())) == 1, "all nodes must converge on identical bytes"
Settings.DISABLE_SIMULATION = False
Settings.ASYNC_ROUNDS = False
print(f"[3] serialized byte-determinism OK (digest {sorted(set(d1.values()))[0][:16]}…)")

# --- (4) ring_attention flash SPMD (the fixed tier-1 failure) -------------

from functools import partial  # noqa: E402

import jax  # noqa: E402
from jax.sharding import PartitionSpec  # noqa: E402

from tpfl.parallel import create_mesh  # noqa: E402
from tpfl.parallel.ring_attention import make_ring_attention  # noqa: E402

rng = np.random.default_rng(0)
B, S, H, D = 2, 64, 4, 16
q, k, v = (
    jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32) for _ in range(3)
)
mesh = create_mesh({"sp": 8})
for causal in (False, True):
    ring = make_ring_attention(mesh, causal=causal, impl="flash")
    out = ring(q, k, v)  # used to die: PartitionId under SPMD partitioning
    assert out.shape == (B, S, H, D)
print("[4] ring_attention flash SPMD OK (causal and non-causal, 8-device mesh)")

print("DRIVE OK: async buffered rounds verified end-to-end")
