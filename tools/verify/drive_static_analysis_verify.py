"""Drive: ISSUE-14 static-analysis suite + TRACE_CONTRACTS verification.

Run from the repo root: ``JAX_PLATFORMS=cpu python - < tools/verify/drive_static_analysis_verify.py``
"""
from tpfl.settings import Settings

Settings.set_test_settings()
Settings.LOG_LEVEL = "ERROR"
from tpfl.management.logger import logger

logger.set_level("ERROR")

import jax.numpy as jnp

from tpfl.learning.dataset import RandomIIDPartitionStrategy, synthetic_mnist
from tpfl.learning.jax_learner import JaxLearner
from tpfl.models import create_model
from tpfl.simulation.batched_fit import job_signature

# --- job_signature on device leaves (the fixed np.asarray copy path) ---
ds = synthetic_mnist(n_train=120, n_test=30, seed=0, noise=0.4)
part = ds.generate_partitions(1, RandomIIDPartitionStrategy, seed=1)[0]
model = create_model("mlp", (28, 28), seed=7, hidden_sizes=(16,))
model.set_parameters([jnp.asarray(p) for p in model.get_parameters_list()])
ln = JaxLearner(model, part, addr="sig-check-0")
sig = job_signature(ln)
assert sig[2] and all(dt == "float32" for _s, dt in sig[2]), sig[2]
model2 = create_model("mlp", (28, 28), seed=9, hidden_sizes=(16,))
assert job_signature(JaxLearner(model2, part, addr="sig-check-1")) == sig
print("job_signature OK on device leaves (no host copies), sharing intact")

# --- TRACE_CONTRACTS on the real engine seam ---
from tpfl.concurrency import TraceContractError
from tpfl.parallel.engine import FederationEngine

Settings.TRACE_CONTRACTS = True
module = create_model("mlp", (4,), seed=0, hidden_sizes=(8,)).module
eng = FederationEngine(module, 2, learning_rate=0.1, seed=0)
params = eng.init_params((4,))
xs = jnp.zeros((2, 1, 4, 4))
ys = jnp.zeros((2, 1, 4), jnp.int32)
out = eng.run_rounds(params, xs, ys, epochs=1, donate=False)
frac = float(Settings.WIRE_TOPK_FRAC)
# seeded key-hygiene bug: donation variants collide on one cache slot
eng._wrapped[("plain", 1, 1, 1, True, False, 0, 0, frac)] = (
    eng._wrapped[("plain", 1, 1, 1, False, False, 0, 0, frac)]
)
try:
    eng.run_rounds(out[0], xs, ys, epochs=1, donate=True)
    raise SystemExit("contract did NOT fire")
except TraceContractError as e:
    assert "ENGINE_DONATE" in str(e)
print("TRACE_CONTRACTS witness OK (names ENGINE_DONATE)")
Settings.TRACE_CONTRACTS = False
eng2 = FederationEngine(module, 2, learning_rate=0.1, seed=0)
eng2.run_rounds(eng2.init_params((4,)), xs, ys, epochs=1, donate=False)
assert not hasattr(next(iter(eng2._wrapped.values())), "contract")
print("contracts-off zero-wrapper OK")

# --- static suite + analysis tier (the CI gates' inputs) ---
import bench

e = {}
bench._analysis_tier(e)
s = e["analysis_static"]
assert s["zero_violations"] and s["jax_passes_clean"] and s["within_5s_budget"], s
assert e["analysis_lock_trace"]["traced"]["acyclic"]
assert e["analysis_lock_trace"]["traced"]["all_threads_named"]
print("analysis tier OK:", {k: s[k] for k in ("wall_s", "violations", "jax_pass_violations")})

# --- capture pass proves the engine key (the acceptance criterion) ---
import pathlib
import shutil
import sys
import tempfile

sys.path.insert(0, str(pathlib.Path.cwd()))
from tools.tpflcheck.capture import check_capture

src = pathlib.Path("tpfl/parallel/engine.py").read_text()
with tempfile.TemporaryDirectory() as td:
    target = pathlib.Path(td) / "tpfl" / "parallel" / "engine.py"
    target.parent.mkdir(parents=True)
    for frag, param in [
        ("bool(donate),\n", "donate"), ("bool(telemetry), ", "telemetry"),
        ("int(codec), ", "codec"), ("float(topk_frac),", "topk_frac"),
    ]:
        target.write_text(src.replace(frag, "", 1))
        found = check_capture(pathlib.Path(td))
        assert any(v.key.endswith(f"::{param}") for v in found), (frag, found)
print("capture pass proves engine key totality (all 4 axes)")
print("ALL DRIVES PASSED")
