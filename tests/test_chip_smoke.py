"""chip_smoke.py's contract, as far as a CPU can hold it to it: the one
compile-cache rule, the device helper that refuses a CPU, and a smoke
that never prints ``"ok": true`` without a chip or after a failed phase.

The ``slow`` walk-through rehearses the one-chip phases' control flow
at toy sizes (the on-chip-measurement guide's first rehearsal) — run it
before spending chip time on a change to ``chip_smoke.py``.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))  # chip_smoke lives at the root

import chip_smoke  # noqa: E402

from tpfl.management import profiling  # noqa: E402
from tpfl.parallel import device_report, require_chip  # noqa: E402

CPU_ENV = dict(os.environ, JAX_PLATFORMS="cpu")


# --- the one compile-cache rule ------------------------------------------


@pytest.mark.parametrize(
    "env, explicit, want",
    [
        ("/placed/from/outside", None, "/placed/from/outside"),
        ("/placed/from/outside", "/a/knob/dir", "/placed/from/outside"),
        (None, "/a/knob/dir", "/a/knob/dir"),
        (None, None, str(REPO / ".jax_cache")),
        ("", None, str(REPO / ".jax_cache")),
    ],
    ids=["env", "env-beats-explicit", "explicit", "fixed-default", "empty-env"],
)
def test_compile_cache_dir_rule(monkeypatch, env, explicit, want):
    if env is None:
        monkeypatch.delenv(profiling.COMPILE_CACHE_ENV, raising=False)
    else:
        monkeypatch.setenv(profiling.COMPILE_CACHE_ENV, env)
    assert profiling.compile_cache_dir(explicit) == want


def test_env_placed_cache_is_never_repointed(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, no code path sets a
    directory — not the resolver's default, not an explicit one — and
    jax's cache object is not reset."""
    from jax.experimental.compilation_cache import compilation_cache

    placed = str(tmp_path / "placed")
    monkeypatch.setenv(profiling.COMPILE_CACHE_ENV, placed)
    monkeypatch.setattr(profiling, "_COMPILE_CACHE_DIR", None)
    updates, resets = [], []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (updates.append(k), real_update(k, v)),
    )
    monkeypatch.setattr(
        compilation_cache, "reset_cache", lambda: resets.append(1)
    )
    assert profiling.ensure_compile_cache() == placed
    assert profiling.ensure_compile_cache(str(tmp_path / "knob")) == placed
    assert "jax_compilation_cache_dir" not in updates
    assert not resets
    assert not os.path.exists(placed)  # jax makes it, not this repo


# --- no silent CPU -------------------------------------------------------


def test_require_chip_raises_on_cpu():
    report = device_report()
    assert report == {
        "platform": "cpu", "kind": "cpu", "count": len(jax.devices())
    }
    with pytest.raises(RuntimeError, match="no TPU"):
        require_chip()


def test_require_chip_rejects_unknown_kind_and_too_few_chips(monkeypatch):
    from tpfl.parallel import mesh

    fake = {"platform": "tpu", "kind": "TPU v99", "count": 1}
    monkeypatch.setattr(mesh, "device_report", lambda: dict(fake))
    with pytest.raises(RuntimeError, match="peaks table"):
        require_chip()
    fake["kind"] = next(iter(profiling.PEAK_FLOPS))
    assert require_chip() == fake
    with pytest.raises(RuntimeError, match="need 4 chips"):
        require_chip(min_count=4)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]], ids=["one", "four"])
def test_chip_smoke_without_a_chip_fails_and_prints_no_result(argv):
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), *argv],
        capture_output=True, text=True, env=CPU_ENV, cwd=REPO, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """The script with nothing else of the repo beside it exits non-zero
    and prints no result (the driver runs it that way, too)."""
    (tmp_path / "chip_smoke.py").write_text(
        (REPO / "chip_smoke.py").read_text()
    )
    env = {k: v for k, v in CPU_ENV.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


# --- the phase runner ----------------------------------------------------

DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def test_run_phases_prints_the_contract_line_last():
    lines = []

    def good(ph):
        ph.check(True, "held")
        ph.facts["n"] = 1

    chip_smoke.run_phases([("a", good), ("b", good)], DEVICE, emit=lines.append)
    assert [json.loads(x).get("phase") for x in lines] == ["a", "b", None]
    assert json.loads(lines[0])["checked"] == ["held"]
    assert lines[-1] == (
        '{"ok": true, "device": {"platform": "tpu", '
        '"kind": "TPU v5 lite", "count": 1}}'
    )


@pytest.mark.parametrize(
    "bad, exc",
    [
        (lambda ph: ph.check(False, "a failed check"), chip_smoke.SmokeFailure),
        (lambda ph: 1 / 0, ZeroDivisionError),
    ],
    ids=["failed-check", "exception"],
)
def test_failing_phase_ends_the_run_with_no_ok_line(bad, exc):
    lines, ran = [], []
    phases = [
        ("first", lambda ph: ran.append("first")),
        ("bad", bad),
        ("never", lambda ph: ran.append("never")),
    ]
    with pytest.raises(exc):
        chip_smoke.run_phases(phases, DEVICE, emit=lines.append)
    assert ran == ["first"]
    assert len(lines) == 1 and '"ok"' not in lines[0]


# --- CPU walk-through of the one-chip phases (slow, not tier-1) ----------


@pytest.mark.slow
def test_one_chip_phases_walk_through_on_cpu(monkeypatch, tmp_path):
    """Toy sizes, the emulator for the kernel, no HBM counters: every
    phase's control flow and checks run end to end. The ``kernel``
    phase's LAST check (``tpu_custom_call`` in the HLO) cannot hold on a
    CPU — it failing, and nothing before it, is the expected outcome."""
    from functools import partial

    from tpfl.communication.memory import clear_registry
    from tpfl.parallel import compat

    monkeypatch.delenv(profiling.COMPILE_CACHE_ENV, raising=False)
    monkeypatch.setattr(chip_smoke, "_peak_hbm", lambda ph, dev: 1)
    monkeypatch.setattr(compat, "pallas_interpret", lambda interpret: True)
    sz = chip_smoke.Sizes(
        resnet_nodes=2, resnet_batches=1, cnn_nodes=4, cnn_batches=1,
        batch=8, sync_rounds=1, lm_seq=256, parity_seq=256, band_seq=1280,
        moe_tokens=256,
    )
    cache_dir = profiling.ensure_compile_cache(str(tmp_path / "cache"))
    clear_registry()
    lines = []
    chip_smoke.run_phases(
        [
            ("engine", partial(chip_smoke.phase_engine, sz=sz, seed=0)),
            ("gossip", partial(chip_smoke.phase_gossip, seed=0)),
            ("sync", partial(chip_smoke.phase_sync, sz=sz, seed=0)),
            ("cache", partial(chip_smoke.phase_cache, cache_dir=cache_dir)),
        ],
        DEVICE, emit=lines.append,
    )
    assert [json.loads(x).get("phase") for x in lines[:-1]] == [
        "engine", "gossip", "sync", "cache"
    ]
    with pytest.raises(chip_smoke.SmokeFailure, match="tpu_custom_call"):
        chip_smoke.phase_kernel(chip_smoke.Phase("kernel"), sz=sz, seed=0)
    # The gather against itself off a TPU; steered onto the TPU branch,
    # the kernel (in the emulator) against the gather, every check held.
    with pytest.raises(chip_smoke.SmokeFailure, match="runs the Pallas kernel"):
        chip_smoke.phase_experts(chip_smoke.Phase("experts"), sz=sz, seed=0)
    monkeypatch.setattr(compat, "on_tpu", lambda: True)
    experts = chip_smoke.Phase("experts")
    chip_smoke.phase_experts(experts, sz=sz, seed=0)
    assert experts.facts["shape"]["rows"] == 2 * 256 * 8  # small: all head


@pytest.mark.slow
def test_four_chip_phases_walk_through_on_virtual_devices(monkeypatch):
    """The guide's second rehearsal: the ``--chips 4`` phases on four of
    conftest's virtual CPU devices, toy sizes. Virtual devices have no
    ``coords`` and no memory counters, and the model-axis ring takes
    its XLA inner off-TPU, so those three are steered here; the 2D
    phase's LAST check (``tpu_custom_call``) failing, and nothing
    before it, is the expected outcome."""
    monkeypatch.setattr(chip_smoke, "_device_order", lambda mesh: [])
    monkeypatch.setattr(chip_smoke, "_memory_on_each", lambda ph, d, w: {})
    sz = chip_smoke.Sizes(
        cnn_nodes=8, cnn_batches=1, batch=8, mesh_lm_seq=128, mesh_lm_batch=2
    )
    ph = chip_smoke.Phase("mesh_nodes4")
    chip_smoke.phase_mesh_1d(ph, sz=sz, seed=0)
    assert ph.facts["params"]["total_bytes"] == 4 * min(
        ph.facts["params"]["bytes_per_device"].values()
    )
    with pytest.raises(chip_smoke.SmokeFailure, match="tpu_custom_call"):
        chip_smoke.phase_mesh_2d(
            chip_smoke.Phase("mesh_nodes2_model2"), sz=sz, seed=0
        )
