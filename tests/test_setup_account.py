"""The set-up account (ISSUE 36): what ``CompileObservatory`` keeps of
JAX's compile events — trace / lower / load / compile by program on
``time.monotonic``, nested events apart — and the engine's first-call
rows. JAX's listeners are global and permanent and the singleton has
heard every earlier test of its worker, so most tests here read a FRESH
observatory put in the singleton's place (its listeners taken off again
afterwards); the ones on the singleton assert on differences."""

import json
import math
import subprocess
import sys
import time

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import pytest

from tpfl.management import profiling
from tpfl.management.telemetry import metrics
from tpfl.models import MLP
from tpfl.parallel import FederationEngine
from tpfl.settings import Settings


@pytest.fixture
def account(monkeypatch):
    """A fresh observatory as ``profiling.observatory``, account open."""
    fresh = profiling.CompileObservatory()
    monkeypatch.setattr(profiling, "observatory", fresh)
    fresh.open_setup_account()
    yield fresh
    jax.monitoring.unregister_event_listener(fresh._on_cache_event)
    jax.monitoring.unregister_event_duration_listener(fresh._on_duration)


def _sum_phases(snapshot, field="seconds"):
    return sum(p[field] for p in snapshot["phases"].values())


def _nested(snapshot, name):
    rows = [r for r in snapshot["nested"] if r["name"] == name]
    return rows[0] if rows else None


# --- (a) nesting ------------------------------------------------------------


def test_nested_traces_are_counted_by_name_and_kept_out_of_the_totals(account):
    n = 5

    @jax.jit
    def acct_inner(x):
        return jnp.sin(x) * 2

    @jax.jit
    def acct_outer(x):
        for i in range(n):
            x = acct_inner(x) + i
        return x

    x = jnp.ones((8,))
    jax.block_until_ready(x)
    before = account.setup_account()
    t0 = time.monotonic()
    jax.block_until_ready(acct_outer(x))
    wall = time.monotonic() - t0
    after = account.setup_account()

    # One program went through the seams, under ONE name (jax calls it
    # ``acct_outer`` while tracing and ``jit(acct_outer)`` after).
    program = after["programs"]["acct_outer"]
    assert program["events"]["trace"] == program["events"]["lower"] == 1
    assert program["events"]["load"] + program["events"]["compile"] == 1
    assert "acct_inner" not in after["programs"]
    assert "jit(acct_outer)" not in after["programs"]
    # The inner jit fired once a call, inside the outer's trace.
    inner = _nested(after, "acct_inner")
    assert inner == {
        "phase": "trace", "name": "acct_inner", "events": n,
        "seconds": pytest.approx(inner["seconds"]),
    } and inner["seconds"] > 0
    trace = after["phases"]["trace"]
    assert trace["nested_events"] >= n + 2  # sin, multiply, add... too
    # Totals hold outermost events only: no more than the call's wall,
    # where the plain sum of every event would count the inner ones twice.
    grew = _sum_phases(after) - _sum_phases(before)
    assert 0 < grew <= wall
    assert program["seconds"]["trace"] <= wall
    assert (
        trace["events"] - before["phases"]["trace"]["events"] == 1
    ), "only the outer trace is an outermost event"


def test_an_event_is_nested_only_in_what_contains_it(account):
    """Driven by hand, on the clock the callback reads: a chain's
    phases follow each other (disjoint: none nested), a later event that
    reaches back over two earlier ones takes both, whatever their phase."""
    trace = "/jax/core/compile/jaxpr_trace_duration"
    lower = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    backend = "/jax/core/compile/backend_compile_duration"
    t0 = time.monotonic()
    account._on_duration(trace, 1e-4, fun_name="kernel_body")
    account._on_duration(trace, 1e-4, fun_name="kernel_body")
    time.sleep(0.002)
    reach = time.monotonic() - t0 + 1e-3  # starts before both
    account._on_duration(lower, reach, fun_name="jit(step)")
    account._on_duration(backend, 1e-5, fun_name="jit(step)")  # follows: disjoint
    snap = account.setup_account()
    assert _nested(snap, "kernel_body")["events"] == 2
    assert snap["phases"]["trace"] == {
        "seconds": pytest.approx(0.0, abs=1e-12), "events": 0,
        "nested_seconds": pytest.approx(2e-4), "nested_events": 2,
    }
    assert snap["phases"]["lower"]["events"] == 1
    assert snap["phases"]["compile"]["events"] == 1
    assert snap["phases"]["compile"]["nested_events"] == 0
    assert set(snap["programs"]) == {"step"}


def test_memory_is_bounded_by_names_not_events(account):
    """10^5 sibling events under no parent: the per-thread list of open
    questions stays capped, the aggregate has one row."""
    trace = "/jax/core/compile/jaxpr_trace_duration"
    for _ in range(100_000):
        account._on_duration(trace, 1e-9, fun_name="add")
    assert len(account._thread.pending) <= profiling._PENDING_CAP
    assert len(account._by_name) == 1
    snap = account.setup_account()
    assert snap["phases"]["trace"]["events"] == 100_000
    # The older half of the open questions was closed unasked, and said.
    half = profiling._PENDING_CAP // 2
    assert snap["frozen_events"] >= half and snap["frozen_events"] % half == 0


# --- (b) the cache's answer -------------------------------------------------


def test_a_program_found_in_the_persistent_cache_is_a_load(
    account, tmp_path, monkeypatch
):
    monkeypatch.delenv(profiling.COMPILE_CACHE_ENV, raising=False)
    profiling.ensure_compile_cache(str(tmp_path / "account-cache"))

    def build():
        # A new function object: jax traces and lowers it again, to the
        # same module, which the persistent cache then holds.
        def acct_cached(x):
            return jnp.tanh(x) @ x.T + 3

        return jax.jit(acct_cached)

    x = jnp.ones((4, 4))
    jax.block_until_ready(x)
    start = account.setup_account()
    jax.block_until_ready(build()(x))
    cold = account.setup_account()
    jax.block_until_ready(build()(x))
    warm = account.setup_account()

    def events(snapshot):
        return snapshot["programs"]["acct_cached"]["events"]

    assert events(cold) == {"trace": 1, "lower": 1, "load": 0, "compile": 1}
    assert cold["cache"]["misses"] - start["cache"]["misses"] == 1
    assert cold["cache"]["hits"] == start["cache"]["hits"]
    # The second chain: traced and lowered again, then LOADED.
    assert events(warm) == {"trace": 2, "lower": 2, "load": 1, "compile": 1}
    assert warm["cache"]["misses"] == cold["cache"]["misses"]
    assert warm["cache"]["hits"] - cold["cache"]["hits"] == 1
    assert warm["cache"]["retrieval_seconds"] > cold["cache"]["retrieval_seconds"]
    assert warm["phases"]["load"]["seconds"] > 0
    assert (
        warm["phases"]["compile"]["seconds"]
        == cold["phases"]["compile"]["seconds"]
    )


# --- (c) steady state -------------------------------------------------------


def test_a_compiled_program_adds_nothing_to_the_account():
    profiling.observatory.open_setup_account()
    f = jax.jit(lambda x: x * 2 + 1)
    x = jnp.ones((16,))
    jax.block_until_ready(f(x))
    before = profiling.observatory.setup_account()
    for _ in range(100):
        x = f(x)
    jax.block_until_ready(x)
    after = profiling.observatory.setup_account()
    assert after["phases"] == before["phases"]
    assert after["cache"] == before["cache"]
    assert after["programs"] == before["programs"]


# --- (d) the engine ---------------------------------------------------------


def _engine_and_state(n=2):
    eng = FederationEngine(
        MLP(hidden_sizes=(8,), compute_dtype=jnp.float32), n, seed=0
    )
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.random((n, 1, 4, 28, 28)).astype(np.float32))
    ys = jnp.asarray(rng.integers(0, 10, (n, 1, 4)).astype(np.int32))
    return eng, eng.init_params((28, 28)), xs, ys


def test_engine_opens_the_account_and_rows_each_new_program_once(monkeypatch):
    fresh = profiling.CompileObservatory()
    monkeypatch.setattr(profiling, "observatory", fresh)
    assert fresh.setup_account()["started"] is None
    # As a deployment runs: the test profile's rank receipts lower a new
    # program during the LOOKUP, ahead of the call that is timed here.
    Settings.RANK_CONTRACTS = False
    try:
        t0 = time.monotonic()
        eng, params, xs, ys = _engine_and_state()
        # Live before the first dispatch: the account is open, holds the
        # engine's own construction, and hears a caller's jit.
        snap = fresh.setup_account()
        assert t0 <= snap["started"] <= time.monotonic()
        (init,) = snap["first_calls"]
        assert init["program"] == "engine_init"
        assert snap["started"] <= init["t0"] <= init["t1"] <= time.monotonic()
        jax.block_until_ready(jax.jit(lambda x: x + 1, inline=False)(xs))
        assert "<lambda>" in fresh.setup_account()["programs"]

        def window_rows():
            return [
                r for r in fresh.setup_account()["first_calls"]
                if r["program"].startswith("engine_round:")
            ]

        assert window_rows() == []
        t1 = time.monotonic()
        params, _ = eng.run_rounds(params, xs, ys, n_rounds=2, donate=False)
        t2 = time.monotonic()
        (row,) = window_rows()
        assert row["program"].startswith("engine_round:plainx2")
        assert t1 <= row["t0"] < row["t1"] <= t2
        # The program's own phases lie inside its first call.
        program = fresh.setup_account()["programs"]["tpfl_window"]
        assert program["events"]["lower"] == 1
        assert sum(program["seconds"].values()) <= row["t1"] - row["t0"]
        # A second window of the same program: no row, no event.
        before = fresh.setup_account()
        params, _ = eng.run_rounds(params, xs, ys, n_rounds=2, donate=False)
        after = fresh.setup_account()
        assert len(window_rows()) == 1
        assert after["programs"]["tpfl_window"] == before["programs"]["tpfl_window"]
        # Another n_rounds is another program: one more row.
        eng.run_rounds(params, xs, ys, n_rounds=3, donate=False)
        rows = window_rows()
        assert [r["program"].split(":")[1] for r in rows] == ["plainx2", "plainx3"]
        assert fresh.setup_account()["programs"]["tpfl_window"]["events"]["lower"] == 2
    finally:
        jax.monitoring.unregister_event_listener(fresh._on_cache_event)
        jax.monitoring.unregister_event_duration_listener(fresh._on_duration)


def test_a_failed_lookup_leaves_no_first_call_for_the_next_dispatch(account):
    """The fresh program's name is taken by the dispatch that built it,
    so a later dispatch of a cached program never rows under it."""
    eng, params, xs, ys = _engine_and_state()
    eng.run_rounds(params, xs, ys, n_rounds=1, donate=False)
    assert eng._fresh_program is None
    rows = account.setup_account()["first_calls"]
    assert [r["program"].split(":")[0] for r in rows] == [
        "engine_init", "engine_round"
    ]


# --- (e) the clock ----------------------------------------------------------


def test_the_account_lies_on_the_clock_setup_s_is_measured_on():
    """``setup_s`` is ``time.perf_counter`` from ``run.py``'s first line,
    the account ``time.monotonic``: on Linux both read CLOCK_MONOTONIC,
    so the account's seconds and spans lie on ``setup_s``'s axis."""
    mono = time.get_clock_info("monotonic")
    perf = time.get_clock_info("perf_counter")
    assert mono.implementation == perf.implementation == (
        "clock_gettime(CLOCK_MONOTONIC)"
    )
    assert mono.monotonic and perf.monotonic
    assert abs(time.monotonic() - time.perf_counter()) < 1e-3


def test_process_start_is_read_on_the_monotonic_axis():
    code = (
        "import json, time\n"
        "from tpfl.management import profiling\n"
        "t = profiling.process_started()\n"
        "print(json.dumps(None if t is None else time.monotonic() - t))\n"
    )
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, check=True,
    )
    wall = time.monotonic() - t0
    age = json.loads(out.stdout)
    assert age is not None, "/proc/self/stat + CLOCK_BOOTTIME on Linux"
    # /proc counts in 10 ms ticks: the child is no older than its call.
    assert -0.02 <= age <= wall + 0.02
    # And in this process: started before the account could open.
    snap = profiling.observatory.setup_account()
    assert profiling.process_started() < time.monotonic()
    if snap["started"] is not None:
        assert snap["process_started"] < snap["started"]


# --- (g) the always-on half and the gated half ------------------------------


def test_wrap_off_is_a_passthrough_and_the_account_is_on_regardless(account):
    Settings.PROFILING_ENABLED = False
    calls = []

    def f(x):
        calls.append(x)
        return x

    w = account.wrap(f, "t_off")
    assert w(7) == 7 and calls == [7]
    assert "t_off" not in account.signature_counts()
    # The gated half recorded nothing; the always-on half hears a
    # wrapped jit all the same.
    wrapped = account.wrap(jax.jit(lambda x: x - 1, inline=False), "t_acct")
    jax.block_until_ready(wrapped(jnp.ones((3,))))
    assert "t_acct" not in account.signature_counts()
    assert account.setup_account()["programs"]["<lambda>"]["events"]["trace"] == 1


def test_registry_carries_the_account_under_its_two_names():
    Settings.PROFILING_ENABLED = False
    profiling.observatory.open_setup_account()

    def series(name):
        return {
            labels: value
            for (n, labels), value in metrics.fold()["counters"].items()
            if n == name
        }

    before_s = series("tpfl_setup_seconds_total")
    before_n = series("tpfl_setup_events_total")

    @jax.jit
    def acct_reg_outer(x):
        return jax.jit(lambda y: y * 3)(x) + 1

    jax.block_until_ready(acct_reg_outer(jnp.ones((5,))))
    after_s = series("tpfl_setup_seconds_total")
    after_n = series("tpfl_setup_events_total")
    trace = (("phase", "trace"),)
    assert after_s[trace] > before_s.get(trace, 0.0)
    outer = (("nested", "false"), ("phase", "trace"))
    nested = (("nested", "true"), ("phase", "trace"))
    assert after_n[outer] - before_n.get(outer, 0.0) >= 1
    assert after_n[nested] - before_n.get(nested, 0.0) >= 1
    assert all(math.isfinite(v) for v in after_s.values())
    # The removed series are gone for good.
    names = {n for n, _ in metrics.fold()["counters"]} | {
        n for n, _ in metrics.fold()["histograms"]
    }
    assert not names & {
        "tpfl_compile_seconds", "tpfl_jax_compile_seconds",
        "tpfl_jax_monitoring_events_total",
    }
