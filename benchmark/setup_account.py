"""The program's own set-up account, read for the ``setup_*`` metrics.

``tpfl.management.profiling.observatory.setup_account()`` (PR 36) is a
snapshot of what JAX's monitoring said every program of this process
cost to trace, lower, load from the persistent cache or compile, on
``time.monotonic`` — on Linux the clock ``setup_s`` is measured on —
with events that lie inside another event kept apart, so the four
phases never count a second twice. The readers run after the traced
slice and before the check against the reference: with no compile in
the measured window, all of the account is set-up.

Every reader returns None, and the line leaves its metric out, where
the program keeps no account (the parent of PR 36) or the measured
window compiled (the account would then hold more than set-up).

What the nine metrics leave out of the snapshot — the rows by program,
the names that were traced most inside other traces — is written whole
to ``.bench_out/setup_account.json`` (the newest traced run's), as
``program_trace.json`` is for the device's side. Nothing is printed.
"""

import json

from benchmark.cells import ROOT

SNAPSHOT_FILE = ROOT / ".bench_out" / "setup_account.json"

#: The engine's window programs, as JAX names them (``jit_tpfl_window``
#: is the XLA module), and the first-call rows the engine writes for
#: them and for its own construction.
WINDOW_PROGRAM = "tpfl_window"
WINDOW_FIRST_CALL_PREFIX = "engine_round:"
ENGINE_INIT = "engine_init"
# The run whose account was read last, and what was read: the nine
# readers of one run see ONE snapshot, written once.
_held: dict = {}


def account(obs):
    """The snapshot, or None where there is nothing to read."""
    if _held.get("obs") is not obs:
        _held.update(obs=obs, snapshot=_read(obs))
    return _held["snapshot"]


def _read(obs):
    if obs["compiles_in_window"]:
        return None
    from tpfl.management import profiling

    reader = getattr(profiling.observatory, "setup_account", None)
    if reader is None:
        return None
    snapshot = reader()
    SNAPSHOT_FILE.parent.mkdir(exist_ok=True)
    SNAPSHOT_FILE.write_text(json.dumps(snapshot, indent=1))
    return snapshot


def phase(obs, name, field="seconds"):
    """One total of one phase (``trace`` / ``lower`` / ``load`` /
    ``compile``): ``seconds`` and ``events`` of outermost events,
    ``nested_seconds`` and ``nested_events`` of the rest."""
    snapshot = account(obs)
    return None if snapshot is None else snapshot["phases"][name][field]


def first_calls(obs, prefix):
    """The first-call rows whose program starts with ``prefix``, or
    None where there is no account."""
    snapshot = account(obs)
    if snapshot is None:
        return None
    return [
        row for row in snapshot["first_calls"]
        if row["program"].startswith(prefix)
    ]
