"""Layer "entry": wall seconds of the ``program_call`` that met a NEW
window program (``dispatch_window``'s first-call row, one a program;
summed where set-up built several). Less ``setup_window_program_s`` it
is what the engine spends dispatching and enqueueing a new program.
None where no window program was built. Source: the engine's own span,
on ``time.monotonic``."""

from benchmark import setup_account


def read(obs):
    rows = setup_account.first_calls(obs, setup_account.WINDOW_FIRST_CALL_PREFIX)
    return sum(row["t1"] - row["t0"] for row in rows) if rows else None
