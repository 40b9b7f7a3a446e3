"""Layer "entry": seconds from the start of the process to the first
``FederationEngine.__init__`` — the interpreter, the imports,
``require_chip`` and the caller's configuration. Process start is
``/proc/self/stat``'s start time against ``CLOCK_BOOTTIME`` (10 ms
steps), brought onto ``time.monotonic``; None where the platform cannot
say. Source: the engine's own span against the program's reading of
its start."""

from benchmark import setup_account


def read(obs):
    rows = setup_account.first_calls(obs, setup_account.ENGINE_INIT)
    if not rows:
        return None
    started = setup_account.account(obs)["process_started"]
    return None if started is None else rows[0]["t0"] - started
