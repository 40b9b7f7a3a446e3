"""Layer "entry": programs the persistent compilation cache did not
hold, so that the backend compiled and wrote them: 0 says the run was
warm, and its ``setup_s`` compares with other warm runs only. Source:
the program's set-up account (``/jax/compilation_cache/cache_misses``)."""

from benchmark import setup_account


def read(obs):
    snapshot = setup_account.account(obs)
    return None if snapshot is None else snapshot["cache"]["misses"]
