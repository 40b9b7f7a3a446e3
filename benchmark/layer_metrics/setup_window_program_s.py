"""Layer "entry": trace + lower + load + compile seconds of the
engine's window program(s) alone (``tpfl_window``) — what the engine
owns of set-up, against the callers' own jits. None where no window
program was built. Source: the program's set-up account, by program."""

from benchmark import setup_account


def read(obs):
    snapshot = setup_account.account(obs)
    if snapshot is None:
        return None
    program = snapshot["programs"].get(setup_account.WINDOW_PROGRAM)
    return None if program is None else sum(program["seconds"].values())
