"""Layer "entry": trace events that lie INSIDE another compile event of
their thread — a jitted function traced while its caller is traced
(``custom_vmap``, ``remat`` and a VJP each trace a kernel's body again:
PR 35's 56), or while a module is lowered. Their seconds are in the
parent's; the count says how often Python ran a body again. Source:
the program's set-up account."""

from benchmark import setup_account


def read(obs):
    return setup_account.phase(obs, "trace", "nested_events")
