"""Layer "entry": seconds of set-up spent TRACING — Python run under
``jax.jit`` to a jaxpr, every program of the process, the callers' own
jits (state, data, placement) beside the engine's window programs.
Outermost events only: a jitted function traced inside another trace
(or inside a lowering) is in its parent's seconds, not here again. Paid
by every process, whether the persistent cache is warm or cold.
Source: the program's set-up account (``jax.monitoring``'s
``jaxpr_trace_duration``)."""

from benchmark import setup_account


def read(obs):
    return setup_account.phase(obs, "trace")
