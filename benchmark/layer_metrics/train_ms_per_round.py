"""Layer "round body": device milliseconds a round under the scope
``tpfl.train`` — the vmapped local training, its optimizer step
(``tpfl.optimizer``, nested in it) included — on the busiest device.
Source: device trace, by the program's own named scopes."""

from benchmark import program_trace


def read(obs):
    return program_trace.scope_ms_per_round(obs, "tpfl.train", "tpfl.optimizer")
