"""Layer "round body": share of the busiest device's operation time that
lies under none of the program's ``tpfl.*`` scopes — what the split into
train / codec / telemetry / fold leaves unexplained. Source: device
trace, by named scope."""

from benchmark import program_trace


def read(obs):
    return program_trace.unscoped_pct(obs)
