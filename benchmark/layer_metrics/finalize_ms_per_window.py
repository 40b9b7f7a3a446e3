"""Layer "window dispatch": median milliseconds of the engine's own span
``tpfl:finalize`` (``EngineWindow.finalize``: the telemetry carry's fetch
and the ``replay_window`` fan-out where telemetry is on) over the traced
slice. Source: the program's span, on the profiler's clock."""

from benchmark import program_trace


def read(obs):
    return program_trace.span_median_ms(obs, "finalize")
