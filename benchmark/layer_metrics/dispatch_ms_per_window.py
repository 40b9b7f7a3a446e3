"""Layer "window dispatch": median milliseconds of the engine's own span
``tpfl:dispatch`` (the whole of ``dispatch_window``: argument
preparation, program lookup, the jitted call) over the traced slice.
Source: the program's span, on the profiler's clock."""

from benchmark import program_trace


def read(obs):
    return program_trace.span_median_ms(obs, "dispatch")
