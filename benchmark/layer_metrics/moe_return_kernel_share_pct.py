"""Layer "kernels": of the device time of the expert layer's way between
the row buffer and the tokens (operations whose path carries scope
``moe_combine`` or ``moe_dispatch`` — ``tpfl.parallel.moe``: the sort and
plan, the gather of the tokens' gradients into rows, and the way BACK to
tokens, forward and backward), the share spent in the Pallas kernel that
reads a token tile's runs of rows where they lie, which carries its own
name (``moe_rows_to_tokens``, ``tpfl/parallel/moe_kernel.py``, PR 35). The
counter that says the mechanism engaged: 0 where the way back is the
gather (the parent of PR 35; a shape the kernel does not take). Self time
on the busiest device. None where no operation carries either scope.
Mirrors ``window_attention_kernel_share_pct``. Source: device trace."""

from benchmark import program_trace, scope_paths, trace_reduce

SCOPES = ("moe_combine", "moe_dispatch")
#: The kernel's own name (a program without it reads 0).
KERNEL_PREFIX = "moe_rows_to_tokens"


def read(obs):
    found = program_trace.find_trace(obs)
    if found is None:
        return None
    _, events, paths = found
    plane, rows = scope_paths.busiest_device_rows(events)
    of_name = paths.get(plane, {})
    under_scopes = in_kernel = 0
    for name, self_ns in rows:
        path = of_name.get(name, "")
        if any(scope_paths.carries(path, scope) for scope in SCOPES):
            under_scopes += self_ns
            if trace_reduce.stem(name).startswith(KERNEL_PREFIX):
                in_kernel += self_ns
    return 100.0 * in_kernel / under_scopes if under_scopes else None
