"""Layer "kernels": of the device time of the block loop INSIDE
compressed convolutional attention (operations whose path carries BOTH
scopes ``cca`` — ``tpfl.models.zaya.ZayaBlock`` — and
``block_attention`` — ``blockwise_attention``'s block loop, forward and
backward), the share spent in the Pallas kernels, which carry their own
names (``block_attention_forward`` / ``block_attention_backward``). The
counter that says the kernels took the shape (2 key heads x 4 query
heads of 128): 0 where the XLA block loop runs it. Self time on the
busiest device. None where no operation carries both scopes. Mirrors
``window_attention_kernel_share_pct``. Source: device trace."""

from benchmark import program_trace, scope_paths, trace_reduce

SCOPES = ("cca", "block_attention")
#: The kernels' own names (given in ``tpfl/parallel/flash_kernel.py``; a
#: program without them reads 0).
KERNEL_PREFIX = "block_attention_"


def read(obs):
    found = program_trace.find_trace(obs)
    if found is None:
        return None
    _, events, paths = found
    plane, rows = scope_paths.busiest_device_rows(events)
    of_name = paths.get(plane, {})
    under_scopes = in_kernels = 0
    for name, self_ns in rows:
        path = of_name.get(name, "")
        if all(scope_paths.carries(path, scope) for scope in SCOPES):
            under_scopes += self_ns
            if trace_reduce.stem(name).startswith(KERNEL_PREFIX):
                in_kernels += self_ns
    return 100.0 * in_kernels / under_scopes if under_scopes else None
