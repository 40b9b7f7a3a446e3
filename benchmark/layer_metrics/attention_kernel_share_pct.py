"""Layer "kernels": of the device time under scope ``block_attention``
(``blockwise_attention``'s block loop, forward and backward: what
``block_attention_ms_per_round`` reads), the share spent in the Pallas
kernels that run it on a TPU, which carry their own names
(``block_attention_forward`` / ``block_attention_backward``, as
``ssm_scan_forward`` appears in ``breakdown.device_ops``). Self time on
the busiest device. 0 where the XLA block loop runs (the parent commit; a
band); under ~90 where glue around the kernels has crept back in. None
where no operation carries the scope. Source: device trace."""

from benchmark import program_trace, scope_paths, trace_reduce

SCOPE = "block_attention"
#: The kernels' own names begin with the scope's (the names are given in
#: ``tpfl/parallel/flash_kernel.py``; a program without them reads 0).
KERNEL_PREFIX = "block_attention_"


def read(obs):
    found = program_trace.find_trace(obs)
    if found is None:
        return None
    _, events, paths = found
    plane, rows = scope_paths.busiest_device_rows(events)
    of_name = paths.get(plane, {})
    under_scope = in_kernels = 0
    for name, self_ns in rows:
        if scope_paths.carries(of_name.get(name, ""), SCOPE):
            under_scope += self_ns
            if trace_reduce.stem(name).startswith(KERNEL_PREFIX):
                in_kernels += self_ns
    return 100.0 * in_kernels / under_scope if under_scope else None
