"""Layer "kernels": the selective scan's share of its BYTES roofline —
the least bytes it has to move in a round (every input read once, every
output written once, forward and backward: ``scan_min_bytes_per_round``
in the configuration's model file) over the chip's HBM bandwidth
(``peaks.json``), divided by its measured time (``ssm_scan_ms_per_round``).
Bytes-bound: the scan has no matmul. The byte count is a lower bound, so
the share cannot pass 100%. Source: device trace. Listed for
``phi4flash_silo_8k`` only, whose files give the shapes."""

from benchmark import cells, scope_paths

CELL = "phi4flash_silo_8k"


def read(obs):
    table = scope_paths.scope_ms_per_round(obs, "ssm_scan")
    if table is None or not table["ssm_scan"]:
        return None
    cell = cells.load_cell(CELL)
    least_bytes = cell.model.scan_min_bytes_per_round(cell.config, cell.traffic)
    least_ms = 1e3 * least_bytes / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_ms / table["ssm_scan"]
