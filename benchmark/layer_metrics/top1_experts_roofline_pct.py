"""Layer "kernels": the experts' share of their COMPUTE roofline — the
operations of the three grouped products over the rows routed to the
held experts under balanced routing (half the tokens: one choice a
token, half the experts held), forward and backward
(``expert_flops_per_round`` in the configuration's model file: the same
work whatever implements the products; what recomputation repeats is not
counted) over the chip's bf16 peak (``peaks.json``), divided by the
measured time (``top1_experts_ms_per_round``). Compute-bound: at 1,024
rows an expert the products run at ~500 operations a weight byte. The
count leaves work out, never adds any, so the share cannot pass 100%
under balanced routing. Source: device trace. Listed for
``zaya1_silo_8k`` only, whose files give the shapes."""

from benchmark import cells, scope_paths

CELL = "zaya1_silo_8k"


def read(obs):
    table = scope_paths.scope_ms_per_round(obs, "moe_experts")
    if table is None or not table["moe_experts"]:
        return None
    cell = cells.load_cell(CELL)
    flops = cell.model.expert_flops_per_round(cell.config, cell.traffic)
    least_ms = 1e3 * flops / obs["peaks"]["bf16_flops_per_s"]
    return 100.0 * least_ms / table["moe_experts"]
