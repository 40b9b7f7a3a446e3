"""Layer "round body": model FLOPs of a round (6 x forward multiplies x
samples, recomputation not counted; the count lives in the
configuration's model file) over what the cell's chips could do at
their bf16 peak (``peaks.json``) in the DEVICE time of a round. Source:
device trace. It is a utilisation of busy device time, not of wall
time: idle time is ``device_idle_pct``'s."""


def read(obs):
    trace = obs["trace"]
    if trace is None or not obs["trace_rounds"] or not trace["busy_s_max"]:
        return None
    per_round_s = trace["busy_s_max"] / obs["trace_rounds"]
    peak = obs["chips"] * obs["peaks"]["bf16_flops_per_s"]
    return 100.0 * obs["flops_per_round"] / (per_round_s * peak)
