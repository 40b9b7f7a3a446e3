"""Layer "round body": device milliseconds per federated round — the
union of the operation intervals on the busiest device in the traced
slice, over the rounds that ran in it. Source: device trace."""


def read(obs):
    trace = obs["trace"]
    if trace is None or not obs["trace_rounds"]:
        return None
    return trace["busy_s_max"] * 1e3 / obs["trace_rounds"]
