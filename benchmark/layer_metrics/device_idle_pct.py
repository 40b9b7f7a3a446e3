"""Layer "device": share of the traced slice in which no operation ran
on the device (mean over the cell's devices). Source: device trace."""


def read(obs):
    trace = obs["trace"]
    if trace is None or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
