"""Layer "exchange and fold across chips": milliseconds per round in
which a collective (all-reduce, all-gather, reduce-scatter,
collective-permute) is in flight on the device that spends most in
them. Source: device trace. A one-chip program has none."""


def read(obs):
    trace = obs["trace"]
    if trace is None or not obs["trace_rounds"] or not trace["collective_s"]:
        return None
    return trace["collective_s"] * 1e3 / obs["trace_rounds"]
