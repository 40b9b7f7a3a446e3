"""Layer "exchange and fold across chips": share of the collective time
during which no other operation runs on the same device — the part no
compute hides. Source: device trace."""


def read(obs):
    trace = obs["trace"]
    if trace is None or not trace["collective_s"]:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["collective_s"]
