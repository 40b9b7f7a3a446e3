"""Layer "window dispatch": host milliseconds a window costs, the median
over the measured window of (span around ``dispatch_window``) + (span
around ``finalize()``, entered after the outputs are ready). Source:
the benchmark's own spans. Only a driver that handles windows one by
one has these spans."""


def read(obs):
    dispatch = obs["spans"].durations("dispatch")
    finalize = obs["spans"].durations("finalize")
    if not dispatch or len(dispatch) != len(finalize):
        return None
    sums = sorted(d + f for d, f in zip(dispatch, finalize))
    return sums[len(sums) // 2] * 1e3
