"""Layer "kernels": device milliseconds a round in what compressed
convolutional attention does to its latents BETWEEN the projections and
the softmax (scope ``cca_mix`` of ``tpfl.models.zaya.ZayaCCA``: the
depthwise and the per-head causal convolutions along the sequence, the
q-k mean across grouped heads, the normalisation of q and k with the key
temperature, and the value shift — forward, recomputation and backward),
busiest device. Work plain grouped-query attention has none of. Source:
device trace, by named scope."""

from benchmark import scope_paths


def read(obs):
    table = scope_paths.scope_ms_per_round(obs, "cca_mix")
    return None if table is None else table["cca_mix"]
