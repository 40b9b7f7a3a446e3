"""Layer "kernels": device milliseconds a round in compressed
convolutional attention (scope ``cca`` of
``tpfl.models.zaya.ZayaBlock``: the norm, the latent projections and the
projection back (``cca_proj``), the causal convolutions, q-k mean,
normalisation and value shift (``cca_mix``), the partial rotary table
(``rope``) and the attention block loop (``block_attention``) — forward,
recomputation and backward), busiest device. Source: device trace, by
named scope."""

from benchmark import scope_paths


def read(obs):
    table = scope_paths.scope_ms_per_round(obs, "cca")
    return None if table is None else table["cca"]
