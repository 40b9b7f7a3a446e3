"""``moe_return_kernel_share_pct`` (PR 35) on a hand-built trace: of the
self time of operations whose path carries scope ``moe_combine`` or
``moe_dispatch``, what the operations named for the Pallas kernel
``moe_rows_to_tokens`` take. The experts' grouped products (scope
``moe_experts``), the router and an operation merely named like the kernel
outside both scopes count nowhere; a program with neither scope gives
nothing to read."""

import pytest

from benchmark import cells, scope_paths

D0, OPS, US = "/device:TPU:0", "XLA Ops", 1000
TRAIN = "jit(tpfl_window)/while/body/tpfl.train/vmap()/"
MOE = "layer_0/moe/"
MOE_T = "transpose(jvp(layer_0))/moe/"
# One device. Self times: `plan` 300 - (120 + 30) = 150 us, so the two
# scopes hold 100 + 150 + 120 + 30 + 60 = 460 us whatever their operations
# are called.
SPANS = [
    ("back", 0, 100, MOE + "moe_combine/"),
    ("plan", 100, 300, MOE_T + "moe_dispatch/"),
    ("back_t", 120, 120, MOE_T + "moe_dispatch/"),
    ("d_gate", 300, 30, MOE_T + "moe_dispatch/"),
    ("dy_rows", 400, 60, MOE_T + "moe_combine/"),
    # The grouped products and the router: the layer's other scopes.
    ("gmm", 500, 200, MOE + "moe_experts/"),
    ("router", 700, 40, MOE + "moe_router/"),
    # Named like the kernel, under neither scope: counts nowhere.
    ("stray", 740, 30, MOE + "moe_experts/"),
]
GATHER = {
    "back": "%fusion.1", "plan": "%fusion.2", "back_t": "%fusion.3",
    "d_gate": "%fusion.4", "dy_rows": "%fusion.5", "gmm": "%gmm.1",
    "router": "%fusion.6", "stray": "%moe_rows_to_tokens.9",
}
KERNEL = dict(
    GATHER, back="%moe_rows_to_tokens.1", back_t="%moe_rows_to_tokens.2"
)


def _trace(names, scope="moe_"):
    events = [(D0, OPS, names[op], t * US, dur * US) for op, t, dur, _ in SPANS]
    paths = {D0: {
        names[op]: TRAIN + path.replace("moe_", scope)
        for op, _, _, path in SPANS
    }}
    return "experts.xplane.pb", events, paths


@pytest.mark.parametrize(
    "names, share",
    [
        # The way back as the kernel, forward and backward, beside the
        # plan, the gates' gather and the rows' gather of dy.
        pytest.param(KERNEL, 100 * (100 + 120) / 460, id="kernel"),
        # The gather (the parent of PR 35): the scopes without the name.
        pytest.param(GATHER, 0.0, id="gather"),
    ],
)
def test_return_share_reads_the_kernels_name_under_either_scope(
    monkeypatch, names, share
):
    monkeypatch.setattr(
        scope_paths.program_trace, "find_trace", lambda obs: _trace(names)
    )
    read = cells.load_reader("moe_return_kernel_share_pct")
    assert read({"trace": {}, "trace_rounds": 2}) == pytest.approx(share)


def test_return_share_is_silent_without_the_scopes_or_a_trace(monkeypatch):
    read = cells.load_reader("moe_return_kernel_share_pct")
    assert read({"trace": None, "trace_rounds": 0}) is None
    assert read({}) is None
    # A program with no expert layer (GPT-2, SambaY, ResNet): nothing to
    # read, not 0.
    monkeypatch.setattr(
        scope_paths.program_trace, "find_trace",
        lambda obs: _trace(KERNEL, scope="mlp_"),
    )
    assert read({"trace": {}, "trace_rounds": 2}) is None


def test_return_share_is_listed_for_the_cells_with_expert_layers():
    entry = next(
        m for m in cells.load_benchmark()["per_layer"]
        if m["name"] == "moe_return_kernel_share_pct"
    )
    assert entry["workloads"] == ["mellum2_silo_8k", "zaya1_silo_8k"]
    assert (entry["layer"], entry["moves"]) == ("kernels", "rounds_per_s")
    assert (entry["source"], entry["better"]) == ("device_trace", "higher")
