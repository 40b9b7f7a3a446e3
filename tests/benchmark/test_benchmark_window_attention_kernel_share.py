"""``window_attention_kernel_share_pct`` (PR 33) on a hand-built trace: of
the self time of operations whose path carries BOTH scopes
``window_attention`` and ``block_attention``, what the operations named for
the Pallas kernels take. The full layer's kernels (scope
``full_attention``), the banded layers' projections and an operation
merely named like a kernel count nowhere; a program without both scopes
on one operation gives nothing to read."""

import pytest

from benchmark import cells, scope_paths

D0, OPS, US = "/device:TPU:0", "XLA Ops", 1000
TRAIN = "jit(tpfl_window)/while/body/tpfl.train/vmap()/"
BAND = "layer_0/window_attention/attention/block_attention/"
BAND_T = "transpose(jvp(layer_0))/window_attention/attention/block_attention/"
# One device. Self times: `loop` 400 - (150 + 50) = 200 us, so the banded
# block loop holds 100 + 200 + 150 + 50 = 500 us whatever its operations
# are called.
SPANS = [
    ("forward", 0, 100, BAND),
    ("loop", 100, 400, BAND_T),
    ("backward", 120, 150, BAND_T),
    ("dq_add", 300, 50, BAND_T),
    ("q_proj", 500, 60, "layer_0/window_attention/attention/q_proj/"),
    # The full layer's kernels: the block loop's scope without the band's.
    ("full", 560, 80, "layer_3/full_attention/attention/block_attention/"),
    # Named like a kernel, under the band's scope alone: counts nowhere.
    ("stray", 640, 30, "layer_0/window_attention/attention/rope/"),
]
XLA_LOOP = {
    "forward": "%fusion.1", "loop": "%while.1", "backward": "%fusion.2",
    "dq_add": "%fusion.3", "q_proj": "%fusion.4",
    "full": "%block_attention_forward.7",
    "stray": "%block_attention_forward.9",
}
KERNELS = dict(
    XLA_LOOP,
    forward="%block_attention_forward.1", backward="%block_attention_backward.4",
)


def _trace(names, band="window_attention"):
    events = [(D0, OPS, names[op], t * US, dur * US) for op, t, dur, _ in SPANS]
    paths = {D0: {
        names[op]: TRAIN + path.replace("window_attention", band)
        for op, _, _, path in SPANS
    }}
    return "band.xplane.pb", events, paths


@pytest.mark.parametrize(
    "names, share",
    [
        # The band inside the kernels, and a little glue (delta).
        pytest.param(KERNELS, 100 * (100 + 150) / 500, id="kernels"),
        # The XLA block loop (the parent of PR 33): no kernel under the band.
        pytest.param(XLA_LOOP, 0.0, id="xla_loop"),
    ],
)
def test_band_share_reads_the_kernels_names_under_both_scopes(
    monkeypatch, names, share
):
    monkeypatch.setattr(
        scope_paths.program_trace, "find_trace", lambda obs: _trace(names)
    )
    read = cells.load_reader("window_attention_kernel_share_pct")
    assert read({"trace": {}, "trace_rounds": 2}) == pytest.approx(share)


def test_band_share_is_silent_without_both_scopes_or_a_trace(monkeypatch):
    read = cells.load_reader("window_attention_kernel_share_pct")
    assert read({"trace": None, "trace_rounds": 0}) is None
    assert read({}) is None
    # A program with no banded layer (GPT-2, SambaY's cell): the block
    # loop's scope alone is nothing to read, not 0.
    monkeypatch.setattr(
        scope_paths.program_trace, "find_trace",
        lambda obs: _trace(KERNELS, band="diff_attention"),
    )
    assert read({"trace": {}, "trace_rounds": 2}) is None


def test_band_share_is_listed_for_the_cell_with_banded_layers():
    entry = next(
        m for m in cells.load_benchmark()["per_layer"]
        if m["name"] == "window_attention_kernel_share_pct"
    )
    assert entry["workloads"] == ["mellum2_silo_8k"]
    assert (entry["layer"], entry["moves"]) == ("kernels", "rounds_per_s")
