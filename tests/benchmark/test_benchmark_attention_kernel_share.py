"""``attention_kernel_share_pct`` (PR 30) on a hand-built trace: of the self
time under scope ``block_attention``, what the operations named for the
Pallas kernels take; an operation merely NAMED like them outside the scope
counts nowhere, and a program without the scope gives nothing to read."""

import pytest

from benchmark import cells, scope_paths

D0, OPS, US = "/device:TPU:0", "XLA Ops", 1000
TRAIN = "jit(tpfl_window)/while/body/tpfl.train/vmap()/"
# One device. Self times: `loop` 400 - (150 + 50) = 200 us, so the scope
# holds 100 + 200 + 150 + 50 = 500 us whatever the operations are called.
SPANS = [
    ("forward", 0, 100, "diff_attention/block_attention/"),
    ("loop", 100, 400, "transpose(jvp(diff_attention))/block_attention/"),
    ("backward", 120, 150, "transpose(jvp(block_attention))/"),
    ("dq_add", 300, 50, "transpose(jvp(diff_attention))/block_attention/"),
    ("q_proj", 500, 60, "diff_attention/q_proj/"),
    # Named like a kernel, under another scope: counts nowhere.
    ("stray", 560, 30, "mlp/"),
]
XLA_LOOP = {
    "forward": "%fusion.1", "loop": "%while.1", "backward": "%fusion.2",
    "dq_add": "%fusion.3", "q_proj": "%fusion.4",
    "stray": "%block_attention_forward.9",
}
KERNELS = dict(
    XLA_LOOP,
    forward="%block_attention_forward.1", backward="%block_attention_backward.4",
)


def _trace(names, with_scope=True):
    events = [(D0, OPS, names[op], t * US, dur * US) for op, t, dur, _ in SPANS]
    paths = {D0: {
        names[op]: TRAIN + (path if with_scope else path.replace("block_", "b_"))
        for op, _, _, path in SPANS
    }}
    return "kernels.xplane.pb", events, paths


@pytest.mark.parametrize(
    "names, share",
    [
        # The block loop as two Pallas kernels and a little glue (delta).
        pytest.param(KERNELS, 100 * (100 + 150) / 500, id="kernels"),
        # The XLA block loop (the parent commit; a band): no kernel.
        pytest.param(XLA_LOOP, 0.0, id="xla_loop"),
    ],
)
def test_kernel_share_reads_the_kernels_own_names_under_the_scope(
    monkeypatch, names, share
):
    monkeypatch.setattr(
        scope_paths.program_trace, "find_trace", lambda obs: _trace(names)
    )
    read = cells.load_reader("attention_kernel_share_pct")
    assert read({"trace": {}, "trace_rounds": 2}) == pytest.approx(share)


def test_kernel_share_is_silent_without_the_scope_or_a_trace(monkeypatch):
    read = cells.load_reader("attention_kernel_share_pct")
    assert read({"trace": None, "trace_rounds": 0}) is None
    assert read({}) is None
    # A program without the scope: nothing to read, not 0.
    monkeypatch.setattr(
        scope_paths.program_trace, "find_trace",
        lambda obs: _trace(KERNELS, with_scope=False),
    )
    assert read({"trace": {}, "trace_rounds": 2}) is None
