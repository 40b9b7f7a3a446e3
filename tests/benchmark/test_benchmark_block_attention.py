"""``block_attention_ms_per_round`` (PR 28) on a hand-built trace: the
reader sums the operations that carry the plain scope ``block_attention``
— the forward's bare path and the backward's inside autodiff's wrappers —
and nothing else of the attention layer around them."""

import pytest

from benchmark import cells, scope_paths

D0, OPS, US = "/device:TPU:0", "XLA Ops", 1000
TRAIN = "jit(tpfl_window)/while/body/tpfl.train/vmap()/"
# One device, two rounds. Self times: while.1 400 - (150 + 50) = 200 us.
EVENTS = [
    (D0, OPS, "%fusion.1", 0 * US, 100 * US),    # the forward's score matmul
    (D0, OPS, "%while.1", 100 * US, 400 * US),   # the backward's pair loop
    (D0, OPS, "%fusion.2", 120 * US, 150 * US),  # a pair's dS matmul
    (D0, OPS, "%fusion.3", 300 * US, 50 * US),   # its slice-add into dq
    (D0, OPS, "%fusion.4", 500 * US, 60 * US),   # the q projection
    (D0, OPS, "%fusion.5", 560 * US, 40 * US),   # an MLP
]
PATHS = {D0: {
    "%fusion.1": TRAIN + "diff_attention/block_attention/while/body/dot_general",
    "%while.1": TRAIN + "transpose(jvp(diff_attention))/block_attention/while",
    "%fusion.2": TRAIN + "transpose(jvp(block_attention))/while/body/dot_general",
    "%fusion.3": TRAIN + "transpose(jvp(diff_attention))/block_attention/while/"
                 "body/dynamic_update_slice",
    "%fusion.4": TRAIN + "diff_attention/q_proj/dot_general",
    "%fusion.5": TRAIN + "mlp/block_attention_like/dot_general",
}}


def test_reader_sums_bare_and_transposed_block_attention_paths(monkeypatch):
    monkeypatch.setattr(
        scope_paths.program_trace, "find_trace",
        lambda obs: ("block_attention.xplane.pb", EVENTS, PATHS),
    )
    obs = {"trace": {}, "trace_rounds": 2}
    read = cells.load_reader("block_attention_ms_per_round")
    # (100 + 200 + 150 + 50) us over two rounds; the projection and the
    # MLP (whose path only CONTAINS the name) are not the loop's.
    assert read(obs) == pytest.approx(0.25)
    # The inclusive attention metric keeps reading what it read: the
    # paths with `diff_attention` in them, the projection too.
    assert cells.load_reader("attention_ms_per_round")(obs) == pytest.approx(0.205)
    # A program without the scope (the parent commit's): nothing.
    monkeypatch.setattr(
        scope_paths.program_trace, "find_trace",
        lambda obs: ("parent.xplane.pb", EVENTS, {D0: {
            name: path.replace("block_attention", "while")
            for name, path in PATHS[D0].items()
        }}),
    )
    assert read(obs) is None


def test_reader_is_silent_without_a_trace():
    read = cells.load_reader("block_attention_ms_per_round")
    assert read({"trace": None, "trace_rounds": 0}) is None
    assert read({}) is None
