"""``benchmark/program_trace.py`` on a hand-built event list: device time
by the program's named scopes, host time by the engine's own spans, and
the readers built on them. Every number can be checked by eye."""

import json
import os

import pytest

from benchmark import cells, program_trace as pt, trace_reduce as tr

D0, D1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
OPS = "XLA Ops"
US = 1000  # the list is written in microseconds


def ev(plane, line, name, start_us, dur_us):
    return (plane, line, name, start_us * US, dur_us * US)


# Device 0, one round, 0..300 us:
#   while.1     0..200  the ROUND loop's own event: no tpfl.* in its path
#     fusion.1    0..80   forward/backward        (tpfl.train)
#     while.2    80..140  a nested loop of the training, one op inside:
#       fusion.2   90..130                         (tpfl.train)
#     fusion.3  140..190  the optimizer step       (tpfl.optimizer in tpfl.train)
#   fusion.4  200..230  the codec                  (tpfl.codec)
#   copy.5    230..240  a compiler-made copy: no path at all
#   idle      240..260
#   fusion.6  260..300  the fold                   (tpfl.fold)
# Self times: while.1 200-(80+60+50)=10, while.2 60-40=20.
DEVICE0_OPS = [
    ev(D0, OPS, "%while.1", 0, 200),
    ev(D0, OPS, "%fusion.1", 0, 80),
    ev(D0, OPS, "%while.2", 80, 60),
    ev(D0, OPS, "%fusion.2", 90, 40),
    ev(D0, OPS, "%fusion.3", 140, 50),
    ev(D0, OPS, "%fusion.4", 200, 30),
    ev(D0, OPS, "%copy.5", 230, 10),
    ev(D0, OPS, "%fusion.6", 260, 40),
    # The same time again under another heading: never an operation.
    ev(D0, "XLA Modules", "jit_tpfl_window", 0, 300),
]
# Device 1 is busier (350 us), all of it training.
DEVICE1 = [ev(D1, OPS, "%fusion.9", 0, 350)]
PATHS = {
    D0: {
        "%while.1": "jit(tpfl_window)/while",
        "%fusion.1": "jit(tpfl_window)/while/body/tpfl.train/vmap()/conv",
        "%while.2": "jit(tpfl_window)/while/body/tpfl.train/vmap()/while",
        "%fusion.2": "jit(tpfl_window)/tpfl.train/broadcast_in_dim;tpfl.train",
        # Nested scopes: the innermost (last) one wins.
        "%fusion.3": "jit(tpfl_window)/tpfl.train/vmap()/while/body/tpfl.optimizer/mul:",
        "%fusion.4": "jit(tpfl_window)/while/body/tpfl.codec/vmap()/round",
        "%fusion.6": "jit(tpfl_window)/while/body/tpfl.fold/dot_general",
    },
    D1: {"%fusion.9": "jit(tpfl_window)/while/body/tpfl.train/vmap()/dot_general"},
}
# Host: two windows' spans. The idle 240..260 us of device 0 lies 10 us
# under finalize (inside pipeline_window) and 10 us under nothing.
HOST_SPANS = [
    ev(HOST, "python3", "tpfl:pipeline_window", 0, 250),
    ev(HOST, "python3", "tpfl:dispatch", 10, 30),
    ev(HOST, "python3", "tpfl:prepare_args", 12, 8),
    ev(HOST, "python3", "tpfl:finalize", 100, 150),
    ev(HOST, "python3", "tpfl:pipeline_window", 400, 100),
    ev(HOST, "python3", "tpfl:dispatch", 410, 50),
    ev(HOST, "python3", "tpfl:dispatch", 470, 10),
    ev(HOST, "python3", "bench:pipeline_run", 0, 500),  # the benchmark's: not ours
    ev(D0, "XLA TraceMe", "tpfl:dispatch", 0, 300),  # a device plane: never a host span
]
EVENTS = DEVICE0_OPS + DEVICE1 + HOST_SPANS


def test_scope_self_times_innermost_scope_two_devices_and_no_scope():
    table = pt.scope_self_times(EVENTS, PATHS)
    assert table[D0] == {
        "unscoped": (10 + 10) * US,  # the round loop itself, and copy.5
        "tpfl.train": (80 + 20 + 40) * US,
        "tpfl.optimizer": 50 * US,  # nested in tpfl.train: the inner one
        "tpfl.codec": 30 * US,
        "tpfl.fold": 40 * US,
    }
    # Everything is counted once: the sum is the union of the operations.
    assert sum(table[D0].values()) == 280 * US
    assert table[D1] == {"tpfl.train": 350 * US}
    # A program that names nothing: all of it unscoped.
    assert pt.scope_self_times(EVENTS, {})[D1] == {"unscoped": 350 * US}
    assert pt.scope_of_path("jit(f)/my_tpfl.train_like/mul") is None
    assert pt.scope_of_path("tpfl.fold") == "tpfl.fold"


XSPACE = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 8000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8] fusion(...)"
    stats { metadata_id: 8 int64_value: 12 }
    stats { metadata_id: 7 str_value: "jit(tpfl_window)/while/body/tpfl.train/vmap()/dot_general:" } } }
  event_metadata { key: 2 value { id: 2 name: "%copy.2 = f32[8] copy(...)"
    stats { metadata_id: 7 ref_value: 9 } } }
  event_metadata { key: 3 value { id: 3 name: "%copy-start.3 = f32[8] copy-start(...)" } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8 value { id: 8 name: "flops" } }
  stat_metadata { key: 9 value { id: 9 name: "jit(tpfl_window)/tpfl.fold/broadcast_in_dim:" } }
}
planes {
  name: "/host:CPU"
  lines { name: "python3"
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "tpfl:dispatch"
    stats { metadata_id: 7 str_value: "tpfl.train" } } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
}
"""


def test_op_paths_are_read_from_the_event_metadata(tmp_path):
    """On the v5e the path is the stat ``tf_op`` of an operation's EVENT
    METADATA (a string, or a reference to a stat name), which
    ``ProfileData`` does not list: read off the wire format, here from a
    file the profiler's own serializer wrote."""
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    paths = pt.op_paths(str(path))
    assert paths == {  # device planes only; an event with no path: no entry
        "/device:TPU:0": {
            "%fusion.1 = f32[8] fusion(...)":
                "jit(tpfl_window)/while/body/tpfl.train/vmap()/dot_general:",
            "%copy.2 = f32[8] copy(...)": "jit(tpfl_window)/tpfl.fold/broadcast_in_dim:",
        },
    }
    assert pt.scope_self_times(tr.load_events(str(path)), paths) == {
        "/device:TPU:0": {"tpfl.train": 5000, "tpfl.fold": 3000, "unscoped": 1000},
    }


def test_program_spans_and_idle_time_by_innermost_span():
    spans = pt.program_spans(EVENTS)
    assert sorted(spans) == ["dispatch", "finalize", "pipeline_window", "prepare_args"]
    assert spans["dispatch"] == [
        (10 * US, 40 * US), (410 * US, 460 * US), (470 * US, 480 * US),
    ]
    # Device 0 idles 240..260: 240..250 under finalize (innermost of
    # finalize and pipeline_window), 250..260 under no span of ours.
    # Device 1 never idles.
    assert pt.gaps_by_program_span(EVENTS) == {
        "finalize": 10 * US, "outside_program": 10 * US,
    }


def test_tables_are_the_busiest_devices_per_round():
    tables = pt.analyse_events(EVENTS, PATHS, rounds=2)
    assert tables["busiest_device"] == D1 and tables["devices"] == 2
    assert tables["scope_ms_per_round"] == {"tpfl.train": 0.175}
    assert tables["spans"]["dispatch"] == {
        "count": 3, "median_ms": 0.03, "total_ms": pytest.approx(0.09),
    }
    assert tables["idle_ms_by_span"] == {"finalize": 0.005, "outside_program": 0.005}


def _obs(events, rounds=1):
    return {"trace": tr.reduce_trace(events), "trace_rounds": rounds}


@pytest.fixture
def trace_on_disk(tmp_path, monkeypatch):
    """A stand-in for the file the harness wrote: ``find_trace`` looks
    for the newest xplane and loads its events and their paths; all
    three are pointed at hand-built ones."""
    xplane = tmp_path / "trace" / "plugins" / "profile" / "t0" / "x.xplane.pb"
    xplane.parent.mkdir(parents=True)
    xplane.write_bytes(b"")
    held = {"events": EVENTS, "paths": PATHS, "writes": 0}

    def write(events, paths=PATHS):
        """A new trace in the file's place (a newer file, as on disk)."""
        held.update(events=events, paths=paths, writes=held["writes"] + 1)
        os.utime(xplane, (held["writes"], held["writes"]))

    monkeypatch.setattr(pt, "_newest_xplane", lambda: str(xplane))
    monkeypatch.setattr(tr, "load_events", lambda path: held["events"])
    monkeypatch.setattr(pt, "op_paths", lambda path: held["paths"])
    monkeypatch.setattr(pt, "_held", {})
    return write, tmp_path / "trace"


def test_find_trace_takes_only_the_trace_the_reduction_came_from(trace_on_disk):
    write, _ = trace_on_disk
    path, events, paths = pt.find_trace(_obs(EVENTS))
    assert events is EVENTS and paths is PATHS and path.endswith("x.xplane.pb")
    # Another run's trace (its reduction differs): nothing to read.
    assert pt.find_trace(_obs(DEVICE0_OPS)) is None
    assert pt.find_trace({"trace": None, "trace_rounds": 1}) is None
    write(HOST_SPANS[:3])  # a trace with no device in it
    assert pt.find_trace(_obs(EVENTS)) is None


def test_every_new_reader_on_the_hand_built_trace(trace_on_disk):
    write, trace_dir = trace_on_disk
    one_device = DEVICE0_OPS + HOST_SPANS
    write(one_device)
    obs = _obs(one_device, rounds=2)
    want = {
        "train_ms_per_round": 0.095,  # (140 + 50) us over two rounds
        "optimizer_ms_per_round": 0.025,
        "codec_ms_per_round": 0.015,
        "telemetry_ms_per_round": 0.0,  # named legs, none of them this
        "fold_ms_per_round": 0.020,
        "unscoped_device_pct": 100 * 20 / 280,
        "dispatch_ms_per_window": 0.03,
        "finalize_ms_per_window": 0.15,
    }
    for metric, value in want.items():
        assert cells.load_reader(metric)(obs) == pytest.approx(value), metric
    # The legs add up to the device time of a round.
    legs = sum(want[m] for m in (
        "train_ms_per_round", "codec_ms_per_round", "telemetry_ms_per_round",
        "fold_ms_per_round",
    )) + 0.280 / 2 * want["unscoped_device_pct"] / 100
    assert legs == pytest.approx(
        cells.load_reader("device_ms_per_round")(obs)
    )
    with open(trace_dir / pt.TABLE_FILE) as f:
        assert json.load(f)["scope_ms_per_round"]["tpfl.codec"] == 0.015


def test_new_readers_return_nothing_with_nothing_to_read(trace_on_disk):
    write, _ = trace_on_disk
    new = [
        "train_ms_per_round", "optimizer_ms_per_round", "codec_ms_per_round",
        "telemetry_ms_per_round", "fold_ms_per_round", "unscoped_device_pct",
        "dispatch_ms_per_window", "finalize_ms_per_window",
    ]
    # A program from before the scopes and spans (the parent commit's):
    # operations, but none of the program's names.
    write(DEVICE0_OPS, paths={})
    for metric in new:
        assert cells.load_reader(metric)(_obs(DEVICE0_OPS)) is None, metric
    # No trace at all, and a trace that is not this run's.
    write(EVENTS)
    for metric in new:
        assert cells.load_reader(metric)({"trace": None, "trace_rounds": 3}) is None
        assert cells.load_reader(metric)(_obs(DEVICE0_OPS)) is None, metric
