"""``benchmark/trace_reduce.py`` on a hand-built event list: every
number below can be checked by eye against the list."""

import pytest

from benchmark import trace_reduce as tr

D0, D1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
US = 1000  # the list is written in microseconds


def ev(plane, line, name, start_us, dur_us):
    return (plane, line, name, start_us * US, dur_us * US)


# Device 0: a 100 us ``while`` holding a 40 us convolution fusion, a
# 20 us all-reduce (10 us of it under nothing, see below) and a 30 us
# fusion; then a 50 us gap; then a 50 us copy. Traced slice 0..200 us,
# busy 150 us.
EVENTS = [
    ev(D0, "XLA Ops", "%while.1", 0, 100),
    ev(D0, "XLA Ops", "%convolution_fusion.3", 0, 40),
    ev(D0, "XLA Ops", "%all-reduce-start.1", 40, 5),
    ev(D0, "XLA Ops", "%fusion.7", 45, 30),
    ev(D0, "XLA Ops", "%all-reduce-done.1", 75, 15),
    ev(D0, "XLA Ops", "%copy.2", 150, 50),
    # The same time again under other headings: never counted.
    ev(D0, "XLA Modules", "jit_multi", 0, 200),
    ev(D0, "Steps", "0", 0, 200),
    # Device 1: busy 100 of its 100 us; no collective.
    ev(D1, "XLA Ops", "%fusion.7", 20, 100),
    # Host: the gap 100..150 us lies 30 us under finalize, 20 us under
    # nothing but the enclosing run span.
    ev(HOST, "python", "bench:run", 0, 200),
    ev(HOST, "python", "bench:finalize", 100, 30),
    ev(HOST, "python", "unrelated", 0, 500),
]


def test_interval_arithmetic():
    merged = tr.merge([(5, 10), (0, 3), (2, 4), (10, 12), (7, 7)])
    assert merged == [(0, 4), (5, 12)]
    assert tr.total(merged) == 11
    assert tr.intersect(merged, [(3, 6), (11, 20)]) == [(3, 4), (5, 6), (11, 12)]
    assert tr.subtract(merged, [(1, 2), (3, 8)]) == [(0, 1), (2, 3), (8, 12)]
    assert tr.subtract([(0, 10)], []) == [(0, 10)]


@pytest.mark.parametrize(
    "name, expected",
    [
        ("%fusion.12", "fusion"),
        ("all-reduce-start.3", "all-reduce-start"),
        ("%convolution_add_fusion.2.clone", "convolution_add_fusion"),
        ("copy", "copy"),
        ("1234", "1234"),
    ],
)
def test_stem(name, expected):
    assert tr.stem(name) == expected


def test_self_time_subtracts_nested_operations():
    ops = tr.device_ops(EVENTS)[D0]
    timed = {name: (ns, parent) for name, ns, parent in tr.self_times(ops)}
    # while: 100 us less its four children (40 + 5 + 30 + 15).
    assert timed["%while.1"] == (10 * US, True)
    assert timed["%convolution_fusion.3"] == (40 * US, False)
    assert timed["%copy.2"] == (50 * US, False)


def test_async_collective_runs_from_start_to_done():
    ops = tr.device_ops(EVENTS)[D0]
    assert tr.collective_intervals(ops) == [(40 * US, 90 * US)]
    assert tr.collective_intervals(tr.device_ops(EVENTS)[D1]) == []


def test_reduce_trace_numbers():
    out = tr.reduce_trace(EVENTS)
    assert out["devices"] == 2
    assert out["busy_s"] == pytest.approx((150 + 100) / 2 * 1e-6)
    assert out["busy_s_max"] == pytest.approx(150e-6)
    assert out["window_s"] == pytest.approx(200e-6)
    # In flight 40..90 us; fusion.7 hides 45..75: 20 us exposed.
    assert out["collective_s"] == pytest.approx(50e-6)
    assert out["collective_exposed_s"] == pytest.approx(20e-6)
    groups = dict(out["device_ops"])
    # Mean over the two devices of the self times, by stem.
    assert groups["fusion"] == pytest.approx((30 + 100) / 2 * 1e-6)
    assert groups["convolution_fusion"] == pytest.approx(40 / 2 * 1e-6)
    assert groups["while"] == pytest.approx(10 / 2 * 1e-6)
    assert groups["all-reduce-done"] == pytest.approx(15 / 2 * 1e-6)
    gaps = dict(out["idle_gaps"])
    assert gaps == {
        "finalize": pytest.approx(30 / 2 * 1e-6),
        "run": pytest.approx(20 / 2 * 1e-6),
    }
    assert out["span_counts"] == {"run": 1, "finalize": 1}


def test_gap_outside_every_span_is_named_so():
    gaps = tr.attribute_gaps([(0, 10)], {"dispatch": [(2, 5)]})
    assert gaps == {"dispatch": 3, tr.OUTSIDE_SPANS: 7}


def test_no_device_operation_reduces_to_nothing():
    host_only = [e for e in EVENTS if e[0] == HOST]
    assert tr.reduce_trace(host_only) is None
    assert tr.device_ops(host_only) == {}


def test_plane_without_an_op_line_takes_every_line_but_the_headings():
    events = [
        ev(D0, "stream 1", "fusion.1", 0, 10),
        ev(D0, "Steps", "0", 0, 50),
    ]
    assert tr.device_ops(events) == {D0: [("fusion.1", 0, 10 * US)]}


def test_inventory_lists_lines_with_counts_and_seconds():
    rows = {(p, l): (n, s) for p, l, n, s in tr.inventory(EVENTS)}
    assert rows[(D0, "XLA Ops")] == (6, pytest.approx(240e-6))
    assert rows[(HOST, "python")][0] == 3


def test_load_events_reads_a_real_profile(tmp_path):
    """The one function that touches the profiler's format: a CPU
    profile has no device plane, but its host spans must be found."""
    import jax
    import jax.numpy as jnp

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("bench:dispatch"):
            jnp.ones((8, 8)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    events = tr.load_events(tr.find_xplane(str(tmp_path)))
    spans = tr.host_spans(events)
    assert list(spans) == ["dispatch"] and len(spans["dispatch"]) == 1
    start, end = spans["dispatch"][0]
    assert end > start
