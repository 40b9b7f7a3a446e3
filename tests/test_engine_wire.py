"""Device-side wire codecs + donated fused train+fold (ISSUE 13).

Pins the tentpole's contracts over ``tpfl/parallel/engine.py`` and
``tpfl/learning/compression.py``:

(a) cache-key hygiene — ``ENGINE_WIRE_CODEC="dense"`` lowers the
    byte-identical pre-codec round program (HLO digest stable across a
    codec toggle; the program-cache key splits on codec, top-k
    fraction and donation mode), and the quant8/topk variants lower
    DIFFERENT programs;
(b) codec math parity — the in-program per-leaf round-trip
    (``engine_codec_roundtrip``) equals the host payload path
    (``_encode_leaf``/``_decode_leaf``) bit-for-bit, across dtypes;
(c) quantized-gossip federation runs stay within a gated loss delta
    of dense at 1 and 8 devices, deterministically;
(d) the telemetry carry's ``wire_bytes`` row is the device-side
    bytes/round accounting (participation x per-model codec bytes,
    same per-leaf policy as the host payload path) and reaches the
    ``tpfl_engine_wire_bytes`` registry series;
(e) donation — the donating program's outputs are byte-identical to
    ``donate=False`` at 1 and 8 devices, and the compiled-HLO
    donation inspection (``donation_report``/``donation_analysis``)
    is clean: every donated state leaf aliases an output buffer.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpfl.learning import compression
from tpfl.management.telemetry import metrics
from tpfl.models import MLP
from tpfl.parallel import FederationEngine, create_mesh
from tpfl.parallel.engine import donation_analysis
from tpfl.settings import Settings


def _mlp():
    return MLP(hidden_sizes=(16,), compute_dtype=jnp.float32)


def _data(n, nb=1, bs=4, seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.random((n, nb, bs, 28, 28)).astype(np.float32)
    ys = rng.integers(0, 10, (n, nb, bs)).astype(np.int32)
    return xs, ys


def _run(mesh=None, codec="dense", donate=None, rounds=3, n=8, epochs=1,
         bs=4):
    Settings.ENGINE_WIRE_CODEC = codec
    eng = _engine(n, mesh)
    p = eng.init_params((28, 28))
    xs, ys = _data(n, bs=bs)
    dx, dy = eng.shard_data(xs, ys)
    return eng.run_rounds(
        p, dx, dy, n_rounds=rounds, epochs=epochs, donate=donate
    )


def _engine(n=8, mesh=None):
    return FederationEngine(_mlp(), n, mesh=mesh, seed=0)


def _bytes_of(tree):
    return b"".join(
        np.asarray(leaf).tobytes() for leaf in jax.tree_util.tree_leaves(tree)
    )




# --- (a) cache-key hygiene / HLO-digest pin -------------------------------


def _hlo_digest(eng, codec, donate=False):
    bits = compression.resolve_engine_codec(codec)
    fn = eng.program("plain", 1, 2, 1, donate=donate, codec=bits)
    p = eng.init_params((28, 28))
    n = eng.padded_nodes
    xs = jnp.zeros((n, 1, 4, 28, 28), jnp.float32)
    ys = jnp.zeros((n, 1, 4), jnp.int32)
    low = fn.lower(p, {}, {}, {}, xs, ys, eng.pad_weights(None), eng.valid)
    return hashlib.sha256(low.as_text().encode()).hexdigest()


def test_codec_off_hlo_identical_across_toggle():
    e1 = _engine()
    off_before = _hlo_digest(e1, "dense")
    on_q8 = _hlo_digest(e1, "quant8")
    on_tk = _hlo_digest(e1, "topk+quant8")
    # An engine that compiled the codec variant FIRST must still lower
    # the identical dense program (cache-key split, no contamination).
    e2 = _engine()
    _hlo_digest(e2, "quant8")
    off_after = _hlo_digest(e2, "dense")
    assert off_before == off_after
    assert on_q8 != off_before
    assert on_tk not in (off_before, on_q8)


def test_program_cache_key_splits_on_codec_and_donate():
    eng = _engine()
    dense = eng.program("plain", 1, 2, 1, donate=False, codec=0)
    q8 = eng.program(
        "plain", 1, 2, 1, donate=False, codec=compression.QUANT8
    )
    donating = eng.program("plain", 1, 2, 1, donate=True, codec=0)
    assert dense is not q8 and dense is not donating
    # Same key -> same cached program; different top-k fraction is a
    # different static k, hence a different cache slot.
    assert eng.program("plain", 1, 2, 1, donate=False, codec=0) is dense
    tk1 = eng.program(
        "plain", 1, 2, 1, donate=False, codec=compression.TOPK,
        topk_frac=0.05,
    )
    tk2 = eng.program(
        "plain", 1, 2, 1, donate=False, codec=compression.TOPK,
        topk_frac=0.25,
    )
    assert tk1 is not tk2


def test_engine_codec_knob_validation():
    with pytest.raises(ValueError, match="host-side"):
        compression.resolve_engine_codec("quant8+zlib")
    with pytest.raises(ValueError, match="Unknown wire codec"):
        compression.resolve_engine_codec("quant16")
    assert compression.resolve_engine_codec("dense") == 0
    assert compression.resolve_engine_codec("topk+quant8") == (
        compression.TOPK | compression.QUANT8
    )
    # The knob is read (and validated) at dispatch time.
    Settings.ENGINE_WIRE_CODEC = "quant8+zlib"
    eng = _engine()
    xs, ys = _data(8)
    dx, dy = eng.shard_data(xs, ys)
    with pytest.raises(ValueError, match="host-side"):
        eng.run_rounds(eng.init_params((28, 28)), dx, dy, n_rounds=1)


# --- (b) codec math parity: in-program == host payload path ---------------


def _leaf_zoo():
    rng = np.random.default_rng(7)
    return [
        rng.normal(size=(16, 8)).astype(np.float32),
        np.asarray(jnp.asarray(rng.normal(size=(9,)), jnp.bfloat16)),
        rng.normal(size=(4, 3)).astype(np.float16),
        np.float32(2.5),
        np.zeros((0, 3), np.float32),
        np.arange(6, dtype=np.int32),
    ]


@pytest.mark.parametrize(
    "codec", ["quant8", "topk", "topk+quant8"]
)
def test_engine_roundtrip_matches_host_payload_path(codec):
    bits = compression.resolve_engine_codec(codec)
    frac = 0.3
    rt = compression.engine_codec_roundtrip(bits, frac)
    for leaf in _leaf_zoo():
        dev = np.asarray(rt(jnp.asarray(leaf)))
        rec = compression._encode_leaf(np.asarray(leaf), bits, frac)
        host = (
            np.asarray(compression._decode_leaf(rec))
            if isinstance(rec, dict)
            and (rec.get("__q8__") == 1 or rec.get("__tk__") == 1)
            else np.asarray(leaf)  # stayed dense (tiny/non-float/empty)
        )
        assert dev.dtype == np.asarray(leaf).dtype
        assert dev.tobytes() == host.astype(dev.dtype).tobytes(), leaf.shape


def test_dense_roundtrip_is_identity():
    rt = compression.engine_codec_roundtrip(0, 0.05)
    x = jnp.ones((4, 4))
    assert rt(x) is x


# --- (c) quantized-gossip loss parity at 1 and 8 devices ------------------


@pytest.mark.parametrize("devices", [1, 8])
def test_quantized_gossip_loss_parity(devices):
    # Parity A/B at a representative per-round load: toy 4-sample
    # batches amplify trajectory noise far past what a real round sees.
    mesh = create_mesh({"nodes": devices}) if devices > 1 else None
    _, dense_losses = _run(mesh, "dense", rounds=4, epochs=2, bs=64)
    _, q8_losses = _run(mesh, "quant8", rounds=4, epochs=2, bs=64)
    ld = float(np.mean(np.asarray(dense_losses)))
    lq = float(np.mean(np.asarray(q8_losses)))
    assert abs(lq - ld) / max(abs(ld), 1e-9) <= 0.02
    # Same-seed quantized runs are byte-identical (the codec is a
    # deterministic program, not added noise).
    pq1, _ = _run(mesh, "quant8", rounds=3)
    pq2, _ = _run(mesh, "quant8", rounds=3)
    assert _bytes_of(pq1) == _bytes_of(pq2)


# --- (d) device-side wire bytes -------------------------------------------


def test_wire_bytes_carry_and_registry_series():
    Settings.ENGINE_TELEMETRY = True
    n = 8
    for codec, bits in (("dense", 0), ("quant8", compression.QUANT8)):
        Settings.ENGINE_WIRE_CODEC = codec
        eng = _engine(n)
        p = eng.init_params((28, 28))
        per_model = compression.wire_bytes_per_model(
            jax.tree_util.tree_map(
                lambda t: jax.ShapeDtypeStruct(t.shape[1:], t.dtype), p
            ),
            bits,
            float(Settings.WIRE_TOPK_FRAC),
        )
        fn = eng.program(
            "plain", 1, 2, 1, donate=False, telemetry=True, codec=bits
        )
        xs, ys = _data(n)
        dx, dy = eng.shard_data(xs, ys)
        w = np.asarray([1, 1, 0, 1, 0, 1, 1, 1], np.float32)
        out = fn(p, {}, {}, {}, dx, dy, eng.pad_weights(w), eng.valid)
        tele = out[5]
        expected = float((w > 0).sum()) * per_model
        np.testing.assert_allclose(
            np.asarray(tele["wire_bytes"]), expected
        )
    # dense/quant8 per-model ratio for an f32 model sits just under 4x.
    p = _engine(n).init_params((28, 28))
    shapes = jax.tree_util.tree_map(
        lambda t: jax.ShapeDtypeStruct(t.shape[1:], t.dtype), p
    )
    ratio = compression.wire_bytes_per_model(
        shapes, 0
    ) / compression.wire_bytes_per_model(shapes, compression.QUANT8)
    assert ratio >= 3.0
    # The run_rounds fan-out lands the gauge + window total counter.
    Settings.ENGINE_WIRE_CODEC = "quant8"
    _run(None, "quant8", rounds=2)
    folded = metrics.fold()
    gauges = {k[0] for k in folded["gauges"]}
    counters = {k[0] for k in folded["counters"]}
    assert "tpfl_engine_wire_bytes" in gauges
    assert "tpfl_engine_wire_bytes_total" in counters
    Settings.ENGINE_TELEMETRY = False


def test_exchange_bytes_from_the_carry_drop_3x_under_quant8():
    """The production scrape path, as byte COUNTS: the per-round
    exchange bytes the device-side carry records, read back through
    ``run_rounds``' fan-out as the ``tpfl_engine_wire_bytes`` gauge —
    quant8 moves at least 3x fewer than dense (f32 models sit just
    under 4x; envelope overhead is a host concept, on neither side)."""
    Settings.ENGINE_TELEMETRY = True

    def gauge(codec):
        metrics.reset()
        _run(None, codec, rounds=2)
        vals = [
            v
            for k, v in metrics.fold()["gauges"].items()
            if k[0] == "tpfl_engine_wire_bytes"
        ]
        assert vals, codec
        return float(vals[-1])

    dense_bytes, quant_bytes = gauge("dense"), gauge("quant8")
    Settings.ENGINE_TELEMETRY = False
    assert quant_bytes > 0
    assert dense_bytes >= 3 * quant_bytes, (dense_bytes, quant_bytes)


# --- (e) donation ---------------------------------------------------------


@pytest.mark.parametrize("devices", [1, 8])
def test_donating_outputs_byte_identical(devices):
    mesh = create_mesh({"nodes": devices}) if devices > 1 else None
    p1, _ = _run(mesh, donate=True)
    p2, _ = _run(mesh, donate=False)
    assert _bytes_of(p1) == _bytes_of(p2)


def test_donation_report_clean():
    eng = _engine()
    p = eng.init_params((28, 28))
    xs, ys = _data(8)
    dx, dy = eng.shard_data(xs, ys)
    rep = eng.donation_report(p, dx, dy, n_rounds=2)
    assert rep["clean"], rep
    assert rep["donated_leaves"] == rep["aliased"] == rep["output_aliases"]
    assert rep["unaliased_donors"] == 0
    # The telemetry + codec variant must stay donation-clean too (the
    # carry is a NEW output, not an aliased one).
    Settings.ENGINE_TELEMETRY = True
    Settings.ENGINE_WIRE_CODEC = "quant8"
    try:
        eng2 = _engine()
        rep2 = eng2.donation_report(
            eng2.init_params((28, 28)), dx, dy, n_rounds=2
        )
        assert rep2["clean"], rep2
    finally:
        Settings.ENGINE_TELEMETRY = False
        Settings.ENGINE_WIRE_CODEC = "dense"


def test_donation_analysis_flags_non_donating_program():
    eng = _engine()
    fn = eng.program("plain", 1, 2, 1, donate=False)
    p = eng.init_params((28, 28))
    xs, ys = _data(8)
    dx, dy = eng.shard_data(xs, ys)
    rep = donation_analysis(
        fn, (p, {}, {}, {}, dx, dy, eng.pad_weights(None), eng.valid)
    )
    assert not rep["clean"]
    assert rep["aliased"] == 0 and rep["output_aliases"] == 0


def test_donate_default_reads_settings_knob():
    """ENGINE_DONATE=False routes run_rounds to the non-donating
    program: the handed-in params buffer survives the dispatch."""
    Settings.ENGINE_DONATE = False
    try:
        eng = _engine()
        p = eng.init_params((28, 28))
        xs, ys = _data(8)
        dx, dy = eng.shard_data(xs, ys)
        eng.run_rounds(p, dx, dy, n_rounds=1)
        _ = _bytes_of(p)  # alive — would raise if donated
    finally:
        Settings.ENGINE_DONATE = True
    eng = _engine()
    p = eng.init_params((28, 28))
    xs, ys = _data(8)
    dx, dy = eng.shard_data(xs, ys)
    eng.run_rounds(p, dx, dy, n_rounds=1)  # knob default: donating
    with pytest.raises(RuntimeError):
        _bytes_of(p)


def test_best_of_wall_donated_rebinds():
    from tpfl.management import profiling

    eng = _engine()
    p = eng.init_params((28, 28))
    xs, ys = _data(8)
    dx, dy = eng.shard_data(xs, ys)

    def window(params):
        return eng.run_rounds(params, dx, dy, n_rounds=1, donate=True)

    best, out = profiling.best_of_wall_donated(
        window, (p,), rebind=lambda out, a: (out[0],), n=2
    )
    assert best > 0.0
    assert np.isfinite(np.asarray(out[1])).all()
