"""Wire codec tests: round trips for every codec over the dtype zoo
(incl. bfloat16, empty and scalar leaves), residual (delta) payloads
with base-mismatch fallback, chunked-stream reassembly integrity, the
wirecheck lint, and an e2e two-node gRPC federation exchanging
quantized deltas over the chunked stream path."""

import numpy as np
import pytest

import jax.numpy as jnp

from tpfl.communication.grpc_transport import chunk_frames, reassemble_frames
from tpfl.exceptions import (
    ChunkIntegrityError,
    DecodingParamsError,
    DeltaBaseMismatchError,
)
from tpfl.learning import compression, serialization
from tpfl.learning.model import TpflModel
from tpfl.settings import Settings

CODECS = ["dense", "quant8", "quant8+zlib", "topk", "topk+quant8+zlib"]


def zoo_params(seed=0):
    """Pytree covering every wire-relevant leaf kind: f32/f64/bf16/f16
    floats, ints, bools, empty and scalar leaves, tuple/list structure."""
    rng = np.random.default_rng(seed)
    return {
        "dense1": {
            "kernel": rng.normal(size=(16, 32)).astype(np.float32),
            "bias": np.zeros((32,), np.float32),
        },
        "bf16": jnp.asarray(rng.normal(size=(8, 8)), jnp.bfloat16),
        "f16": rng.normal(size=(4, 4)).astype(np.float16),
        "f64": rng.normal(size=(3,)).astype(np.float64),
        "ints": np.arange(6, dtype=np.int32).reshape(2, 3),
        "flags": np.array([True, False, True]),
        "empty": np.zeros((0, 4), np.float32),
        "scalar": np.float32(2.5),
        "nested": (np.ones((2,), np.float32), [np.int64(3), None, "tag"]),
    }


def _leaf_arrays(tree):
    import jax

    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("codec", CODECS)
def test_codec_roundtrip_all_dtypes(codec):
    params = zoo_params()
    blob = compression.encode_model_payload(
        params, ["n1", "n2"], 7, {"k": np.arange(3)}, codec
    )
    # every decode site dispatches through serialization
    back, contribs, n, info = serialization.decode_model_payload(blob)
    assert contribs == ["n1", "n2"] and n == 7
    np.testing.assert_array_equal(info["k"], np.arange(3))
    # structure preserved
    assert isinstance(back["nested"], tuple)
    assert back["nested"][1][1] is None and back["nested"][1][2] == "tag"
    # non-float / empty / scalar leaves are exact under every codec
    np.testing.assert_array_equal(back["ints"], params["ints"])
    np.testing.assert_array_equal(back["flags"], params["flags"])
    assert np.asarray(back["empty"]).shape == (0, 4)
    assert float(np.asarray(back["scalar"])) == 2.5
    # dtypes survive (bfloat16 included)
    for a, b in zip(_leaf_arrays(params), _leaf_arrays(back)):
        assert a.dtype == b.dtype, (a.dtype, b.dtype)
        assert a.shape == b.shape
    if codec == "dense":
        for a, b in zip(_leaf_arrays(params), _leaf_arrays(back)):
            np.testing.assert_array_equal(a, b)
    elif "topk" not in codec:
        # int8 symmetric quantization error bound: half a step per leaf
        k = np.asarray(back["dense1"]["kernel"], np.float32)
        ref = params["dense1"]["kernel"]
        assert np.abs(k - ref).max() <= np.abs(ref).max() / 127.0


def test_quant8_is_actually_smaller():
    rng = np.random.default_rng(0)
    params = {"w": rng.normal(size=(256, 256)).astype(np.float32)}
    dense = compression.encode_model_payload(params, [], 0, {}, "dense")
    q8 = compression.encode_model_payload(params, [], 0, {}, "quant8+zlib")
    assert len(dense) / len(q8) >= 3.5  # ~4x minus envelope overhead


def test_wire_ab_codec_moves_4x_fewer_bytes_at_loss_parity():
    """The seeded wire A/B as byte COUNTS: a 4-node FedAvg on rendered
    digits run twice from one seed — every upload and every result
    broadcast pushed through the dense v1 wire, then through the scale
    profile's ``quant8+zlib`` with the broadcast shipped as a residual
    against the previous round's round-tripped aggregate. The codec run
    puts >= 4x fewer payload bytes on the wire and its steady loss sits
    within 2% of the dense run's (mid-descent, not flat at init)."""
    import jax
    import optax

    from tpfl.learning.dataset.rendered import rendered_digits
    from tpfl.models import MLP

    nodes, batches, bs, rounds = 4, 2, 64, 10
    ds = rendered_digits(n_train=nodes * batches * bs, n_test=10, seed=0)
    dx = np.asarray(ds.get_split(True)["image"], np.float32).reshape(
        nodes, batches, bs, 28, 28
    )
    dy = np.asarray(ds.get_split(True)["label"], np.int32).reshape(
        nodes, batches, bs
    )
    mlp = MLP(hidden_sizes=(32,), compute_dtype=jnp.float32)
    p0 = mlp.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 28, 28)), train=False
    )["params"]
    tx = optax.sgd(0.5)

    @jax.jit
    def fit(p, x, y):
        o = tx.init(p)
        loss = jnp.float32(0)
        for b in range(batches):
            def loss_of(pp, b=b):
                logits = mlp.apply({"params": pp}, x[b], train=True)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, y[b]
                ).mean()

            loss, g = jax.value_and_grad(loss_of)(p)
            upd, o = tx.update(g, o, p)
            p = optax.apply_updates(p, upd)
        return p, loss

    def run(codec):
        g = jax.tree_util.tree_map(np.asarray, p0)
        total, base, steady = 0, None, 0.0
        for r in range(rounds):
            locals_, losses = [], []
            for i in range(nodes):
                pi, li = fit(g, dx[i], dy[i])
                pi = jax.tree_util.tree_map(np.asarray, pi)
                if codec is None:
                    blob = serialization.encode_model_payload(
                        pi, [f"n{i}"], 1, {}
                    )
                    back = serialization.decode_model_payload(blob)[0]
                else:
                    blob = compression.encode_model_payload(
                        pi, [f"n{i}"], 1, {}, codec
                    )
                    back = compression.decode_model_payload(blob)[0]
                total += len(blob)
                locals_.append(back)
                losses.append(float(li))
            agg = jax.tree_util.tree_map(
                lambda *xs: np.mean(np.stack(xs), axis=0), *locals_
            )
            if codec is None:
                blob = serialization.encode_model_payload(agg, ["agg"], 1, {})
                g = serialization.decode_model_payload(blob)[0]
            else:
                cache = compression.BaseCache()
                if base is not None:
                    cache.put(base[0], base[2])
                blob = compression.encode_model_payload(
                    agg, ["agg"], 1, {}, codec, delta_base=base
                )
                g = compression.decode_model_payload(blob, bases=cache)[0]
                base = (r, compression.pytree_fingerprint(g), g)
            # one result broadcast per non-trainer peer, in both runs
            total += len(blob) * (nodes - 1)
            steady = float(np.mean(losses))
        return total, steady

    dense_bytes, dense_loss = run(None)
    codec_bytes, codec_loss = run("quant8+zlib")
    assert dense_bytes >= 4 * codec_bytes, (dense_bytes, codec_bytes)
    assert dense_loss < 2.1  # descended from ln(10): the parity is not trivial
    assert abs(codec_loss - dense_loss) <= 0.02 * abs(dense_loss)


def test_topk_keeps_largest_magnitudes():
    x = np.zeros((100,), np.float32)
    x[[3, 50, 97]] = [5.0, -7.0, 2.0]
    prev = Settings.WIRE_TOPK_FRAC
    Settings.WIRE_TOPK_FRAC = 0.03  # k = 3
    try:
        blob = compression.encode_model_payload(
            {"x": x}, [], 0, {}, "topk", topk_frac=0.03
        )
    finally:
        Settings.WIRE_TOPK_FRAC = prev
    back, *_ = compression.decode_model_payload(blob)
    np.testing.assert_allclose(np.asarray(back["x"]), x, atol=1e-6)


def _parity_zoo():
    """Dtype zoo for the jitted-vs-numpy kernel parity pins: every
    float dtype the wire carries, plus 0-d and empty leaves."""
    rng = np.random.default_rng(11)
    return [
        rng.normal(size=(16, 8)).astype(np.float32),
        jnp.asarray(rng.normal(size=(9,)), jnp.bfloat16),
        rng.normal(size=(4, 3)).astype(np.float16),
        rng.normal(size=(5,)).astype(np.float64),
        np.float32(2.5),
        np.float32(0.0),
        np.zeros((0, 4), np.float32),
        np.full((4,), 1e30, np.float32),
        np.array([2.0, -2.0, 2.0, 1.0], np.float32),  # magnitude ties
    ]


def test_q8_kernel_bit_equal_to_numpy_reference():
    """The jitted device codec and the host-side numpy path must agree
    BIT-FOR-BIT — the engine's in-program exchange and a gRPC peer's
    decode are the same math, not merely close."""
    for x in _parity_zoo():
        qj, sj = compression._q8_encode(jnp.asarray(x))
        qn, sn = compression.q8_encode_np(np.asarray(x))
        assert np.asarray(qj).tobytes() == qn.tobytes(), np.shape(x)
        assert np.float32(sj).tobytes() == np.float32(sn).tobytes()
        dj = np.asarray(compression._q8_decode(qj, sj))
        dn = compression.q8_decode_np(qn, sn)
        assert dj.tobytes() == dn.tobytes()


def test_topk_kernel_bit_equal_to_numpy_reference():
    for x in _parity_zoo():
        size = int(np.prod(np.shape(x))) if np.shape(x) else 1
        k = max(1, min(3, size))
        if size == 0:
            k = 1  # guard path: empty in, empty out
        ij, vj = compression._topk_encode(jnp.asarray(x), k)
        inp, vn = compression.topk_encode_np(np.asarray(x), k)
        assert np.array_equal(np.asarray(ij), inp), np.shape(x)
        assert np.asarray(vj).tobytes() == vn.tobytes()


def test_wire_bytes_per_model_accounting():
    """The static accounting mirrors _encode_leaf's per-leaf policy:
    non-float/empty dense, top-k only past one element."""
    tree = {
        "w": np.zeros((256, 256), np.float32),
        "b16": np.zeros((64,), np.float16),
        "ints": np.zeros((8,), np.int32),
        "scalar": np.float32(1.0),
        "empty": np.zeros((0, 4), np.float32),
    }
    dense = compression.wire_bytes_per_model(tree, 0)
    assert dense == 256 * 256 * 4 + 64 * 2 + 8 * 4 + 4
    q8 = compression.wire_bytes_per_model(tree, compression.QUANT8)
    # floats of size>0 quantize (int8 + f32 scale); ints ride dense;
    # the scalar quantizes too (1 + 4 bytes).
    assert q8 == (256 * 256 + 4) + (64 + 4) + 8 * 4 + (1 + 4)
    tk = compression.wire_bytes_per_model(
        tree, compression.TOPK | compression.QUANT8, topk_frac=0.05
    )
    k = int(np.ceil(256 * 256 * 0.05))
    k16 = int(np.ceil(64 * 0.05))
    # top-k'd leaves: uint32 idx + int8 vals + scale; the scalar has
    # no top-k (size 1) and falls back to quant8.
    assert tk == (k * 4 + k + 4) + (k16 * 4 + k16 + 4) + 8 * 4 + (1 + 4)
    # ShapeDtypeStruct leaves (the engine's trace-time form) agree.
    import jax

    structs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype),
        tree,
    )
    assert compression.wire_bytes_per_model(structs, 0) == dense


def test_resolve_codec_validation():
    assert compression.resolve_codec("dense") == 0
    assert compression.resolve_codec("quant8+zlib") == (
        compression.QUANT8 | compression.ZLIB
    )
    with pytest.raises(ValueError, match="Unknown wire codec"):
        compression.resolve_codec("quant16")
    with pytest.raises(ValueError):
        compression.resolve_codec("zlib+zstd")
    # the profiles must all name resolvable codecs
    for profile in (
        Settings.set_test_settings,
        Settings.set_standalone_settings,
        Settings.set_scale_settings,
    ):
        snap = Settings.snapshot()
        try:
            profile()
            compression.resolve_codec(Settings.WIRE_CODEC)
        finally:
            Settings.restore(snap)


def test_v1_payloads_still_decode():
    """Old peers' dense payloads (v1 envelope) decode unchanged — the
    codec-id dispatch must never break back-compat."""
    params = zoo_params()
    blob = serialization.encode_model_payload(params, ["old"], 3, {})
    assert compression.payload_version(blob) == 1
    back, contribs, n, _ = serialization.decode_model_payload(blob)
    assert contribs == ["old"] and n == 3
    np.testing.assert_array_equal(
        np.asarray(back["dense1"]["kernel"]), params["dense1"]["kernel"]
    )


def test_corrupt_v2_payload_raises_decoding_error():
    params = {"w": np.ones((8,), np.float32)}
    blob = compression.encode_model_payload(params, [], 0, {}, "quant8+zlib")
    # flip a byte inside the body: CRC must catch it
    corrupted = bytearray(blob)
    corrupted[len(corrupted) // 2] ^= 0xFF
    with pytest.raises(DecodingParamsError):
        compression.decode_model_payload(bytes(corrupted))
    with pytest.raises(DecodingParamsError):
        compression.decode_model_payload(b"\x02\x01 garbage")


# --- residual (delta) payloads ---


def test_delta_roundtrip_and_base_mismatch_fallback():
    base = zoo_params(seed=1)
    # drift the float leaves a little (what one round of FedAvg does)
    cur = {
        **base,
        "dense1": {
            "kernel": base["dense1"]["kernel"] + 0.01,
            "bias": base["dense1"]["bias"] - 0.02,
        },
    }
    fp = compression.pytree_fingerprint(base)
    blob = compression.encode_model_payload(
        cur, ["n1"], 4, {}, "quant8+zlib", delta_base=(5, fp, base)
    )
    assert compression.payload_is_delta(blob)
    assert not compression.payload_is_delta(
        compression.encode_model_payload(cur, [], 0, {}, "quant8")
    )

    cache = compression.BaseCache()
    cache.put(5, base)
    back, contribs, n, _ = compression.decode_model_payload(blob, bases=cache)
    assert contribs == ["n1"] and n == 4
    ref = np.asarray(cur["dense1"]["kernel"], np.float32)
    got = np.asarray(back["dense1"]["kernel"], np.float32)
    # residual quantization error is bounded by the RESIDUAL's range,
    # far tighter than quantizing the full weights
    assert np.abs(got - ref).max() <= 0.03 / 127.0 + 1e-6
    # dtypes restored from the base
    assert np.asarray(back["bf16"]).dtype == np.asarray(base["bf16"]).dtype

    # no base at all
    with pytest.raises(DeltaBaseMismatchError):
        compression.decode_model_payload(blob, bases=None)
    # wrong round
    empty = compression.BaseCache()
    empty.put(4, base)
    with pytest.raises(DeltaBaseMismatchError):
        compression.decode_model_payload(blob, bases=empty)
    # right round, different weights -> fingerprint mismatch
    drifted = compression.BaseCache()
    drifted.put(5, cur)
    with pytest.raises(DeltaBaseMismatchError):
        compression.decode_model_payload(blob, bases=drifted)


def test_base_cache_is_bounded():
    cache = compression.BaseCache()
    for r in range(10):
        cache.put(r, {"w": np.full((2,), float(r), np.float32)})
    assert cache.get(0) is None
    assert cache.get(9) is not None
    fp, params = cache.get(9)
    assert cache.lookup(9, fp) is not None
    assert cache.lookup(9, b"\x00" * 32) is None


def test_model_decodes_delta_through_base_store():
    """TpflModel.set_parameters(bytes) resolves residual payloads via
    the attached BaseCache and restores the model's own dtypes."""
    base = {"w": np.ones((4, 4), np.float32)}
    cur = {"w": (np.ones((4, 4)) * 1.25).astype(np.float32)}
    store = compression.BaseCache()
    store.put(0, base)
    model = TpflModel(params={"w": jnp.zeros((4, 4), jnp.float32)})
    model.base_store = store
    blob = compression.encode_model_payload(
        cur, ["a"], 1, {}, "quant8",
        delta_base=(0, compression.pytree_fingerprint(base), base),
    )
    model.set_parameters(blob)
    np.testing.assert_allclose(
        np.asarray(model.get_parameters()["w"]), cur["w"], atol=0.25 / 127
    )
    # base_store rides build_copy (the wire-intake chain)
    assert model.build_copy(params=cur).base_store is store


# --- chunked streaming ---


def test_chunk_roundtrip():
    data = bytes(np.random.default_rng(0).integers(0, 256, 100_000, np.uint8))
    frames = list(chunk_frames(data, 4096))
    assert len(frames) == -(-len(data) // 4096)
    assert reassemble_frames(iter(frames)) == data
    # single-chunk message still frames correctly
    assert reassemble_frames(chunk_frames(b"tiny", 4096)) == b"tiny"


def test_chunk_truncation_and_corruption_rejected():
    data = b"x" * 50_000
    frames = list(chunk_frames(data, 8192))
    with pytest.raises(ChunkIntegrityError, match="Truncated"):
        reassemble_frames(iter(frames[:-1]))  # dropped tail
    with pytest.raises(ChunkIntegrityError, match="gap"):
        reassemble_frames(iter([frames[0], frames[2]]))  # hole
    with pytest.raises(ChunkIntegrityError, match="gap"):
        reassemble_frames(iter([frames[1], frames[0]]))  # reorder
    # corrupt one chunk's payload byte (inside the msgpack bin field)
    bad = bytearray(frames[1])
    bad[-1] ^= 0xFF
    with pytest.raises(ChunkIntegrityError, match="CRC|Malformed"):
        reassemble_frames(iter([frames[0], bytes(bad), *frames[2:]]))
    with pytest.raises(ChunkIntegrityError, match="Malformed"):
        reassemble_frames(iter([b"not msgpack"]))


# --- wirecheck lint ---


def test_wirecheck_lint_passes():
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
    try:
        from tools.tpflcheck.wire import check
    finally:
        sys.path.pop(0)
    assert check() == []


# --- e2e: two gRPC nodes exchanging quantized deltas over chunks ---


def test_e2e_grpc_quantized_delta_gossip():
    from tpfl.communication.grpc_transport import GrpcCommunicationProtocol
    from tpfl.learning.dataset import (
        RandomIIDPartitionStrategy,
        synthetic_mnist,
    )
    from tpfl.models import create_model
    from tpfl.node import Node
    from tpfl.utils import wait_convergence, wait_to_finish

    Settings.WIRE_CODEC = "quant8+zlib"
    Settings.WIRE_DELTA = True
    Settings.WIRE_CHUNK_SIZE = 2048  # force the streaming path
    Settings.TRAIN_SET_SIZE = 1  # guarantee a FullModel push every round

    n, rounds = 2, 2
    ds = synthetic_mnist(n_train=200 * n, n_test=40 * n, seed=0, noise=0.4)
    parts = ds.generate_partitions(n, RandomIIDPartitionStrategy, seed=1)
    nodes = [
        Node(
            create_model("mlp", (28, 28), seed=7, hidden_sizes=(32,)),
            parts[i],
            protocol=GrpcCommunicationProtocol,
            learning_rate=0.1,
            batch_size=32,
        )
        for i in range(n)
    ]
    seen = {"v2": 0, "delta": 0, "dense_v1": 0}
    for nd in nodes:
        orig_send = nd.communication.send

        def counting_send(nei, msg, *a, _orig=orig_send, **kw):
            payload = getattr(msg, "payload", None)
            if payload:
                if compression.payload_version(payload) == 2:
                    seen["v2"] += 1
                    if compression.payload_is_delta(payload):
                        seen["delta"] += 1
                else:
                    seen["dense_v1"] += 1
            return _orig(nei, msg, *a, **kw)

        nd.communication.send = counting_send
    for nd in nodes:
        nd.start()
    try:
        nodes[0].connect(nodes[1].addr)
        wait_convergence(nodes, n - 1, only_direct=False, wait=10)
        nodes[0].set_start_learning(rounds=rounds, epochs=1)
        wait_to_finish(nodes, timeout=120)
        for nd in nodes:
            assert nd.state.round is None  # experiment finished cleanly
        # every weight payload went through the v2 codec...
        assert seen["v2"] > 0 and seen["dense_v1"] == 0, seen
        # ...and round >= 1 full-model pushes rode as residuals
        assert seen["delta"] >= 1, seen
        # both nodes converged to the same aggregate (within int8
        # quantization noise of one wire hop)
        a = nodes[0].learner.get_model().get_parameters_list()
        b = nodes[1].learner.get_model().get_parameters_list()
        for x, y in zip(a, b):
            np.testing.assert_allclose(
                np.asarray(x, np.float32),
                np.asarray(y, np.float32),
                atol=0.05,
            )
    finally:
        for nd in nodes:
            nd.stop()
