"""Communication layer tests — mirrors the reference's
``test/communication/communication_test.py`` contract: connection
errors, handshake symmetry, gossip discovery of indirect peers,
disconnect propagation, abrupt-death eviction, plus dedup/TTL and the
synchronous model-gossip loop. Parametrized over protocol classes so the
future gRPC transport slots into the same suite."""

import threading
import time

import pytest

from tpfl.communication import InMemoryCommunicationProtocol
from tpfl.communication.grpc_transport import GrpcCommunicationProtocol
from tpfl.communication.memory import clear_registry
from tpfl.communication.message import Message
from tpfl.exceptions import CommunicationError
from tpfl.settings import Settings

PROTOCOLS = [InMemoryCommunicationProtocol, GrpcCommunicationProtocol]


@pytest.fixture(autouse=True)
def _clean_registry():
    clear_registry()
    yield
    clear_registry()


def make_nodes(protocol_class, n):
    nodes = [protocol_class() for _ in range(n)]
    for nd in nodes:
        nd.start()
    return nodes


def stop_all(nodes):
    for nd in nodes:
        nd.stop()


def hard_kill(p):
    """Crash, not a graceful stop: worker threads die and the server
    unbinds, but NO disconnect messages go out — peers must discover
    the death themselves (failed sends / heartbeat loss). ``stop()``
    notifies every neighbor, which is a clean leave, not a crash."""
    p._heartbeater.stop()
    p._gossiper.stop()
    for t in (p._heartbeater, p._gossiper):
        if t.is_alive():
            t.join(timeout=3)
    p._server_stop()
    p._started = False
    p._terminated.set()


@pytest.mark.parametrize("protocol_class", PROTOCOLS)
def test_not_started_errors(protocol_class):
    p = protocol_class()
    with pytest.raises(CommunicationError):
        p.connect("nowhere")
    p.start()
    with pytest.raises(CommunicationError):
        p.start()  # double start
    p.stop()


@pytest.mark.parametrize("protocol_class", PROTOCOLS)
def test_invalid_connect(protocol_class):
    (a,) = make_nodes(protocol_class, 1)
    ghost = (
        "ghost-address"
        if protocol_class is InMemoryCommunicationProtocol
        else "127.0.0.1:1"  # closed port
    )
    assert not a.connect(a.get_address())  # self
    assert not a.connect(ghost)  # unreachable
    assert a.get_neighbors() == {}
    stop_all([a])


@pytest.mark.parametrize("protocol_class", PROTOCOLS)
def test_handshake_symmetry(protocol_class):
    a, b = make_nodes(protocol_class, 2)
    assert a.connect(b.get_address())
    assert b.get_address() in a.get_neighbors(only_direct=True)
    assert a.get_address() in b.get_neighbors(only_direct=True)
    # double connect refused
    assert not a.connect(b.get_address())
    stop_all([a, b])


@pytest.mark.parametrize("protocol_class", PROTOCOLS)
def test_disconnect_propagation(protocol_class):
    a, b = make_nodes(protocol_class, 2)
    a.connect(b.get_address())
    a.disconnect(b.get_address())
    assert b.get_address() not in a.get_neighbors()
    assert a.get_address() not in b.get_neighbors()
    stop_all([a, b])


@pytest.mark.parametrize("protocol_class", PROTOCOLS)
def test_message_dispatch_and_dedup(protocol_class):
    a, b = make_nodes(protocol_class, 2)
    a.connect(b.get_address())
    got = []
    b.add_command("probe", lambda source, round, args: got.append((source, args)))
    msg = a.build_msg("probe", ["x", "y"], round=3)
    a.send(b.get_address(), msg)
    a.send(b.get_address(), msg)  # same hash -> dropped by dedup
    assert got == [(a.get_address(), ["x", "y"])]
    stop_all([a, b])


@pytest.mark.parametrize("protocol_class", PROTOCOLS)
def test_weights_dispatch(protocol_class):
    a, b = make_nodes(protocol_class, 2)
    a.connect(b.get_address())
    got = {}
    b.add_command(
        "model",
        lambda source, round, weights, contributors, num_samples, **kw: got.update(
            dict(w=weights, c=contributors, n=num_samples, r=round)
        ),
    )
    msg = a.build_weights("model", 2, b"\x01\x02", ["a"], 7)
    a.send(b.get_address(), msg)
    assert got == {"w": b"\x01\x02", "c": ["a"], "n": 7, "r": 2}
    stop_all([a, b])


@pytest.mark.parametrize("protocol_class", PROTOCOLS)
def test_gossip_discovers_indirect_peers(protocol_class):
    # Line topology a-b-c: a learns about c through b's gossiped beats.
    a, b, c = make_nodes(protocol_class, 3)
    a.connect(b.get_address())
    b.connect(c.get_address())
    deadline = time.time() + 5
    while time.time() < deadline:
        if c.get_address() in a.get_neighbors() and a.get_address() in c.get_neighbors():
            break
        time.sleep(0.05)
    assert c.get_address() in a.get_neighbors()
    # ...but NOT as a direct neighbor.
    assert c.get_address() not in a.get_neighbors(only_direct=True)
    stop_all([a, b, c])


@pytest.mark.parametrize("protocol_class", PROTOCOLS)
def test_abrupt_death_eviction(protocol_class):
    a, b = make_nodes(protocol_class, 2)
    a.connect(b.get_address())
    b.stop()  # no disconnect message — simulates a crash
    deadline = time.time() + Settings.HEARTBEAT_TIMEOUT + 3
    while time.time() < deadline:
        if b.get_address() not in a.get_neighbors():
            break
        time.sleep(0.1)
    assert b.get_address() not in a.get_neighbors()
    stop_all([a])


@pytest.mark.parametrize("protocol_class", PROTOCOLS)
def test_broadcast_reaches_all_direct_neighbors(protocol_class):
    hub, s1, s2 = make_nodes(protocol_class, 3)
    hub.connect(s1.get_address())
    hub.connect(s2.get_address())
    got = []
    for nd in (s1, s2):
        nd.add_command(
            "ping", lambda source, round, args, _n=nd: got.append(_n.get_address())
        )
    hub.broadcast(hub.build_msg("ping"))
    assert sorted(got) == sorted([s1.get_address(), s2.get_address()])
    stop_all([hub, s1, s2])


@pytest.mark.parametrize("protocol_class", PROTOCOLS)
def test_ttl_flood_reaches_line_ends(protocol_class):
    # a-b-c-d line: a control message from a floods to d via TTL gossip.
    nodes = make_nodes(protocol_class, 4)
    for x, y in zip(nodes, nodes[1:]):
        x.connect(y.get_address())
    got = threading.Event()
    for nd in nodes[1:3]:
        nd.add_command("flood", lambda source, round, args: None)
    nodes[3].add_command("flood", lambda source, round, args: got.set())
    nodes[0].broadcast(nodes[0].build_msg("flood"))
    assert got.wait(timeout=5)
    stop_all(nodes)


@pytest.mark.parametrize("protocol_class", PROTOCOLS)
def test_gossip_weights_until_early_stop(protocol_class):
    a, b = make_nodes(protocol_class, 2)
    a.connect(b.get_address())
    received = []
    b.add_command(
        "part",
        lambda source, round, weights, contributors, num_samples, **kw: received.append(
            weights
        ),
    )
    stop_after = {"n": 0}

    def early_stop():
        stop_after["n"] += 1
        return len(received) >= 2

    a.gossip_weights(
        early_stopping_fn=early_stop,
        get_candidates_fn=lambda: [b.get_address()],
        status_fn=lambda: len(received),
        model_fn=lambda nei: a.build_weights("part", 0, b"w", ["a"], 1),
        period=0.01,
    )
    assert len(received) >= 2
    stop_all([a, b])


@pytest.mark.parametrize("protocol_class", PROTOCOLS)
def test_gossip_weights_exits_on_static_status(protocol_class):
    a, b = make_nodes(protocol_class, 2)
    a.connect(b.get_address())
    b.add_command("part", lambda **kwargs: None)
    t0 = time.time()
    a.gossip_weights(
        early_stopping_fn=lambda: False,
        get_candidates_fn=lambda: [b.get_address()],
        status_fn=lambda: "static",
        model_fn=lambda nei: a.build_weights("part", 0, b"w", ["a"], 1),
        period=0.01,
    )
    # Exited via GOSSIP_EXIT_ON_X_EQUAL_ROUNDS, not hung.
    assert time.time() - t0 < 5
    stop_all([a, b])


def test_message_wire_roundtrip():
    m = Message(
        source="a", cmd="model", round=2, args=["1"], ttl=3,
        payload=b"\x00\x01", contributors=["a", "b"], num_samples=5,
    ).new_hash()
    m2 = Message.from_bytes(m.to_bytes())
    assert m2.source == "a" and m2.cmd == "model" and m2.round == 2
    assert m2.payload == b"\x00\x01" and m2.contributors == ["a", "b"]
    assert m2.msg_hash == m.msg_hash and m2.ttl == 3 and m2.num_samples == 5


# --- mTLS (reference gen-certs.sh + CI's SSL test settings) ---------------


def test_mtls_handshake_and_send(tmp_path):
    """Full mutual-TLS loopback: cert generation (gen-certs.sh port),
    secure server + secure channel, handshake, message delivery."""
    from tpfl.settings import Settings
    from tpfl.utils.certificates import enable_mtls

    enable_mtls(str(tmp_path))
    assert Settings.USE_SSL
    got = []
    a, b = make_nodes(GrpcCommunicationProtocol, 2)
    try:
        a.add_command("ping", lambda source, round, **kw: got.append(source))
        assert b.connect(a.get_address())
        assert b.get_address() in a.get_neighbors(only_direct=True)
        b.send(a.get_address(), b.build_msg("ping"))
        deadline = time.time() + 10
        while not got and time.time() < deadline:
            time.sleep(0.05)
        assert got == [b.get_address()]
    finally:
        stop_all([a, b])


def test_mtls_rejects_unauthenticated_client(tmp_path):
    """A TLS client presenting no client certificate must be rejected
    (require_client_auth=True) — this is the mutual part of mTLS; a
    plaintext dial failing would not prove it."""
    import grpc

    from tpfl.settings import Settings
    from tpfl.utils.certificates import enable_mtls

    enable_mtls(str(tmp_path))
    server = make_nodes(GrpcCommunicationProtocol, 1)[0]
    try:
        with open(Settings.CA_CRT, "rb") as f:
            ca = f.read()
        # Trusts the server's CA but presents NO client cert.
        channel = grpc.secure_channel(
            server.get_address(), grpc.ssl_channel_credentials(root_certificates=ca)
        )
        import msgpack

        stub = channel.unary_unary(
            "/tpfl.NodeServices/Handshake",
            request_serializer=lambda b: b,
            response_deserializer=lambda b: b,
        )
        with pytest.raises(grpc.RpcError):
            stub(msgpack.packb({"addr": "mallory"}), timeout=5)
        channel.close()
    finally:
        stop_all([server])


def test_grpc_unix_socket_transport(tmp_path):
    """gRPC over unix domain sockets (reference address_parser unix:
    support) — handshake + send without TCP."""
    got = []
    a = GrpcCommunicationProtocol(f"unix:{tmp_path}/a.sock")
    b = GrpcCommunicationProtocol(f"unix:{tmp_path}/b.sock")
    a.start()
    b.start()
    try:
        a.add_command("ping", lambda source, round, **kw: got.append(source))
        assert b.connect(a.get_address())
        assert b.get_address() in a.get_neighbors(only_direct=True)
        b.send(a.get_address(), b.build_msg("ping"))
        deadline = time.time() + 10
        while not got and time.time() < deadline:
            time.sleep(0.05)
        assert got == [b.get_address()]
    finally:
        stop_all([a, b])


def test_heartbeat_priority_relay_order():
    """Liveness beats drain before a queued vote/status burst at a
    relay, and normal traffic still drains afterward (no starvation)."""
    from tpfl.communication.gossiper import Gossiper

    sent = []
    g = Gossiper.__new__(Gossiper)  # no thread: drive the drain manually
    Gossiper.__init__(
        g, "relay", lambda nei, m: sent.append(m.cmd),
        lambda direct: {"peer": None},
    )
    for i in range(5):
        g.add_message(Message(source=f"s{i}", cmd="vote", msg_hash=f"v{i}"))
    g.add_message(
        Message(source="s9", cmd="beat", msg_hash="b1"), priority=True
    )
    # One drain pass (replicate run()'s batch pop under the budget).
    with g._pending_lock:
        budget = Settings.GOSSIP_MESSAGES_PER_PERIOD
        batch = [g._priority.popleft() for _ in range(min(len(g._priority), budget))]
        batch += [g._pending.popleft() for _ in range(min(len(g._pending), budget - len(batch)))]
    for m in batch:
        g._send("peer", m)
    assert sent[0] == "beat"  # liveness first
    assert sent.count("vote") == 5  # nothing starved


def test_digest_merge_does_not_resurrect_dead_peers():
    """A relayed digest entry carries its OBSERVED freshness: an
    unknown peer is added with the carried beat time (not 'now'), and
    entries already older than max_age are dropped entirely — so an
    evicted dead node cannot ping-pong back into peer tables with a
    fresh timestamp."""
    import time as _time

    from tpfl.communication.neighbors import Neighbors

    n = Neighbors("me")
    # Stamps ride the MONOTONIC clock (heartbeater.py: only relative
    # ages cross the wire; absolute stamps are node-local, NTP-immune).
    now = _time.monotonic()
    n.merge_digest(
        [("stale-peer", now - 500.0), ("recent-peer", now - 3.0)],
        max_age=120.0,
    )
    assert "stale-peer" not in n.get_all()
    entry = n.get_all()["recent-peer"]
    assert abs((now - 3.0) - entry.last_beat) < 0.5  # carried, not now
    # Known peers merge monotonically: an older observation never
    # regresses freshness.
    n.merge_digest([("recent-peer", now - 50.0)], max_age=120.0)
    assert abs((now - 3.0) - n.get_all()["recent-peer"].last_beat) < 0.5


def test_full_model_relay_on_first_adoption():
    """FullModelCommand relays the received payload ONCE to lagging
    direct neighbors (epidemic diffusion — O(diameter) instead of
    stage-timing-bound); repeats and up-to-date neighbors are skipped."""
    import threading
    from types import SimpleNamespace

    from tpfl.communication.commands import FullModelCommand

    sent = []

    class FakeComm:
        def get_neighbors(self, only_direct=False):
            return ["nb-lag", "nb-done", "nb-src"]

        def build_weights(self, cmd, round, weights, contributors=None,
                          num_samples=0):
            return {"cmd": cmd, "round": round, "weights": weights,
                    "contributors": contributors, "num_samples": num_samples}

        def send(self, dest, payload):
            sent.append((dest, payload))

    class FakeLearner:
        def set_model(self, weights):
            self.last = weights

    state = SimpleNamespace(
        round=3,
        last_full_model_round=-1,
        aggregated_model_event=threading.Event(),
        model_initialized_event=threading.Event(),
        # nb-lag is behind; nb-done already reported round 3.
        nei_status={"nb-done": 3},
        addr="me",
    )
    state.model_initialized_event.set()
    node = SimpleNamespace(
        state=state, learner=FakeLearner(), communication=FakeComm()
    )
    state.relay_lock = threading.Lock()
    state.last_relayed_round = -1
    state.model_version = 0
    state.model_round_origin = 0
    # The relay reads neighbor status through the snapshot accessor
    # (nei_status is nei_status_lock-guarded on the real NodeState).
    state.get_nei_status = lambda: dict(state.nei_status)
    cmd = FullModelCommand(node)

    def wait_sends(n, timeout=10.0):
        import time

        deadline = time.time() + timeout
        while len(sent) < n and time.time() < deadline:
            time.sleep(0.02)

    cmd.execute("nb-src", 3, b"payload", ["a"], 10)
    wait_sends(1)  # relay runs on a daemon thread
    # Relayed to the lagging neighbor only — not the sender, not the
    # up-to-date one.
    assert [d for d, _ in sent] == ["nb-lag"]
    assert sent[0][1]["cmd"] == "full_model"
    assert sent[0][1]["weights"] == b"payload"  # forwarded verbatim
    assert state.last_full_model_round == 3

    # Same round again: adopted but NOT re-relayed (at most once).
    cmd.execute("nb-other", 3, b"payload", ["a"], 10)
    wait_sends(2, timeout=1.0)
    assert len(sent) == 1


def test_models_aggregated_targets_train_set_only():
    """Coverage announcements are DIRECT sends to train-set peers — the
    only consumers — never a network-wide broadcast (the reference
    floods them; at scale the flood lag fractured the partial
    exchange, see commands.send_models_aggregated)."""
    from types import SimpleNamespace

    from tpfl.communication.commands import send_models_aggregated

    sent, broadcasts = [], []

    class FakeComm:
        def build_msg(self, cmd, args, round=None):
            return {"cmd": cmd, "args": args, "round": round}

        def send(self, dest, msg, create_connection=False):
            sent.append((dest, msg, create_connection))

        def broadcast(self, msg, node_list=None):
            broadcasts.append(msg)

    state = SimpleNamespace(
        addr="me",
        round=2,
        train_set=["me", "peer-a", "peer-b"],
    )
    node = SimpleNamespace(state=state, communication=FakeComm())

    send_models_aggregated(node, ["me", "peer-a"])

    assert broadcasts == []  # never flooded
    assert sorted(d for d, _, _ in sent) == ["peer-a", "peer-b"]  # not self
    for _, msg, create_connection in sent:
        assert msg["cmd"] == "models_aggregated"
        assert msg["args"] == ["me", "peer-a"]
        assert msg["round"] == 2
        assert create_connection  # train set may not be dialed yet


# --- chaos: deterministic fault injection, retry, breaker, quorum ---------
# (ISSUE 2 — the network-plane counterpart of the attacks/ harness.)


def test_wirecheck_rpc_lint_passes():
    """No outbound RPC call site bypasses the retrying send path: raw
    stub/channel use stays inside grpc_transport.py, and nothing but
    the transport layer calls _transport_send directly."""
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
    try:
        from tools.tpflcheck.wire import check_rpc
    finally:
        sys.path.pop(0)
    assert check_rpc() == []


def test_fault_injector_is_deterministic():
    """Same (seed, plan) -> identical per-link decision sequences and
    counters, regardless of how many other links interleave — the
    property that makes chaos runs exactly reproducible."""
    from tpfl.communication.faults import FaultInjector, FaultPlan, LinkFaults

    def plan():
        return FaultPlan(
            links={
                ("*", "*"): LinkFaults(drop=0.25, corrupt=0.1, duplicate=0.1)
            }
        )

    runs = []
    for _ in range(2):
        fi = FaultInjector(plan(), seed=7)
        seq = []
        for i in range(300):
            # Interleave two links; each has its own RNG stream.
            link = ("a", "b") if i % 3 else ("b", "a")
            seq.append((link, fi.decide(*link).action))
        runs.append((seq, fi.stats()))
    assert runs[0] == runs[1]
    # And a different seed actually changes the sequence.
    fi3 = FaultInjector(plan(), seed=8)
    seq3 = [fi3.decide("a", "b").action for _ in range(200)]
    assert seq3 != [a for (link, a) in runs[0][0] if link == ("a", "b")][:200]


def test_fault_plan_schema_and_windows():
    """FaultPlan.from_dict parses the documented schema; crash and
    partition windows gate links by the injector clock."""
    from tpfl.communication.faults import FaultInjector, FaultPlan

    plan = FaultPlan.from_dict(
        {
            "links": {"a->b": {"drop": 0.5, "drop_limit": 2}},
            "crashes": [{"addr": "c", "start": 0.0}],
            "partitions": [
                {"groups": [["a"], ["b"]], "start": 0.0, "end": 0.05}
            ],
        }
    )
    assert plan.faults_for("a", "b").drop == 0.5
    assert plan.faults_for("x", "y") is None
    fi = FaultInjector(plan, seed=0).start()
    assert fi.is_down("c")  # crashed from t=0, never recovers
    assert fi.link_blocked("c", "a") and fi.link_blocked("a", "c")
    assert fi.link_blocked("a", "b")  # partition active
    time.sleep(0.1)
    assert not fi.link_blocked("a", "b")  # partition window expired
    # Manual crash control (round-driven harnesses).
    fi.crash("a")
    assert fi.decide("a", "b").action == "block"
    fi.revive("a")
    assert fi.decide("b", "a").action in ("deliver", "drop")


@pytest.mark.chaos
@pytest.mark.parametrize("protocol_class", PROTOCOLS)
def test_retry_recovers_from_transient_drop(protocol_class):
    """A dropped send attempt is retried with backoff and delivered on
    the second try — the message does NOT silently vanish, and the
    retry is visible in the transport metrics."""
    from tpfl.communication.faults import FaultInjector, FaultPlan, LinkFaults
    from tpfl.management.logger import logger as _logger

    Settings.HEARTBEAT_PERIOD = 30.0  # keep the link quiet for the test
    Settings.RETRY_MAX_ATTEMPTS = 2
    a, b = make_nodes(protocol_class, 2)
    try:
        a.connect(b.get_address())
        fi = FaultInjector(
            FaultPlan(links={("*", "*"): LinkFaults(drop=1.0, drop_limit=1)}),
            seed=3,
        )
        fi.attach(a)
        got = []
        b.add_command("probe", lambda source, round, args: got.append(args))
        a.send(b.get_address(), a.build_msg("probe", ["x"]), raise_error=True)
        assert got == [["x"]]
        link = f"{a.get_address()}->{b.get_address()}"
        assert fi.stats()[link]["dropped"] == 1
        assert fi.stats()[link]["delivered"] == 1
        stats = a.get_transport_stats()[b.get_address()]
        assert stats["sends_ok"] == 1 and stats["retries"] >= 1
        assert stats["breaker_state"] == "closed"
        # Mirrored into the management layer.
        mirrored = _logger.transport_metrics.get_node_logs(a.get_address())
        assert mirrored[b.get_address()]["retries"] >= 1
    finally:
        stop_all([a, b])


@pytest.mark.chaos
def test_corruption_rejected_by_chunk_crc_and_retried():
    """A fault-injected corrupted payload is rejected by the receiver's
    REAL per-chunk CRC check (reassemble_frames), the sender retries,
    and the clean retry delivers — no hang, no silent adoption of
    corrupt bytes."""
    from tpfl.communication.faults import FaultInjector, FaultPlan, LinkFaults

    Settings.HEARTBEAT_PERIOD = 30.0
    Settings.RETRY_MAX_ATTEMPTS = 2
    a, b = make_nodes(GrpcCommunicationProtocol, 2)
    try:
        a.connect(b.get_address())
        fi = FaultInjector(
            FaultPlan(
                links={("*", "*"): LinkFaults(corrupt=1.0, corrupt_limit=1)}
            ),
            seed=5,
        )
        fi.attach(a)
        got = []
        b.add_command(
            "model",
            lambda source, round, weights, contributors, num_samples, **kw: got.append(
                weights
            ),
        )
        payload = bytes(range(256)) * 64
        a.send(
            b.get_address(),
            a.build_weights("model", 1, payload, ["a"], 1),
            raise_error=True,
        )
        assert got == [payload]  # delivered intact exactly once
        link = f"{a.get_address()}->{b.get_address()}"
        stats = fi.stats()[link]
        assert stats["corrupted"] == 1
        assert stats["corrupt_rejected"] == 1  # the CRC did its job
        assert "corrupt_accepted" not in stats  # corrupt bytes NEVER land
        assert stats["delivered"] == 1
    finally:
        stop_all([a, b])


@pytest.mark.chaos
def test_circuit_breaker_evicts_and_readmits():
    """BREAKER_THRESHOLD consecutive failed sends open the circuit and
    evict the dead peer (it stops eating send budget); after a restart
    the periodic half-open probe re-dials and re-admits it."""
    Settings.HEARTBEAT_PERIOD = 0.2
    Settings.HEARTBEAT_TIMEOUT = 60.0  # eviction must come from the breaker
    Settings.RETRY_MAX_ATTEMPTS = 1
    Settings.BREAKER_THRESHOLD = 2
    Settings.BREAKER_PROBE_PERIOD = 0.3
    a, b = make_nodes(InMemoryCommunicationProtocol, 2)
    b_addr = b.get_address()
    b2 = None
    try:
        a.connect(b_addr)
        hard_kill(b)  # crash: no disconnect message
        for _ in range(Settings.BREAKER_THRESHOLD):
            a.send(b_addr, a.build_msg("noop"))
        assert b_addr not in a.get_neighbors()
        stats = a.get_transport_stats()[b_addr]
        assert stats["breaker_state"] == "open"
        assert stats["sends_failed"] >= Settings.BREAKER_THRESHOLD
        # While open, sends are refused instantly (no budget burned).
        with pytest.raises(Exception):
            a.send(b_addr, a.build_msg("noop"), raise_error=True)
        # Restart the peer at the same address: the half-open probe
        # re-dials, handshakes, and re-admits it.
        b2 = InMemoryCommunicationProtocol(b_addr)
        b2.start()
        deadline = time.time() + 10
        while time.time() < deadline:
            if b_addr in a.get_neighbors(only_direct=True):
                break
            time.sleep(0.05)
        assert b_addr in a.get_neighbors(only_direct=True)
        assert a.get_address() in b2.get_neighbors()
        assert a.get_transport_stats()[b_addr]["breaker_state"] == "closed"
        # And traffic flows again.
        got = []
        b2.add_command("probe", lambda source, round, args: got.append(source))
        a.send(b_addr, a.build_msg("probe"), raise_error=True)
        assert got == [a.get_address()]
    finally:
        stop_all([a] + ([b2] if b2 is not None else []))


@pytest.mark.chaos
def test_grpc_dial_timeout_is_typed():
    """A dead endpoint's dial raises ConnectionTimeoutError (slow or
    silent), not a bare CommunicationError (refused) — the distinction
    the retry layer and chaos tests key on."""
    from tpfl.exceptions import CommunicationError, ConnectionTimeoutError

    p = GrpcCommunicationProtocol()
    with pytest.raises(ConnectionTimeoutError) as e:
        p._dial("127.0.0.1:1")  # closed port: nothing ever answers
    assert isinstance(e.value, CommunicationError)  # still caught broadly


@pytest.mark.chaos
def test_quorum_round_completes_without_burning_timeout():
    """A trainer crashing mid-round no longer costs the survivors the
    full AGGREGATION_TIMEOUT: heartbeat loss shrinks the expected
    contributor set (Aggregator.remove_dead_nodes) and the round closes
    on the live members."""
    from tpfl.learning.dataset import RandomIIDPartitionStrategy, synthetic_mnist
    from tpfl.models import create_model
    from tpfl.node import Node
    from tpfl.utils import check_equal_models, wait_convergence, wait_to_finish

    Settings.ELECTION = "hash"  # n <= TRAIN_SET_SIZE: all three elected
    n = 3
    ds = synthetic_mnist(n_train=200 * n, n_test=40 * n, seed=0, noise=0.4)
    parts = ds.generate_partitions(n, RandomIIDPartitionStrategy, seed=1)
    nodes = [
        Node(
            create_model("mlp", (28, 28), seed=7, hidden_sizes=(32,)),
            parts[i],
            learning_rate=0.1,
            batch_size=32,
        )
        for i in range(n)
    ]
    for nd in nodes:
        nd.start()
    try:
        for nd in nodes[1:]:
            nodes[0].connect(nd.addr)
        wait_convergence(nodes, n - 1, only_direct=False, wait=10)
        t0 = time.monotonic()
        nodes[0].set_start_learning(rounds=1, epochs=1)
        # Kill the victim the moment it enters the round's train set.
        deadline = time.time() + 20
        while time.time() < deadline and not nodes[2].state.train_set:
            time.sleep(0.02)
        assert nodes[2].state.train_set, "victim never entered the round"
        nodes[2].stop()
        wait_to_finish(nodes[:2], timeout=60)
        elapsed = time.monotonic() - t0
        # The discriminating assert: without degradation the survivors
        # sit out AGGREGATION_TIMEOUT (30 s under test settings) before
        # aggregating their partial — with it the round closes as soon
        # as the dead peer is evicted and live coverage is complete.
        assert elapsed < Settings.AGGREGATION_TIMEOUT - 5, (
            f"round took {elapsed:.1f}s — burned the aggregation timeout"
        )
        check_equal_models(nodes[:2])
    finally:
        for nd in nodes:
            nd.stop()


@pytest.mark.chaos
def test_two_node_grpc_federation_under_seeded_drop():
    """E2E: a two-node gRPC federation under seeded 30% per-attempt
    message drop still converges — retries, re-pushes, and the relay
    absorb the loss."""
    from tpfl.communication.faults import FaultInjector, FaultPlan, LinkFaults
    from tpfl.learning.dataset import RandomIIDPartitionStrategy, synthetic_mnist
    from tpfl.models import create_model
    from tpfl.node import Node
    from tpfl.utils import check_equal_models, wait_convergence, wait_to_finish

    # Drop is per attempt, so a message is lost for good with p = 0.3^k.
    # At k = 3 that is 2.7% a message, and the one message with no
    # fallback, StartLearning, went missing in a tier-1 run of PR 29 (the
    # peer sat the experiment out); k = 5 makes it 0.24%.
    Settings.RETRY_MAX_ATTEMPTS = 5
    n, rounds = 2, 1
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
    fi = FaultInjector(
        FaultPlan(links={("*", "*"): LinkFaults(drop=0.3)}), seed=42
    )
    for nd in nodes:
        fi.attach(nd.communication)
        nd.start()
    try:
        nodes[0].connect(nodes[1].addr)
        wait_convergence(nodes, n - 1, only_direct=False, wait=10)
        nodes[0].set_start_learning(rounds=rounds, epochs=1)
        wait_to_finish(nodes, timeout=180)
        check_equal_models(nodes)
        dropped = sum(s.get("dropped", 0) for s in fi.stats().values())
        delivered = sum(s.get("delivered", 0) for s in fi.stats().values())
        assert dropped > 0, "the plan never fired — not a chaos run"
        assert delivered > 0
    finally:
        for nd in nodes:
            nd.stop()
