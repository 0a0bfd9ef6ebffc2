"""The fleet's binary wire (ISSUE 20): frame codec edge cases
(truncation, oversize, version/auth refusals), the zero-copy payload
codec and its restricted pickle fallback, multiplexed
``WireClient``/``WirePool`` semantics (including eviction + re-dial
after a SIGKILL'd peer), blockwise-int8 weight distribution through a
real ``stage_tree`` round trip, the fleet's ``wire`` telemetry events
-> metrics bridge -> obs_report rendering, and the pooled binary
wire against the in-process engine."""

import base64
import importlib.util
import json
import os
import pickle
import signal
import socket
import struct
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.observability import StepTelemetry
from bigdl_tpu.observability.metrics import MetricsRegistry
from bigdl_tpu.serving import ServingEngine, transport
from bigdl_tpu.serving.transport import (MAX_FRAME_BYTES, ReplicaCallError,
                                         WireAuthError, WireClient,
                                         WireFrameError, WirePool,
                                         WireProtocolError,
                                         WireVersionError, decode_payload,
                                         dequantize_wire_tree,
                                         encode_payload,
                                         quantize_tree_for_wire,
                                         serve_connection)
from bigdl_tpu.serving.worker import ReplicaServer, call, send_msg
from bigdl_tpu.utils.random_generator import RNG

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp(seed=0, hidden=16):
    RNG.set_seed(seed)
    m = (nn.Sequential().add(nn.Linear(8, hidden)).add(nn.ReLU())
         .add(nn.Linear(hidden, 4)))
    m.build(jax.ShapeDtypeStruct((2, 8), jnp.float32))
    return m


def _xs(n=8, seed=0):
    return np.random.default_rng(seed).standard_normal((n, 8)) \
        .astype("float32")


def _engine(telemetry=None, **kw):
    eng = ServingEngine(_mlp(), max_batch_size=4, max_wait_ms=1.0,
                        telemetry=telemetry, **kw)
    eng.precompile(example_feature=_xs(2)[0])
    return eng


class _StubServer:
    """A transport-speaking stub (no engine, no jax in the loop): every
    accepted connection runs ``serve_connection`` with ``handler``."""

    def __init__(self, handler, token=None, max_frame_bytes=None,
                 port=0):
        self.handler = handler
        self.token = token
        self.max_frame_bytes = max_frame_bytes
        self.sock = socket.socket()
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", port))
        self.sock.listen(8)
        self.port = self.sock.getsockname()[1]
        self._accept = threading.Thread(target=self._loop, daemon=True)
        self._accept.start()

    def _loop(self):
        while True:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            threading.Thread(
                target=serve_connection,
                args=(conn, self.handler),
                kwargs={"token": self.token,
                        "max_frame_bytes": self.max_frame_bytes},
                daemon=True).start()

    def close(self):
        self.sock.close()


def _echo(req):
    return {"ok": True, "result": {k: v for k, v in req.items()
                                   if k != "op"}}


# --------------------------------------------------------------------------- #
# Payload codec.
# --------------------------------------------------------------------------- #

class TestPayloadCodec:
    def test_round_trip_no_pickle(self):
        payload = {
            "a": np.arange(12, dtype=np.float32).reshape(3, 4),
            "nested": [np.zeros((2, 2), np.int8),
                       (1, "two", np.float64(3.5))],
            "blob": b"\x00\x01raw",
            "scalar": np.int32(7),
            "empty": np.zeros((0, 4), np.float32),
            "keys": {(0, 1): "tuple-key", 3: "int-key"},
            "spoof": {"__t__": "a user dict carrying a marker key"},
            "none": None, "flag": True,
        }
        skeleton, tensors, stats = encode_payload(payload)
        assert stats["pickle_fallbacks"] == 0, \
            "this tree is fully wire-native; nothing may ride pickle"
        assert len(tensors) == 3
        json.dumps(skeleton)                   # the skeleton IS JSON-able
        out = decode_payload(skeleton, tensors)
        np.testing.assert_array_equal(out["a"], payload["a"])
        np.testing.assert_array_equal(out["nested"][0],
                                      payload["nested"][0])
        assert out["nested"][1][:2] == (1, "two")
        assert out["nested"][1][2] == np.float64(3.5)
        assert out["blob"] == payload["blob"]
        assert out["scalar"] == 7 and out["empty"].shape == (0, 4)
        assert out["keys"] == payload["keys"]
        assert out["spoof"] == {"__t__": "a user dict carrying a "
                                         "marker key"}
        assert out["none"] is None and out["flag"] is True

    def test_received_tensor_is_writable(self):
        # the zero-copy contract: np.frombuffer over the frame's own
        # bytearray yields an array the receiver OWNS (jax staging and
        # in-place consumers must not trip read-only flags)
        a = np.arange(6, dtype=np.float32)
        payload = bytearray(transport._tensor_frame_parts(a)[0])
        for part in transport._tensor_frame_parts(a)[1:]:
            payload += bytes(part)
        out = transport._decode_tensor(bytearray(payload))
        assert out.flags.writeable
        np.testing.assert_array_equal(out, a)

    def test_tensor_frame_byte_mismatch_refused(self):
        hdr = json.dumps({"d": "float32", "s": [4]}).encode()
        frame = bytearray(struct.pack(">I", len(hdr)) + hdr + b"\x00" * 7)
        with pytest.raises(WireProtocolError, match="carries 7 bytes"):
            transport._decode_tensor(frame)

    def test_legacy_metadata_rides_restricted_pickle(self):
        import collections
        payload = {"d": collections.deque([1, 2, 3])}
        skeleton, tensors, stats = encode_payload(payload)
        assert stats["pickle_fallbacks"] == 1
        out = decode_payload(skeleton, tensors)
        assert list(out["d"]) == [1, 2, 3]

    def test_restricted_unpickler_refuses_hostile_global(self):
        evil = base64.b64encode(
            pickle.dumps(subprocess.Popen)).decode()
        with pytest.raises(WireProtocolError,
                           match="refused subprocess.Popen"):
            decode_payload({"__py__": evil}, [])


# --------------------------------------------------------------------------- #
# Raw framing: truncation, oversize, foreign bytes.
# --------------------------------------------------------------------------- #

class TestFraming:
    def _pair(self):
        a, b = socket.socketpair()
        a.settimeout(5.0)
        b.settimeout(5.0)
        return a, b

    def test_truncated_mid_frame_raises_with_byte_count(self):
        a, b = self._pair()
        # a valid header claiming 100 payload bytes, then 10 and a hangup
        a.sendall(transport._HEADER.pack(b"BW", 1, transport.FT_MSG, 100))
        a.sendall(b"x" * 10)
        a.close()
        with pytest.raises(WireProtocolError,
                           match=r"closed mid-frame \(10/100"):
            transport._recv_frame(b)
        b.close()

    def test_truncated_mid_tensor_raises(self):
        # the multi-frame message case: skeleton lands whole, the peer
        # dies inside the tensor frame that follows
        a, b = self._pair()
        conn = transport.WireConnection(b)
        env = json.dumps({"id": 1, "nt": 1,
                          "body": {"x": {"__t__": 0}}}).encode()
        a.sendall(transport._HEADER.pack(b"BW", 1, transport.FT_MSG,
                                         len(env)) + env)
        hdr = json.dumps({"d": "float32", "s": [1024]}).encode()
        a.sendall(transport._HEADER.pack(
            b"BW", 1, transport.FT_TENSOR, 4 + len(hdr) + 4096))
        a.sendall(struct.pack(">I", len(hdr)) + hdr + b"\x00" * 100)
        a.close()
        with pytest.raises(WireProtocolError, match="closed mid-frame"):
            conn.recv_message()
        b.close()

    def test_bad_magic_refused(self):
        a, b = self._pair()
        a.sendall(struct.pack(">2sBBI", b"GE", 1, 4, 0))   # HTTP-ish junk
        with pytest.raises(WireProtocolError, match="bad frame magic"):
            transport._recv_frame(b)
        a.close(), b.close()

    def test_foreign_version_refused(self):
        a, b = self._pair()
        a.sendall(struct.pack(">2sBBI", b"BW", 9, 4, 0))
        with pytest.raises(WireVersionError, match="wire version 9"):
            transport._recv_frame(b)
        a.close(), b.close()

    def test_oversize_length_refused_before_allocation(self):
        a, b = self._pair()
        a.sendall(struct.pack(">2sBBI", b"BW", 1, 4, MAX_FRAME_BYTES + 1))
        with pytest.raises(WireFrameError, match="refused before"):
            transport._recv_frame(b)
        a.close(), b.close()

    def test_outbound_oversize_refused(self):
        a, b = self._pair()
        conn = transport.WireConnection(a, max_frame_bytes=1024)
        with pytest.raises(WireFrameError, match="exceeds the 1024"):
            conn.send_message({"x": np.zeros(4096, np.float32)}, 1)
        a.close(), b.close()

    def test_pickle_wire_cap_is_typed(self):
        # satellite: the legacy wire's cap refusal is the same typed
        # error family (and still a ValueError for legacy callers)
        class _Cap:
            def sendall(self, data):
                raise AssertionError("oversize must refuse before send")
        big = {"x": b"\x00" * (transport.MAX_FRAME_BYTES + 1)}
        with pytest.raises(WireFrameError):
            send_msg(_Cap(), big)
        assert issubclass(WireFrameError, ValueError)


# --------------------------------------------------------------------------- #
# Handshake: version + auth refusals answer TYPED, never hang.
# --------------------------------------------------------------------------- #

class TestHandshake:
    def test_wrong_token_refused(self):
        srv = _StubServer(_echo, token="s3cret")
        try:
            with pytest.raises(WireAuthError, match="run token"):
                WireClient("127.0.0.1", srv.port, token="wrong")
        finally:
            srv.close()

    def test_matching_token_accepted(self):
        srv = _StubServer(_echo, token="s3cret")
        try:
            cli = WireClient("127.0.0.1", srv.port, token="s3cret")
            assert cli.request("ping", x=1) == {"x": 1}
            cli.close()
        finally:
            srv.close()

    def test_version_mismatch_answers_typed_error(self):
        srv = _StubServer(_echo)
        sock = socket.create_connection(("127.0.0.1", srv.port),
                                        timeout=5.0)
        try:
            ftype, payload = transport._recv_frame(sock)
            assert ftype == transport.FT_HELLO
            # a client from the future: AUTH claiming wire version 2
            body = json.dumps({"v": 2, "digest": ""}).encode()
            transport._send_frame(sock, transport.FT_AUTH, [body])
            with pytest.raises(WireVersionError, match="version 2"):
                ftype, payload = transport._recv_frame(sock)
                assert ftype == transport.FT_ERR
                transport._raise_wire_error(payload)
        finally:
            sock.close()
            srv.close()

    def test_default_token_rides_env(self, monkeypatch):
        monkeypatch.setenv("BIGDL_RUN_TOKEN", "envtok")
        srv = _StubServer(_echo, token=transport.run_token())
        try:
            cli = WireClient("127.0.0.1", srv.port)   # defaults to env
            assert cli.request("ping") == {}
            cli.close()
            monkeypatch.setenv("BIGDL_RUN_TOKEN", "other")
            with pytest.raises(WireAuthError):
                WireClient("127.0.0.1", srv.port)
        finally:
            srv.close()

    def test_tcp_nodelay_set_on_client(self):
        srv = _StubServer(_echo)
        try:
            cli = WireClient("127.0.0.1", srv.port)
            assert cli._conn.sock.getsockopt(socket.IPPROTO_TCP,
                                             socket.TCP_NODELAY) != 0
            cli.close()
        finally:
            srv.close()


# --------------------------------------------------------------------------- #
# Multiplexing + pool semantics.
# --------------------------------------------------------------------------- #

class TestClientAndPool:
    def test_multiplexed_fast_overtakes_slow(self):
        def handler(req):
            if req.get("op") == "slow":
                time.sleep(0.5)
            return {"ok": True, "result": req["op"]}
        srv = _StubServer(handler)
        cli = WireClient("127.0.0.1", srv.port)
        try:
            done = []
            def run(op):
                cli.request(op)
                done.append(op)
            ts = [threading.Thread(target=run, args=(op,))
                  for op in ("slow", "fast")]
            ts[0].start()
            time.sleep(0.05)               # slow is in flight first
            ts[1].start()
            for t in ts:
                t.join(10)
            assert done == ["fast", "slow"], \
                "one stalled op must not head-of-line-block the socket"
        finally:
            cli.close()
            srv.close()

    def test_oversize_response_answers_error_envelope(self):
        def handler(req):
            return {"ok": True,
                    "result": np.zeros(1 << 16, np.float32)}
        srv = _StubServer(handler, max_frame_bytes=4096)
        cli = WireClient("127.0.0.1", srv.port, max_frame_bytes=4096)
        try:
            with pytest.raises(ReplicaCallError) as ei:
                cli.request("big")
            assert ei.value.error_type == "WireFrameError"
        finally:
            cli.close()
            srv.close()

    def test_rpc_timeout_leaves_connection_healthy(self):
        def handler(req):
            if req.get("op") == "hang":
                time.sleep(1.0)
            return {"ok": True, "result": req["op"]}
        srv = _StubServer(handler)
        cli = WireClient("127.0.0.1", srv.port)
        try:
            with pytest.raises(TimeoutError):
                cli.request("hang", rpc_timeout=0.1)
            assert not cli.broken
            assert cli.request("ok") == "ok"   # late reply was dropped
        finally:
            cli.close()
            srv.close()

    def test_pool_eviction_and_redial_after_sigkill(self, tmp_path):
        """A SIGKILL'd peer process: in-flight requests fail typed, the
        broken connections are EVICTED, and once a successor listens on
        the same port the pool re-dials under backoff and recovers."""
        child_src = (
            "import socket, sys, threading\n"
            "from bigdl_tpu.serving.transport import serve_connection\n"
            "srv = socket.socket()\n"
            "srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)\n"
            "srv.bind(('127.0.0.1', int(sys.argv[1])))\n"
            "srv.listen(8)\n"
            "print(srv.getsockname()[1], flush=True)\n"
            "def h(req):\n"
            "    return {'ok': True, 'result': 'pong'}\n"
            "while True:\n"
            "    c, _ = srv.accept()\n"
            "    threading.Thread(target=serve_connection, args=(c, h),\n"
            "                     daemon=True).start()\n")
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

        def spawn(port):
            p = subprocess.Popen(
                [sys.executable, "-c", child_src, str(port)],
                env=env, stdout=subprocess.PIPE, cwd=REPO, text=True)
            got = int(p.stdout.readline())
            return p, got

        proc, port = spawn(0)
        pool = WirePool("127.0.0.1", port, size=2,
                        backoff_base_s=0.01, backoff_max_s=0.05)
        try:
            assert pool.request("ping") == "pong"
            assert pool.request("ping") == "pong"
            assert pool.connections == 2
            os.kill(proc.pid, signal.SIGKILL)
            proc.wait(10)
            with pytest.raises((ConnectionError, TimeoutError)):
                for _ in range(4):             # drain every pooled conn
                    pool.request("ping", rpc_timeout=2.0)
            assert pool.connections == 0, "broken connections evicted"
            proc, port2 = spawn(port)          # successor on SAME port
            assert port2 == port
            deadline = time.time() + 10
            while True:                        # re-dial under backoff
                try:
                    assert pool.request("ping") == "pong"
                    break
                except ConnectionError:
                    if time.time() > deadline:
                        raise
                    time.sleep(0.02)
        finally:
            pool.close()
            proc.kill()
            proc.wait(10)


# --------------------------------------------------------------------------- #
# Worker integration: weights over the wire, honest wire_bytes audit.
# --------------------------------------------------------------------------- #

class TestWeightDistribution:
    def test_quantize_tree_round_trip_bounds(self):
        rng = np.random.default_rng(0)
        tree = {"w": rng.standard_normal((64, 64)).astype(np.float32),
                "b": rng.standard_normal((4,)).astype(np.float32),
                "step": 7}
        q = quantize_tree_for_wire(tree)
        assert q["w"].get("__q8__") == 1
        assert q["b"] is tree["b"], "tiny leaves ship raw"
        assert q["step"] == 7
        deq = dequantize_wire_tree(q)
        assert deq["w"].dtype == np.float32
        block_absmax = np.abs(tree["w"]).max()
        assert np.abs(deq["w"] - tree["w"]).max() <= \
            0.51 * block_absmax / 127 + 1e-7
        wire = transport.tree_wire_bytes(q)
        assert wire < 0.35 * transport.tree_wire_bytes(tree)

    def test_stage_tree_int8_commit_records_wire_bytes(self, tmp_path):
        tel = StepTelemetry(str(tmp_path), run_name="t", trace=False)
        eng = _engine(telemetry=tel)
        srv = ReplicaServer(eng, port=0).start()
        cli = WireClient("127.0.0.1", srv.port)
        try:
            params = eng.model.parameters()[0]
            qtree = quantize_tree_for_wire(params, min_size=64)
            tok, out_bytes, _ = cli.request_ex(
                "stage_tree", params=qtree, weight_wire="int8")
            ok, reason = cli.request("gate", token=tok)
            assert ok, reason
            assert cli.request("commit", token=tok, version=2,
                               digest="d2", wire_bytes=out_bytes,
                               weight_wire="int8")
            h = cli.request("health")
            assert h["version"]["version"] == 2
        finally:
            cli.close()
            srv.close()
            eng.close()
            tel.close()
        evs = [json.loads(l) for l in
               open(os.path.join(str(tmp_path), "telemetry.jsonl"))
               if '"param_refresh"' in l]
        refresh = [e for e in evs if e["kind"] == "param_refresh"]
        assert refresh, "commit must land a param_refresh audit event"
        assert refresh[-1]["wire_bytes"] == out_bytes
        assert refresh[-1]["weight_wire"] == "int8"

    def test_stage_tree_refuses_src_layout(self):
        eng = _engine()
        srv = ReplicaServer(eng, port=0).start()
        cli = WireClient("127.0.0.1", srv.port)
        try:
            with pytest.raises(ReplicaCallError, match="stage_tree"):
                cli.request("stage_tree",
                            params=eng.model.parameters()[0],
                            src_layout={"mesh": [2]})
        finally:
            cli.close()
            srv.close()
            eng.close()

    def test_predict_bit_identical_across_transports(self):
        eng = _engine()
        srv_b = ReplicaServer(eng, port=0, transport="binary").start()
        srv_p = ReplicaServer(eng, port=0, transport="pickle").start()
        try:
            for row in _xs(4):
                yb = call("127.0.0.1", srv_b.port, "predict",
                          feature=row)
                yp = call("127.0.0.1", srv_p.port, "predict",
                          feature=row, transport="pickle")
                np.testing.assert_array_equal(np.asarray(yb),
                                              np.asarray(yp))
        finally:
            srv_b.close()
            srv_p.close()
            eng.close()


# --------------------------------------------------------------------------- #
# Wire observability: fleet events -> metrics bridge -> obs_report.
# --------------------------------------------------------------------------- #

def _load_obs_report():
    spec = importlib.util.spec_from_file_location(
        "_wire_obs", os.path.join(REPO, "tools", "obs_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestWireObservability:
    def test_wire_events_metrics_and_report(self, tmp_path):
        from bigdl_tpu.serving import InProcessReplica, ServingFleet

        tel = StepTelemetry(str(tmp_path), run_name="t", trace=False)
        metrics = MetricsRegistry()
        tel.attach_metrics(metrics)
        eng = _engine()
        fleet = ServingFleet([InProcessReplica(eng)], telemetry=tel,
                             metrics=metrics, wire_flush_every=4)
        try:
            for i in range(6):                 # crosses one flush edge
                fleet._note_wire(1, "predict", 0.002 + i * 1e-4,
                                 100, 300)
            fleet._note_wire(1, "stage_tree", 0.1, 50_000, 200)
            live = fleet.wire_stats()
            assert live, "unflushed remainder visible via wire_stats"
        finally:
            fleet.close()                      # flushes the remainder
            eng.close()
            tel.close()
        evs = [json.loads(l) for l in
               open(os.path.join(str(tmp_path), "telemetry.jsonl"))]
        wire = [e for e in evs
                if e["kind"] == "fleet" and e.get("event") == "wire"]
        verbs = {e["verb"]: e for e in wire}
        assert sum(e["calls"] for e in wire
                   if e["verb"] == "predict") == 6
        assert verbs["stage_tree"]["bytes_sent"] == 50_000
        assert all(r > 0 for e in wire for r in e["rtt_s"])
        text = metrics.render()
        assert ('bigdl_fleet_wire_bytes_total{verb="stage_tree",'
                'direction="sent"} 50000') in text
        assert 'bigdl_fleet_wire_rtt_seconds_bucket' in text
        report = _load_obs_report().build_report(str(tmp_path))
        rows = {r["verb"]: r for r in report["fleet"]["wire"]}
        assert rows["predict"]["calls"] == 6
        assert rows["stage_tree"]["bytes_sent"] == 50_000
        assert rows["predict"]["rtt_p50_ms"] > 0
        rendered = _load_obs_report().format_report(report)
        assert "wire stage_tree:" in rendered

    def test_subprocess_replica_pickle_path_still_notes_rtt(self):
        # the pickle escape hatch reports rtt with zero byte counts --
        # the schema stays uniform across transports
        from bigdl_tpu.serving.fleet import SubprocessReplica

        rep = SubprocessReplica(lambda a: (None, 0), transport="pickle")
        seen = []
        rep._wire_sink = lambda *a: seen.append(a)
        rep._note_wire("predict", 0.01, 0, 0)
        assert seen == [(rep.rid, "predict", 0.01, 0, 0)]


# --------------------------------------------------------------------------- #
# The binary wire against the engine it fronts, under concurrent clients.
# --------------------------------------------------------------------------- #

class TestBinaryWireAgainstInProcessEngine:
    def test_pooled_predict_and_staged_weight_bytes(self):
        """Two clients share a ``WirePool`` of two connections: every
        answer is, bit for bit, what the engine answers in process at
        some rung of its batch ladder (a tick's bucket depends on
        timing, the bits on the bucket), requests sent one at a time
        equal the in-process ``predict``, no array rode pickle, nothing
        compiled; and the serving tree staged as blockwise int8 costs at
        most 0.35x its fp32 bytes, both measured on the wire."""
        from concurrent.futures import ThreadPoolExecutor

        from bigdl_tpu.observability.watchdogs import backend_compile_count

        RNG.set_seed(0)
        model = (nn.Sequential().add(nn.Linear(16, 128)).add(nn.ReLU())
                 .add(nn.Linear(128, 128)).add(nn.ReLU())
                 .add(nn.Linear(128, 10)))
        model.build(jax.ShapeDtypeStruct((2, 16), jnp.float32))
        xs = np.random.default_rng(0).standard_normal((8, 16)) \
            .astype("float32")
        eng = ServingEngine(model, max_batch_size=2, max_wait_ms=1.0)
        srv = ReplicaServer(eng, port=0, transport="binary").start()
        pool = WirePool("127.0.0.1", srv.port, size=2)
        cli = WireClient("127.0.0.1", srv.port)
        try:
            eng.precompile()
            before = backend_compile_count()

            def over_the_wire(x):
                return np.asarray(pool.request("predict", feature=x))

            with ThreadPoolExecutor(2) as clients:
                got = list(clients.map(over_the_wire, xs))
            for x, y in zip(xs, got):
                assert any(np.array_equal(y, eng.predict_at(x, b))
                           for b in eng.ladder)
            for x in xs[:4]:
                np.testing.assert_array_equal(over_the_wire(x),
                                              eng.predict(x))
            assert pool.stats()["pickle_fallbacks"] == 0
            assert backend_compile_count() - before == 0

            params = eng.model.parameters()[0]
            staged = {}
            for wire, tree in (("fp32", params),
                               ("int8", quantize_tree_for_wire(params))):
                token, staged[wire], _ = cli.request_ex(
                    "stage_tree", rpc_timeout=120.0, params=tree,
                    weight_wire=wire)
                cli.request_ex("release", token=token)
            assert staged["int8"] <= 0.35 * staged["fp32"]
        finally:
            cli.close()
            pool.close()
            srv.close()
            eng.close()
