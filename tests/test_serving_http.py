"""HTTP frontend for the online serving engine: predict routes (JSON and
npy bodies), metrics/healthz, and the error-to-status contract."""

import io
import json
import re
import urllib.error
import urllib.request

import numpy as np
import pytest

from analytics_zoo_tpu.serving import BatcherConfig, ServingEngine
from analytics_zoo_tpu.serving.batcher import (
    DeadlineExceededError,
    QueueFullError,
)
from analytics_zoo_tpu.serving.engine import ModelNotFoundError
from analytics_zoo_tpu.serving.http import serve, status_for_exception


class Doubler:
    """Minimal do_predict duck-type: y = 2x."""

    def do_predict(self, x):
        return np.asarray(x, np.float32) * 2.0


@pytest.fixture
def server():
    engine = ServingEngine()
    engine.register("dbl", Doubler(), example_input=np.zeros((1, 3)),
                    config=BatcherConfig(max_batch_size=8, max_wait_ms=1.0))
    srv, _t = serve(engine, port=0)
    yield f"http://127.0.0.1:{srv.server_port}", engine
    srv.shutdown()
    engine.shutdown()


def _post(url, body: bytes, headers=None):
    req = urllib.request.Request(url, data=body, headers=headers or {})
    with urllib.request.urlopen(req, timeout=10) as resp:
        return resp.status, resp.headers, resp.read()


def test_predict_json(server):
    base, _ = server
    x = [[1.0, 2.0, 3.0], [4.0, 5.0, 6.0]]
    code, headers, body = _post(
        f"{base}/v1/models/dbl:predict",
        json.dumps({"instances": x}).encode(),
        {"Content-Type": "application/json"})
    assert code == 200
    # every response carries the request's trace id (docs/observability.md)
    assert len(headers["X-Zoo-Trace-Id"]) == 16
    np.testing.assert_allclose(json.loads(body)["predictions"],
                               np.asarray(x) * 2.0)


def test_predict_npy_roundtrip(server):
    base, _ = server
    x = np.arange(6, dtype=np.float32).reshape(2, 3)
    buf = io.BytesIO()
    np.save(buf, x)
    code, headers, body = _post(
        f"{base}/v1/models/dbl:predict", buf.getvalue(),
        {"Content-Type": "application/x-npy",
         "Accept": "application/x-npy"})
    assert code == 200
    assert headers["Content-Type"] == "application/x-npy"
    np.testing.assert_array_equal(np.load(io.BytesIO(body)), x * 2.0)


def test_predict_json_multi_input():
    """A model registered with several input arrays (BERT's ids / token
    types / mask) takes ``{"inputs": [...]}``: one array per model input,
    dtypes coerced to the registered signature."""
    class Mix:
        def do_predict(self, x):
            ids, mask = x
            assert ids.dtype == np.int32 and mask.dtype == np.float32
            return ids.astype(np.float32) * mask

    engine = ServingEngine()
    engine.register("mix", Mix(), example_input=[
        np.zeros((1, 3), np.int32), np.zeros((1, 3), np.float32)],
        config=BatcherConfig(max_batch_size=8, max_wait_ms=1.0))
    srv, _t = serve(engine, port=0)
    try:
        url = f"http://127.0.0.1:{srv.server_port}/v1/models/mix:predict"
        ids, mask = [[1, 2, 3], [4, 5, 6]], [[1.0, 0.0, 1.0], [0.5, 1.0, 0.0]]
        code, _, body = _post(url, json.dumps({"inputs": [ids, mask]}).encode())
        assert code == 200
        np.testing.assert_allclose(json.loads(body)["predictions"],
                                   np.asarray(ids) * np.asarray(mask))
        for bad in ({"inputs": []}, {"inputs": [ids]},
                    {"inputs": [ids, [[1.0], [1.0, 2.0]]]}):
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(url, json.dumps(bad).encode())
            assert e.value.code == 400
    finally:
        srv.shutdown()
        engine.shutdown()


def test_versioned_route_and_unknown_model(server):
    base, _ = server
    payload = json.dumps({"instances": [[1.0, 1.0, 1.0]]}).encode()
    code, _, _ = _post(f"{base}/v1/models/dbl/versions/1:predict", payload)
    assert code == 200
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(f"{base}/v1/models/ghost:predict", payload)
    assert e.value.code == 404
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(f"{base}/v1/models/dbl/versions/9:predict", payload)
    assert e.value.code == 404


def test_malformed_bodies_400(server):
    base, _ = server
    for body in (b"not json", b'{"wrong": 1}',
                 json.dumps({"instances": [[1], [2, 3]]}).encode()):
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(f"{base}/v1/models/dbl:predict", body)
        assert e.value.code == 400, body


def test_metrics_and_healthz(server):
    base, _ = server
    _post(f"{base}/v1/models/dbl:predict",
          json.dumps({"instances": [[1.0, 2.0, 3.0]]}).encode())
    with urllib.request.urlopen(f"{base}/metrics", timeout=10) as resp:
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/plain")
        text = resp.read().decode()
    assert 'zoo_serving_requests_total{model="dbl"}' in text
    assert "zoo_serving_latency_seconds" in text
    with urllib.request.urlopen(f"{base}/healthz", timeout=10) as resp:
        health = json.loads(resp.read())
    assert health["status"] == "ok"
    assert "dbl" in health["models"]
    assert health["models"]["dbl"]["latest"] == "1"


def test_status_mapping_contract():
    """429 backpressure / 504 deadline / 404 unknown / 400 bad input /
    500 fault — the documented client contract. Only the registry's
    ModelNotFoundError is a 404; a bare KeyError (e.g. from inside a
    model's predict) is a server fault, not a routing miss."""
    assert status_for_exception(QueueFullError("full")) == 429
    assert status_for_exception(DeadlineExceededError("late")) == 504
    assert status_for_exception(ModelNotFoundError("no model")) == 404
    assert status_for_exception(KeyError("inside predict")) == 500
    assert status_for_exception(ValueError("bad")) == 400
    assert status_for_exception(RuntimeError("boom")) == 500


def test_predict_path_keyerror_is_500_not_404(server):
    """A KeyError raised by the model itself must surface as 500 — a 404
    would tell the client the model doesn't exist."""
    base, engine = server

    class KeyErrorModel:
        def do_predict(self, x):
            raise KeyError("missing feature column")

    engine.register("kerr", KeyErrorModel(),
                    example_input=np.zeros((1, 3)),
                    config=BatcherConfig(max_batch_size=4, max_wait_ms=1.0))
    payload = json.dumps({"instances": [[1.0, 2.0, 3.0]]}).encode()
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(f"{base}/v1/models/kerr:predict", payload)
    assert e.value.code == 500


def test_signature_mismatch_is_400(server):
    """Trailing-dim mismatch against the registered example is rejected at
    the boundary with 400 (never reaches a flush where it could take a
    batch down)."""
    base, _ = server
    payload = json.dumps({"instances": [[1.0, 2.0]]}).encode()  # dim 2 != 3
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(f"{base}/v1/models/dbl:predict", payload)
    assert e.value.code == 400
    # error responses carry the trace id too — a failing request is
    # exactly the one an operator wants to find in the trace
    assert len(e.value.headers["X-Zoo-Trace-Id"]) == 16


def test_nonfinite_predictions_are_null_with_marker(server):
    """NaN/Inf in model output (ISSUE 7 satellite): JSON has no literal
    for them, and Python's json.dumps emits bare ``NaN`` — invalid JSON
    that strict parsers reject. The contract: non-finite values serialize
    as ``null`` and the response carries a top-level
    ``"non_finite": true`` marker so clients can tell a real null from a
    poisoned prediction."""
    base, engine = server

    class NaNer:
        def do_predict(self, x):
            out = np.asarray(x, np.float32) * 2.0
            out = np.array(out)
            out[0, 0] = np.nan
            out[0, 2] = np.inf
            return out

    engine.register("nanner", NaNer(), example_input=np.zeros((1, 3)),
                    config=BatcherConfig(max_batch_size=4, max_wait_ms=1.0))
    code, _, body = _post(
        f"{base}/v1/models/nanner:predict",
        json.dumps({"instances": [[1.0, 2.0, 3.0]]}).encode(),
        {"Content-Type": "application/json"})
    assert code == 200
    payload = json.loads(body)  # must be strictly valid JSON
    assert payload["non_finite"] is True
    assert payload["predictions"][0][0] is None
    assert payload["predictions"][0][2] is None
    assert payload["predictions"][0][1] == pytest.approx(4.0)


def test_nonfinite_marker_absent_for_finite_output(server):
    base, _ = server
    code, _, body = _post(
        f"{base}/v1/models/dbl:predict",
        json.dumps({"instances": [[1.0, 2.0, 3.0]]}).encode(),
        {"Content-Type": "application/json"})
    assert code == 200
    assert "non_finite" not in json.loads(body)


def test_retry_after_present_and_integer_on_429_and_503(server):
    """The transport contract (ISSUE 14): every 429 and 503 carries
    ``Retry-After`` in integer seconds — quota 429s from the bucket's
    real refill deficit, draining 503s from the drain hint."""
    base, engine = server
    from analytics_zoo_tpu.serving.quota import QuotaConfig, TenantQuota

    engine.quota.configure(QuotaConfig(
        tenants={"slowpoke": TenantQuota(rate=0.001, burst=1)}))
    payload = json.dumps({"instances": [[1.0, 2.0, 3.0]]}).encode()
    _post(f"{base}/v1/models/dbl:predict", payload,
          {"X-Zoo-Tenant": "slowpoke"})          # burns the single token
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(f"{base}/v1/models/dbl:predict", payload,
              {"X-Zoo-Tenant": "slowpoke"})
    assert e.value.code == 429
    assert re.fullmatch(r"\d+", e.value.headers["Retry-After"])
    engine.quota.configure(QuotaConfig())

    engine.drain(5.0)                             # empty engine: instant
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(f"{base}/v1/models/dbl:predict", payload)
    assert e.value.code == 503
    assert re.fullmatch(r"\d+", e.value.headers["Retry-After"])
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(f"{base}/healthz", timeout=10)
    assert e.value.code == 503
    assert re.fullmatch(r"\d+", e.value.headers["Retry-After"])


def test_incoming_trace_id_adopted_invalid_replaced(server):
    """A valid 16-hex ``X-Zoo-Trace-Id`` is adopted (the front door
    relies on this to join spans across the process hop); junk ids are
    replaced, never echoed."""
    base, _ = server
    payload = json.dumps({"instances": [[1.0, 2.0, 3.0]]}).encode()
    _c, headers, _b = _post(f"{base}/v1/models/dbl:predict", payload,
                            {"X-Zoo-Trace-Id": "deadbeefdeadbeef"})
    assert headers["X-Zoo-Trace-Id"] == "deadbeefdeadbeef"
    for junk in ("xyz", "DEADBEEFDEADBEEF", "deadbeef", "a" * 32):
        _c, headers, _b = _post(f"{base}/v1/models/dbl:predict", payload,
                                {"X-Zoo-Trace-Id": junk})
        assert headers["X-Zoo-Trace-Id"] != junk
        assert re.fullmatch(r"[0-9a-f]{16}", headers["X-Zoo-Trace-Id"])


def test_listener_socket_options(server):
    """SO_REUSEADDR and TCP_NODELAY are set explicitly on the listener
    (SO_REUSEPORT where the platform has it) — restart-without-
    TIME_WAIT-stall and no Nagle delay on small predict responses."""
    import socket as socket_mod

    from analytics_zoo_tpu.serving.http import ZooHTTPServer

    engine = ServingEngine()
    srv = ZooHTTPServer(("127.0.0.1", 0), _probe_handler(engine))
    try:
        s = srv.socket
        assert s.getsockopt(socket_mod.SOL_SOCKET,
                            socket_mod.SO_REUSEADDR) != 0
        assert s.getsockopt(socket_mod.IPPROTO_TCP,
                            socket_mod.TCP_NODELAY) != 0
        if hasattr(socket_mod, "SO_REUSEPORT"):
            assert s.getsockopt(socket_mod.SOL_SOCKET,
                                socket_mod.SO_REUSEPORT) != 0
    finally:
        srv.server_close()
        engine.shutdown()


def _probe_handler(engine):
    from analytics_zoo_tpu.serving.http import make_handler

    return make_handler(engine)


def test_http11_keepalive_reuses_connection(server):
    """The handler speaks HTTP/1.1 with Content-Length on every
    response, so one connection serves many requests — what the front
    door's per-worker connection pools depend on."""
    import http.client

    base, _ = server
    host, port = base.replace("http://", "").split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=10)
    try:
        payload = json.dumps({"instances": [[1.0, 2.0, 3.0]]}).encode()
        for _ in range(3):
            conn.request("POST", "/v1/models/dbl:predict", body=payload,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = resp.read()      # must fully drain to reuse
            assert resp.status == 200
            assert resp.version == 11
            assert not resp.will_close
            assert json.loads(body)["predictions"]
    finally:
        conn.close()


def test_nonfinite_npy_roundtrip_preserves_bits(server):
    """The binary path has no such limitation: npy responses carry the
    NaN/Inf bits untouched."""
    base, engine = server

    class InfModel:
        def do_predict(self, x):
            out = np.array(np.asarray(x, np.float32))
            out[0, 0] = np.inf
            out[0, 1] = np.nan
            return out

    engine.register("infm", InfModel(), example_input=np.zeros((1, 3)),
                    config=BatcherConfig(max_batch_size=4, max_wait_ms=1.0))
    buf = io.BytesIO()
    np.save(buf, np.zeros((1, 3), np.float32))
    code, headers, body = _post(
        f"{base}/v1/models/infm:predict", buf.getvalue(),
        {"Content-Type": "application/x-npy",
         "Accept": "application/x-npy"})
    assert code == 200
    out = np.load(io.BytesIO(body))
    assert np.isposinf(out[0, 0]) and np.isnan(out[0, 1])


# -- sequence serving: GET model info + the :generate endpoint (ISSUE 16)


@pytest.fixture(scope="module")
def seq_server():
    """One real seq2seq-backed engine for the generate/model-info tests —
    module-scoped because registration warms the whole prefill grid."""
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    from analytics_zoo_tpu.models.seq2seq import Seq2seqNet
    from analytics_zoo_tpu.serving.sequence import SequenceConfig

    zoo.init_nncontext()
    net = Seq2seqNet(12, 8, (8,), cell_type="lstm", name="s2s_http")
    model = InferenceModel()
    model.do_load_keras(net)
    engine = ServingEngine()
    engine.register(
        "s2s", model,
        example_input=[np.zeros((1, 4), np.int32), np.zeros((1, 3), np.int32)],
        config=BatcherConfig(max_batch_size=1, max_wait_ms=1.0),
        sequence=SequenceConfig(max_prompt_len=4, max_prefill_batch=1,
                                slots=2, max_new_tokens=3, start_token=1))
    srv, _t = serve(engine, port=0)
    yield f"http://127.0.0.1:{srv.server_port}", engine
    srv.shutdown()
    engine.shutdown()


def _get_json(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.status, json.loads(resp.read())


def test_model_info_pins_signature_and_sequence_shape(seq_server):
    """GET /v1/models/<name> is the client's capability probe: the exact
    JSON shape of the input signature (wildcard axes as null) and the
    sequence-serving block (bucket ladders, slot capacity, token caps)
    is API surface — pinned here."""
    base, _ = seq_server
    code, desc = _get_json(f"{base}/v1/models/s2s")
    assert code == 200
    info = desc["versions"][desc["latest"]]
    sig = info["input_signature"]
    assert sig == {"inputs": [{"shape": [4], "dtype": "int32"},
                              {"shape": [3], "dtype": "int32"}],
                   "multi": True}
    seq = info["sequence"]
    assert seq == {"slots": 2, "max_prompt_len": 4, "max_new_tokens": 3,
                   "start_token": 1, "eos_token": None,
                   "prompt_buckets": [1, 2, 4],
                   "prefill_batch_buckets": [1],
                   "queue_depth": 0}


def test_model_info_without_sequence_has_no_block(server):
    base, _ = server
    code, desc = _get_json(f"{base}/v1/models/dbl")
    assert code == 200
    info = desc["versions"][desc["latest"]]
    assert "sequence" not in info
    assert info["input_signature"]["inputs"] == [
        {"shape": [3], "dtype": "float64"}]


def test_generate_roundtrip_matches_engine_api(seq_server):
    base, engine = seq_server
    prompts = [[1, 2, 3], [4], [5, 6, 7, 8]]
    code, headers, body = _post(
        f"{base}/v1/models/s2s:generate",
        json.dumps({"prompts": prompts, "max_new_tokens": 2}).encode(),
        {"Content-Type": "application/json"})
    assert code == 200
    assert len(headers["X-Zoo-Trace-Id"]) == 16
    seqs = json.loads(body)["sequences"]
    assert len(seqs) == 3
    for p, got in zip(prompts, seqs):
        expect = engine.generate("s2s", np.asarray(p), max_new_tokens=2)
        assert got == expect.tolist()


def test_generate_validation_400s(seq_server):
    base, _ = seq_server
    for body in (b"not json",
                 json.dumps({"wrong": 1}).encode(),
                 json.dumps({"prompts": []}).encode(),
                 json.dumps({"prompts": [[]]}).encode(),
                 json.dumps({"prompts": "nope"}).encode(),
                 json.dumps({"prompts": [[0.5, 1.5]]}).encode(),
                 json.dumps({"prompts": [[1, 2, 3, 4, 5]]}).encode()):
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(f"{base}/v1/models/s2s:generate", body)
        assert e.value.code == 400, body


def test_generate_on_non_sequence_model_is_400(server):
    base, _ = server
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(f"{base}/v1/models/dbl:generate",
              json.dumps({"prompts": [[1, 2]]}).encode())
    assert e.value.code == 400
    assert b"sequence" in e.value.read()


def test_generate_unknown_model_is_404(seq_server):
    base, _ = seq_server
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(f"{base}/v1/models/ghost:generate",
              json.dumps({"prompts": [[1]]}).encode())
    assert e.value.code == 404


# -- ops plane: traceparent interop + debug surface (ISSUE 17) --------------


def _payload():
    return json.dumps({"instances": [[1.0, 2.0, 3.0]]}).encode()


def test_traceparent_adopted_and_emitted(server):
    """A well-formed W3C ``traceparent`` is adopted as the trace id (low
    64 bits), and every response emits BOTH headers so house tooling and
    W3C proxies each see their own dialect."""
    base, _ = server
    tid = "aabbccdd00112233"
    tp = f"00-{'0' * 16}{tid}-{tid}-01"
    _c, headers, _b = _post(f"{base}/v1/models/dbl:predict", _payload(),
                            {"traceparent": tp})
    assert headers["X-Zoo-Trace-Id"] == tid
    assert headers["traceparent"] == tp

    # malformed / all-zero traceparent: replaced with a fresh id, and
    # the outgoing traceparent matches that fresh id
    for junk in ("garbage", f"00-{'0' * 32}-{'0' * 16}-01",
                 "01-" + "a" * 32 + "-" + "b" * 16 + "-01"):
        _c, headers, _b = _post(f"{base}/v1/models/dbl:predict",
                                _payload(), {"traceparent": junk})
        fresh = headers["X-Zoo-Trace-Id"]
        assert re.fullmatch(r"[0-9a-f]{16}", fresh) and fresh != tid
        assert headers["traceparent"] == \
            f"00-{'0' * 16}{fresh}-{fresh}-01"


def test_house_trace_header_wins_over_traceparent(server):
    """When both a valid ``X-Zoo-Trace-Id`` and a valid ``traceparent``
    arrive, the house header wins — the front door propagates ids via
    ``X-Zoo-Trace-Id``, and an external proxy's traceparent must not
    re-split a fleet trace mid-hop."""
    base, _ = server
    house = "1111111111111111"
    foreign = "2222222222222222"
    _c, headers, _b = _post(
        f"{base}/v1/models/dbl:predict", _payload(),
        {"X-Zoo-Trace-Id": house,
         "traceparent": f"00-{'0' * 16}{foreign}-{foreign}-01"})
    assert headers["X-Zoo-Trace-Id"] == house
    # an invalid house header falls back to the (valid) traceparent
    _c, headers, _b = _post(
        f"{base}/v1/models/dbl:predict", _payload(),
        {"X-Zoo-Trace-Id": "NOT-HEX",
         "traceparent": f"00-{'0' * 16}{foreign}-{foreign}-01"})
    assert headers["X-Zoo-Trace-Id"] == foreign


def test_debug_flightrecorder_and_slo_endpoints(server):
    """The worker-side ops-plane surface: the flight ring and the SLO
    report are one GET away, as JSON."""
    base, _ = server
    tid = "feedfacecafe0123"
    _post(f"{base}/v1/models/dbl:predict", _payload(),
          {"X-Zoo-Trace-Id": tid})

    with urllib.request.urlopen(f"{base}/v1/debug/flightrecorder",
                                timeout=10) as resp:
        doc = json.loads(resp.read())
    assert doc["capacity"] > 0
    mine = [r for r in doc["records"] if r["trace_id"] == tid]
    assert mine and mine[0]["model"] == "dbl"
    assert mine[0]["outcome"] == "ok"
    assert mine[0]["t_submit"] is not None and mine[0]["t_done"] is not None

    with urllib.request.urlopen(f"{base}/v1/debug/slo",
                                timeout=10) as resp:
        report = json.loads(resp.read())
    byname = {o["name"]: o for o in report["objectives"]}
    assert "availability:dbl" in byname
    assert byname["availability:dbl"]["windows"]


def test_debug_traces_endpoint_serves_spans(server):
    """With tracing on, a request's spans come back from
    ``GET /v1/debug/traces/<id>`` alongside this process's wall anchor
    (what the front door's fleet merge consumes)."""
    from analytics_zoo_tpu.common.observability import get_tracer

    base, _ = server
    tracer = get_tracer()
    tracer.clear()
    tracer.enable()
    try:
        tid = "0123456789abcdef"
        _post(f"{base}/v1/models/dbl:predict", _payload(),
              {"X-Zoo-Trace-Id": tid})
        with urllib.request.urlopen(f"{base}/v1/debug/traces",
                                    timeout=10) as resp:
            index = json.loads(resp.read())
        assert index["enabled"] is True
        assert tid in index["traces"]
        with urllib.request.urlopen(f"{base}/v1/debug/traces/{tid}",
                                    timeout=10) as resp:
            doc = json.loads(resp.read())
        assert doc["trace_id"] == tid
        assert isinstance(doc["wall_anchor"], float)
        names = [s["name"] for s in doc["spans"]]
        assert "serving.request" in names
        assert all(s["trace_id"] == tid for s in doc["spans"])
    finally:
        tracer.disable()
        tracer.clear()


def test_metrics_scrape_refreshes_process_gauges(server):
    """``zoo_process_open_fds`` must be sampled at scrape time, not at
    engine-activity time: two scrapes with fds opened in between — and
    no serving traffic at all — must disagree."""
    import os as _os

    base, _ = server

    def scrape_open_fds():
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
            text = r.read().decode()
        for line in text.splitlines():
            if line.startswith("zoo_process_open_fds"):
                return float(line.rsplit(" ", 1)[1])
        raise AssertionError("zoo_process_open_fds not in /metrics")

    before = scrape_open_fds()
    held = [_os.open(_os.devnull, _os.O_RDONLY) for _ in range(16)]
    try:
        after = scrape_open_fds()
    finally:
        for fd in held:
            _os.close(fd)
    assert after >= before + 16
