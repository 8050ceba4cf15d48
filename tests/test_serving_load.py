"""Serving hardening (VERDICT r3 item 6 + ISSUE 5, Triton scope —
``triton/src/instance.cc``, ``backend.cc``): bounded queue with
backpressure, N concurrent instances, metrics endpoint, model
load/unload, a concurrent-load p50/p99 artifact (slow tier), and the
overload-robustness contract: request deadlines end-to-end, admission
control with Retry-After, circuit-breaker transitions, batch-poison
isolation, and graceful drain under load."""
import json
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
from flexflow_tpu.models import build_mlp
from flexflow_tpu.serving import (BatchScheduler, CircuitBreaker,
                                  CircuitOpenError,
                                  DeadlineExceededError,
                                  DeadlineRejectedError, DrainingError,
                                  InferenceSession, InvalidInputError,
                                  ModelRepository, QueueFullError,
                                  serve_http)


def _mlp_session(buckets=(1, 4, 16)):
    cfg = FFConfig()
    cfg.batch_size = 16
    cfg.only_data_parallel = True
    ff = FFModel(cfg)
    out = build_mlp(ff, 16, in_dim=8, hidden=(16,), num_classes=4)
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy", [],
               output_tensor=out)
    return InferenceSession(ff, batch_buckets=buckets)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def test_bounded_queue_backpressure():
    sess = _mlp_session()

    class Slow:
        input_names = sess.input_names

        def infer(self, inputs):
            import time
            time.sleep(0.3)
            return sess.infer(inputs)

    sched = BatchScheduler(Slow(), max_batch=1, max_queue=2,
                           max_delay_ms=0.0)
    x = np.zeros((1, 8), np.float32)
    results, rejected = [], []

    def fire():
        try:
            results.append(sched.infer({"input": x}, timeout=10))
        except QueueFullError:
            rejected.append(1)

    threads = [threading.Thread(target=fire) for _ in range(12)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert rejected, "12 requests into a 2-deep queue must shed load"
    assert results, "some requests must still complete"
    assert sched.metrics.rejected == len(rejected)
    sched.close()


def test_instances_share_queue():
    sess = _mlp_session()
    sched = BatchScheduler([sess, sess, sess], max_batch=4)
    assert sched.num_instances == 3
    x = np.random.default_rng(0).normal(size=(2, 8)).astype(np.float32)
    outs = [sched.infer({"input": x}) for _ in range(6)]
    assert all(o.shape == (2, 4) for o in outs)
    snap = sched.metrics.snapshot(0)
    assert snap["completed"] == 6
    assert snap["latency_p99_ms"] > 0
    sched.close()


def test_metrics_and_unload_endpoints():
    repo = ModelRepository()
    repo.register("mlp", _mlp_session(), instances=2)
    port = _free_port()
    srv, thread, scheds = serve_http(repo, port=port, block=False)
    try:
        base = f"http://127.0.0.1:{port}"
        x = np.zeros((1, 8), np.float32)
        body = json.dumps({"inputs": [{
            "name": "input", "shape": [1, 8],
            "data": x.ravel().tolist()}]}).encode()
        r = urllib.request.urlopen(urllib.request.Request(
            f"{base}/v2/models/mlp/infer", data=body,
            headers={"Content-Type": "application/json"}))
        assert r.status == 200
        m = json.loads(urllib.request.urlopen(
            f"{base}/v2/metrics").read())
        assert m["models"]["mlp"]["completed"] >= 1
        assert m["models"]["mlp"]["instances"] == 2
        # unload, then infer -> 404
        r = urllib.request.urlopen(urllib.request.Request(
            f"{base}/v2/repository/models/mlp/unload", data=b"{}"))
        assert r.status == 200
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                f"{base}/v2/models/mlp/infer", data=body))
        assert ei.value.code == 404
    finally:
        srv.shutdown()
        for s in scheds.values():
            s.close()


# ======================================================================
# ISSUE 5: overload robustness — deadlines, admission control, circuit
# breaker, batch-poison isolation, graceful drain
# ======================================================================

class _RecordingSession:
    """Wraps a real session: records the marker value (column 0) of
    every row that reaches a device step, optionally sleeping first —
    the probe for 'expired requests never consume a device step'."""

    def __init__(self, inner, delay_s=0.0):
        self.inner = inner
        self.delay_s = delay_s
        self.calls = 0
        self.seen = []

    @property
    def input_names(self):
        return self.inner.input_names

    @property
    def input_signature(self):
        return self.inner.input_signature

    def infer(self, inputs):
        self.calls += 1
        self.seen.extend(
            np.asarray(inputs[self.input_names[0]])[:, 0].tolist())
        if self.delay_s:
            time.sleep(self.delay_s)
        return self.inner.infer(inputs)


class _FlakySession:
    """Fails the calls whose 0-based index is in ``fail_calls``."""

    input_names = ["input"]

    def __init__(self, fail_calls):
        self.fail_calls = set(fail_calls)
        self.calls = 0

    def infer(self, inputs):
        i = self.calls
        self.calls += 1
        if i in self.fail_calls:
            raise RuntimeError(f"injected session failure (call {i})")
        return np.zeros((int(inputs["input"].shape[0]), 4), np.float32)


def _wait_idle(sched, timeout_s=5.0):
    end = time.perf_counter() + timeout_s
    while time.perf_counter() < end:
        with sched._stat_lock:
            idle = sched._pending == 0
        if idle:
            return True
        time.sleep(0.005)
    return False


def test_expired_request_never_batched():
    """A request whose deadline passes while queued (or whose client
    timed out) is failed at dequeue time and NEVER reaches a device
    step (ISSUE 5 acceptance)."""
    rec = _RecordingSession(_mlp_session(), delay_s=0.12)
    sched = BatchScheduler(rec, max_batch=1)
    errs = {}

    def fire(v, dl_ms):
        x = np.full((1, 8), v, np.float32)
        try:
            sched.infer({"input": x}, timeout=10, deadline_ms=dl_ms)
        except Exception as e:  # noqa: BLE001
            errs[v] = e

    t1 = threading.Thread(target=fire, args=(1.0, 2000.0))
    t1.start()
    time.sleep(0.04)           # worker is now inside the 120 ms step
    late = [threading.Thread(target=fire, args=(v, 50.0))
            for v in (2.0, 3.0, 4.0)]
    for t in late:
        t.start()
    for t in late:
        t.join()
    t1.join()
    assert 1.0 not in errs, errs.get(1.0)
    for v in (2.0, 3.0, 4.0):
        assert isinstance(errs[v], DeadlineExceededError), errs[v]
    assert _wait_idle(sched), "queue never drained"
    # the three expired requests were skipped at dequeue: their marker
    # rows never appeared in any device batch
    assert all(v not in rec.seen for v in (2.0, 3.0, 4.0)), rec.seen
    assert sched.metrics.expired == 3
    snap = sched.metrics.snapshot(0)
    assert snap["requests"] == snap["completed"] + snap["failed"] \
        + snap["expired"]
    sched.close()


def test_overload_shedding_http():
    """2x-capacity bursts with short deadlines through the HTTP stack:
    expired requests never reach ``session.infer``, admission
    rejections carry ``Retry-After``, and the request accounting
    balances (ISSUE 5 satellite)."""
    rec = _RecordingSession(_mlp_session(), delay_s=0.08)
    repo = ModelRepository()
    repo.register("m", rec)
    handle = serve_http(repo, port=_free_port(), block=False,
                        max_batch=1)
    srv, _, scheds = handle
    base = f"http://127.0.0.1:{handle[0].server_address[1]}"
    codes, headers, lock = [], [], threading.Lock()

    def fire(v, dl_ms="60"):
        body = json.dumps({"inputs": [{
            "name": "input", "shape": [1, 8],
            "data": [float(v)] * 8}]}).encode()
        req = urllib.request.Request(
            f"{base}/v2/models/m/infer", data=body,
            headers={"x-ff-timeout-ms": dl_ms})
        try:
            r = urllib.request.urlopen(req, timeout=10)
            code, hdr = r.status, dict(r.headers)
        except urllib.error.HTTPError as e:
            code, hdr = e.code, dict(e.headers)
        with lock:
            codes.append(code)
            headers.append(hdr)
    try:
        # malformed deadline header -> 400 before any queueing:
        # non-numeric, non-positive, and the non-finite values that
        # pass a bare '> 0' check but would overflow Event.wait
        for bad in ("banana", "0", "-5", "inf", "nan"):
            err_req = urllib.request.Request(
                f"{base}/v2/models/m/infer", data=b"{}",
                headers={"x-ff-timeout-ms": bad})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(err_req, timeout=10)
            assert ei.value.code == 400, bad
        # wave 1: burst of 8 with 60 ms deadlines against an 80 ms step
        wave1 = [threading.Thread(target=fire, args=(float(i),))
                 for i in range(8)]
        for t in wave1:
            t.start()
        for t in wave1:
            t.join()
        sched = scheds["m"]
        assert _wait_idle(sched), "queue never drained after wave 1"
        # wave 2: the EWMA now knows a batch takes ~80 ms, so most of a
        # burst is shed AT ADMISSION with Retry-After
        n_before = len(codes)
        wave2 = [threading.Thread(target=fire, args=(100.0 + i,))
                 for i in range(5)]
        for t in wave2:
            t.start()
        for t in wave2:
            t.join()
        assert _wait_idle(sched), "queue never drained after wave 2"
        wave2_codes = codes[n_before:]
        wave2_headers = headers[n_before:]
        assert any(c == 503 for c in wave2_codes), wave2_codes
        for c, h in zip(wave2_codes, wave2_headers):
            if c == 503:
                assert int(h["Retry-After"]) >= 1, h
        # every request either expired unexecuted, was shed at
        # admission, or actually ran — and the device only ever saw the
        # ran ones (seen rows == completed + failed)
        snap = sched.metrics.snapshot(0)
        offered = len(codes)
        assert snap["requests"] + snap["rejected"] \
            + snap["deadline_rejected"] == offered
        assert snap["requests"] == snap["completed"] + snap["failed"] \
            + snap["expired"]
        assert len(rec.seen) == snap["completed"] + snap["failed"]
        assert snap["expired"] >= 5
        assert snap["deadline_rejected"] >= 1
        assert rec.calls <= 5, (rec.calls, snap)
    finally:
        srv.shutdown()
        for s in scheds.values():
            s.close()


def test_circuit_breaker_cycle():
    """closed -> open after K consecutive failures (fast 503s) ->
    half-open probe after cooldown; a failed probe re-opens, a good one
    closes and restores service."""
    sched = BatchScheduler(_FlakySession({0, 1, 2, 3}), max_batch=1,
                           breaker_threshold=3, breaker_cooldown_s=0.25)
    x = np.zeros((1, 8), np.float32)
    for _ in range(3):
        with pytest.raises(RuntimeError, match="injected session"):
            sched.infer({"input": x})
    assert sched.breaker.state == "open"
    assert sched.metrics.breaker_opens == 1
    t0 = time.perf_counter()
    with pytest.raises(CircuitOpenError) as ei:
        sched.infer({"input": x})
    assert time.perf_counter() - t0 < 0.1, "open circuit must fast-fail"
    assert ei.value.retry_after_s > 0
    # cooldown -> half-open; the probe (call 3) FAILS -> re-open
    time.sleep(0.3)
    with pytest.raises(RuntimeError, match="injected session"):
        sched.infer({"input": x})
    assert sched.breaker.state == "open"
    assert sched.metrics.breaker_opens == 2
    # next cooldown: the probe succeeds -> closed, service restored
    time.sleep(0.3)
    out = sched.infer({"input": x})
    assert out.shape == (1, 4)
    assert sched.breaker.state == "closed"
    assert sched.stats()["circuit"] == "closed"
    sched.close()


def test_breaker_probe_slot_release():
    """A half-open probe that is shed before execution (queue full,
    admission rejection, queued expiry) must give the slot back —
    otherwise the model wedges in half-open, rejecting forever."""
    br = CircuitBreaker(threshold=1, cooldown_s=0.05)
    br.on_failure()
    assert br.state == "open"
    time.sleep(0.08)
    ok, _, probe = br.allow()
    assert ok and probe
    # slot held: a second request must not probe concurrently
    assert br.allow()[0] is False
    # the probe died before reaching the session — release the slot
    br.release_probe()
    ok2, _, probe2 = br.allow()
    assert ok2 and probe2
    br.on_success()
    assert br.state == "closed"


def test_retry_skips_expired_members():
    """When a failed batch's members have already expired (their
    clients are gone), the individual-retry pass must expire them
    instead of burning device steps — and must not feed their
    non-outcomes to the breaker."""
    class SlowFailOnce:
        input_names = ["input"]

        def __init__(self):
            self.calls = 0

        def infer(self, inputs):
            self.calls += 1
            if self.calls == 1:
                time.sleep(0.15)      # longer than the deadlines below
                raise RuntimeError("transient batch failure")
            return np.zeros((int(inputs["input"].shape[0]), 4),
                            np.float32)

    sess = SlowFailOnce()
    sched = BatchScheduler(sess, max_batch=8, max_delay_ms=80.0,
                           breaker_threshold=10)
    errs = []

    def fire():
        try:
            sched.infer({"input": np.zeros((1, 8), np.float32)},
                        deadline_ms=100.0)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=fire) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert _wait_idle(sched)
    assert len(errs) == 2
    assert all(isinstance(e, DeadlineExceededError) for e in errs), errs
    assert sess.calls == 1, "abandoned members must not be retried"
    assert sched.metrics.expired == 2
    assert sched.breaker.state == "closed"
    sched.close()


def test_batch_poison_isolation():
    """A poisoned member fails a whole batch execution; members are
    retried individually once, so the good co-batched requests still
    succeed and only the poison one errors."""
    sess = _mlp_session()

    class PoisonGate(_RecordingSession):
        def infer(self, inputs):
            if np.isnan(np.asarray(inputs["input"])).any():
                self.calls += 1
                raise RuntimeError("poisoned batch")
            return super().infer(inputs)

    gate = PoisonGate(sess)
    sched = BatchScheduler(gate, max_batch=8, max_delay_ms=250.0)
    results, errors = {}, {}

    def fire(key, arr):
        try:
            results[key] = sched.infer({"input": arr}, timeout=15)
        except Exception as e:  # noqa: BLE001
            errors[key] = e

    threads = [
        threading.Thread(target=fire,
                         args=("g1", np.zeros((1, 8), np.float32))),
        threading.Thread(target=fire,
                         args=("bad", np.full((1, 8), np.nan,
                                              np.float32))),
        threading.Thread(target=fire,
                         args=("g2", np.ones((1, 8), np.float32)))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert "g1" in results and "g2" in results, errors
    assert isinstance(errors.get("bad"), RuntimeError), errors
    assert sched.metrics.completed == 2
    assert sched.metrics.failed == 1
    assert sched.breaker.state == "closed"
    sched.close()


def test_admission_validation_rejects_malformed():
    """Schema mismatches are caught at admission (400 for THAT request
    only) instead of crashing a co-batched device step."""
    sched = BatchScheduler(_mlp_session(), max_batch=4)
    x = np.zeros((2, 8), np.float32)
    with pytest.raises(InvalidInputError, match="missing inputs"):
        sched.infer({"wrong": x})
    with pytest.raises(InvalidInputError, match="feature shape"):
        sched.infer({"input": np.zeros((2, 7), np.float32)})
    with pytest.raises(InvalidInputError, match="dtype"):
        sched.infer({"input": np.zeros((2, 8), np.complex64)})
    # int32 -> float32 is a same-kind-compatible widening: accepted
    assert sched.infer({"input": np.zeros((2, 8),
                                          np.int32)}).shape == (2, 4)
    with pytest.raises(InvalidInputError, match="batch dim"):
        sched.infer({"input": np.float32(3.0)})
    # a well-formed request still flows end-to-end afterwards
    out = sched.infer({"input": x})
    assert out.shape == (2, 4)
    assert sched.metrics.completed == 2   # int32 widening + this one
    sched.close()

    class TwoInputs:
        input_names = ["a", "b"]

        def infer(self, inputs):
            return np.zeros((2, 1), np.float32)

    s2 = BatchScheduler(TwoInputs(), max_batch=2)
    with pytest.raises(InvalidInputError, match="ragged"):
        s2.infer({"a": np.zeros((2, 3), np.float32),
                  "b": np.zeros((3, 3), np.float32)})
    s2.close()


def test_session_client_errors_are_valueerrors():
    """`python -O` strips asserts, so client errors in
    InferenceSession.infer must be real ValueErrors (ISSUE 5
    satellite)."""
    sess = _mlp_session()
    with pytest.raises(ValueError, match="missing inputs"):
        sess.infer({})
    sig = sess.input_signature
    assert sig["input"][0][1:] == (8,)
    assert sig["input"][1] == np.dtype(np.float32)


def test_graceful_drain_while_loaded():
    """drain() flips readiness to 503, rejects new work with 503 +
    Retry-After, finishes everything in flight, then closes."""
    rec = _RecordingSession(_mlp_session(), delay_s=0.2)
    repo = ModelRepository()
    repo.register("m", rec)
    handle = serve_http(repo, port=_free_port(), block=False,
                        max_batch=1)
    base = f"http://127.0.0.1:{handle.server.server_address[1]}"
    body = json.dumps({"inputs": [{
        "name": "input", "shape": [1, 8], "data": [0.0] * 8}]}).encode()
    codes = []

    def fire():
        try:
            r = urllib.request.urlopen(urllib.request.Request(
                f"{base}/v2/models/m/infer", data=body), timeout=15)
            codes.append(r.status)
        except urllib.error.HTTPError as e:
            codes.append(e.code)

    inflight = [threading.Thread(target=fire) for _ in range(3)]
    for t in inflight:
        t.start()
    time.sleep(0.05)           # ensure they are queued / executing
    drained = []
    dt = threading.Thread(
        target=lambda: drained.append(handle.drain(deadline_s=15)))
    dt.start()
    # readiness flips to 503 while the in-flight work finishes
    saw_unready = False
    end = time.perf_counter() + 3.0
    while time.perf_counter() < end and not saw_unready:
        try:
            urllib.request.urlopen(f"{base}/v2/health/ready", timeout=5)
        except urllib.error.HTTPError as e:
            if e.code == 503:
                doc = json.loads(e.read())
                assert doc["ready"] is False
                saw_unready = True
        except urllib.error.URLError:
            break              # drain finished and closed the listener
        time.sleep(0.005)
    assert saw_unready, "readiness never flipped during drain"
    # new work is rejected with a retry hint while draining
    try:
        r = urllib.request.urlopen(urllib.request.Request(
            f"{base}/v2/models/m/infer", data=body), timeout=5)
        assert False, f"draining server accepted work: {r.status}"
    except urllib.error.HTTPError as e:
        assert e.code == 503
        assert int(e.headers["Retry-After"]) >= 1
    except urllib.error.URLError:
        pass                   # listener already closed — also a reject
    for t in inflight:
        t.join()
    dt.join()
    # every in-flight request completed before the close
    assert codes == [200, 200, 200]
    assert drained == [True]
    # the listener is really gone
    with pytest.raises(urllib.error.URLError):
        urllib.request.urlopen(f"{base}/v2/health/ready", timeout=2)


def test_infer_racing_close_fails_promptly():
    """An ``infer`` that passes the draining check but enqueues AFTER
    close()'s queue sweep must fail promptly (scheduler-closed error),
    not strand its client until the full timeout on a queue no worker
    reads."""

    class Echo:
        input_names = ["x"]

        def infer(self, inputs):
            return np.zeros((int(inputs["x"].shape[0]), 1), np.float32)

    sched = BatchScheduler(Echo(), max_batch=4)
    orig_validate = sched._validate

    def validate_then_close(inputs):
        out = orig_validate(inputs)
        sched.close()      # lands between the draining check and put
        return out

    sched._validate = validate_then_close
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="closed"):
        sched.infer({"x": np.zeros((1, 1), np.float32)}, timeout=10.0)
    assert time.perf_counter() - t0 < 5.0, \
        "request stranded until timeout after racing close()"


@pytest.mark.slow
def test_concurrent_load_p50_p99_artifact(tmp_path):
    """Sustained concurrent load through the HTTP stack; writes the
    p50/p99 record (CPU host latencies, not speeds) under ``tmp_path``."""
    import time
    repo = ModelRepository()
    repo.register("mlp", _mlp_session(buckets=(1, 4, 16, 64)),
                  instances=2)
    port = _free_port()
    srv, thread, scheds = serve_http(repo, port=port, block=False,
                                     max_batch=64, max_queue=512)
    n_clients, per_client = 16, 25
    lat = []
    lat_lock = threading.Lock()
    errs = []

    def client(ci):
        rng = np.random.default_rng(ci)
        for _ in range(per_client):
            x = rng.normal(size=(2, 8)).astype(np.float32)
            body = json.dumps({"inputs": [{
                "name": "input", "shape": [2, 8],
                "data": x.ravel().tolist()}]}).encode()
            t0 = time.perf_counter()
            try:
                r = urllib.request.urlopen(urllib.request.Request(
                    f"http://127.0.0.1:{port}/v2/models/mlp/infer",
                    data=body), timeout=30)
                assert r.status == 200
                with lat_lock:
                    lat.append(time.perf_counter() - t0)
            except Exception as e:  # noqa: BLE001
                errs.append(repr(e))

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    try:
        assert not errs, errs[:3]
        assert len(lat) == n_clients * per_client
        lat.sort()
        p = lambda q: lat[min(len(lat) - 1, int(q * len(lat)))]  # noqa: E731
        m = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/v2/metrics").read())["models"]["mlp"]
        rec = {
            "workload": "mlp infer, 16 clients x 25 reqs x 2 rows",
            "requests": len(lat),
            "wall_s": round(wall, 3),
            "throughput_rps": round(len(lat) / wall, 1),
            "p50_ms": round(p(0.50) * 1e3, 2),
            "p99_ms": round(p(0.99) * 1e3, 2),
            "server_metrics": m,
        }
        with open(tmp_path / "serving_load_http.json", "w") as f:
            json.dump(rec, f, indent=1)
        # sanity: batching must actually aggregate under load
        assert m["mean_batch_rows"] > 2.0, m
    finally:
        srv.shutdown()
        for s in scheds.values():
            s.close()
