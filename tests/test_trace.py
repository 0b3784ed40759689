"""Request-scoped tracing, iteration ledger, flight recorder (ISSUE 14).

The acceptance proofs:

* **Critical-path partition**: the phase decomposition sums to the root
  span's duration exactly on a synthetic tree and end-to-end through the
  HTTP server, where the root span lies between the scheduler's own
  submit-to-done time and the client's latency.
* **Failover span tree**: a 3-replica fleet with the serving replica
  killed mid-flight yields ONE trace holding both dispatch spans (tagged
  primary / failover reason); the final span's replica matches the
  response's ``served_by``; the tree is retrievable via ``GET
  /v1/trace/<id>``.
* **Server-minted request ids**: a client that omits ``request_id`` gets
  a deterministic ``srv-`` id echoed in success AND rejection bodies.
* **MFU attribution**: the engine's iteration ledger accounts for >=95%
  of engine wall time, split device / host / idle.
* **Flight recorder**: a watchdog trip dumps a parseable blackbox JSON
  with the trip in the event ring.
"""

import json
import pathlib
import re
import threading
import time
import urllib.error
import urllib.request

import pytest

from consensus_tpu.backends import FakeBackend, GenerationRequest
from consensus_tpu.backends.batching import BatchingBackend
from consensus_tpu.backends.engine import DecodeEngine
from consensus_tpu.backends.faults import (
    FaultInjectingBackend,
    FaultPlan,
    FaultSpec,
)
from consensus_tpu.obs.metrics import Registry
from consensus_tpu.obs.trace import (
    HOST_SPANS,
    MAX_SPANS_PER_TRACE,
    FlightRecorder,
    IterationLedger,
    RollingWindow,
    TraceContext,
    TraceStore,
    get_flight_recorder,
    get_trace_store,
    span,
    trace_current,
    use_trace,
)
from consensus_tpu.serve import (
    ConsensusServer,
    FleetRouter,
    Replica,
    SchedulerRejected,
    create_server,
    parse_request,
)

ISSUE = "Should we invest in public transport?"
OPINIONS = {
    "Agent 1": "Yes, buses are vital.",
    "Agent 2": "Only with congestion pricing.",
}


def _payload(seed=7, **overrides):
    payload = {
        "issue": ISSUE,
        "agent_opinions": dict(OPINIONS),
        "method": "best_of_n",
        "params": {"n": 2, "max_tokens": 16},
        "seed": seed,
        "request_id": f"req-{seed}",
    }
    payload.update(overrides)
    return payload


def _post(base_url, payload, timeout=30.0):
    request = urllib.request.Request(
        base_url + "/v1/consensus",
        data=json.dumps(payload).encode("utf-8"),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    try:
        with urllib.request.urlopen(request, timeout=timeout) as response:
            return response.status, json.loads(response.read().decode())
    except urllib.error.HTTPError as exc:
        return exc.code, json.loads(exc.read().decode())


def _get(base_url, path, timeout=10.0):
    try:
        with urllib.request.urlopen(
            base_url + path, timeout=timeout
        ) as response:
            return response.status, json.loads(response.read().decode())
    except urllib.error.HTTPError as exc:
        return exc.code, json.loads(exc.read().decode())


def _wait_for(predicate, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.01)
    return predicate()


# ---------------------------------------------------------------------------
# TraceContext unit behaviour
# ---------------------------------------------------------------------------


class TestTraceContext:
    def test_span_tree_export(self):
        trace = TraceContext("t-1")
        root = trace.begin("http_request", method="best_of_n")
        child = trace.begin("queue_wait", parent=root, replica="r0")
        trace.event(child, "probe", detail=1)
        trace.end(child)
        trace.end(root, status=200)
        exported = trace.to_dict()
        assert exported["trace_id"] == "t-1"
        by_name = {s["name"]: s for s in exported["spans"]}
        assert by_name["queue_wait"]["parent"] == root
        assert by_name["http_request"]["attrs"]["status"] == 200
        assert not by_name["http_request"]["in_flight"]
        assert by_name["queue_wait"]["events"][0]["name"] == "probe"

    def test_end_is_idempotent_first_wins(self):
        trace = TraceContext("t-2")
        span = trace.begin("handler")
        trace.end(span, outcome="ok")
        first = trace.to_dict()["spans"][0]["duration_s"]
        time.sleep(0.02)
        trace.end(span, outcome="late")  # attrs update, duration does not
        again = trace.to_dict()["spans"][0]
        assert again["duration_s"] == first
        assert again["attrs"]["outcome"] == "late"

    def test_span_cap_returns_noop_sentinel(self):
        trace = TraceContext("t-3")
        ids = [trace.begin(f"s{i}") for i in range(MAX_SPANS_PER_TRACE + 5)]
        assert ids[-1] == 0
        assert trace.dropped_spans == 5
        trace.end(0, outcome="ignored")  # must not raise
        trace.event(0, "ignored")
        assert len(trace.to_dict()["spans"]) == MAX_SPANS_PER_TRACE

    def test_critical_path_partitions_root_exactly(self):
        trace = TraceContext("t-4")
        root = trace.begin("http_request")
        queue = trace.begin("queue_wait", parent=root)
        time.sleep(0.01)
        trace.end(queue)
        row = trace.begin("engine_row", parent=root)
        time.sleep(0.005)
        trace.event(row, "slot_admitted")
        time.sleep(0.005)
        trace.event(row, "prefill_complete")
        time.sleep(0.01)
        trace.end(row, outcome="retired")
        score = trace.begin("engine_score", parent=root)
        time.sleep(0.005)
        # the call waits in the engine, then one dispatch takes it up
        run = trace.begin("engine.dispatch", parent=score)
        time.sleep(0.005)
        trace.end(run)
        trace.end(score)
        trace.end(root)
        path = trace.critical_path()
        phases = path["phases"]
        assert abs(sum(phases.values()) - path["total_s"]) < 1e-4
        for name in ("queue_wait", "admission_wait", "prefill", "decode",
                     "score", "engine_wait"):
            assert phases[name] > 0.0, name
        assert phases["failover_overhead"] == 0.0

    def test_engine_wait_is_the_call_less_its_dispatches(self):
        trace = TraceContext("t-5")
        root = trace.begin("http_request")
        call = trace.begin("engine_score_matrix", parent=root)
        time.sleep(0.02)
        run = trace.begin("engine.dispatch", parent=call)
        time.sleep(0.01)
        trace.end(run)
        trace.end(call)
        row = trace.begin("engine_row", parent=root)
        trace.event(row, "slot_admitted")
        trace.event(row, "prefill_complete")
        time.sleep(0.02)  # prefilled, waiting for its cohort's dispatch
        trace.event(row, "decode_dispatch")
        time.sleep(0.01)
        trace.end(row)
        trace.end(root)
        spans = {s["name"]: s for s in trace.to_dict()["spans"]}
        phases = trace.critical_path()["phases"]
        assert phases["score"] == pytest.approx(
            spans["engine.dispatch"]["duration_s"], abs=1e-4)
        # both waits, and neither is booked as device work any more
        assert phases["engine_wait"] >= 0.038
        assert phases["decode"] < 0.02
        # a call that no dispatch took up waited, all of it
        lost = TraceContext("t-6")
        root = lost.begin("http_request")
        call = lost.begin("engine_next_token", parent=root)
        time.sleep(0.005)
        lost.end(call)
        lost.end(root)
        phases = lost.critical_path()["phases"]
        assert phases["score"] == 0.0 and phases["engine_wait"] > 0.0


class TestUseTrace:
    def test_thread_local_carrier_nests_and_restores(self):
        trace = TraceContext("t-5")
        assert trace_current() is None
        with use_trace(trace, 1):
            assert trace_current() == (trace, 1)
            with use_trace(trace, 2):
                assert trace_current() == (trace, 2)
            assert trace_current() == (trace, 1)
        assert trace_current() is None

    def test_none_trace_is_passthrough(self):
        with use_trace(None, 7):
            assert trace_current() is None


class TestSpan:
    def test_child_of_the_active_trace_and_parent_of_what_runs_inside(self):
        trace = TraceContext("s-1")
        root = trace.begin("http_request")
        with use_trace(trace, root):
            with span("serve.method", method="best_of_n"):
                inner_parent = trace_current()[1]
                with span("method.render"):
                    pass
            assert trace_current() == (trace, root)
        spans = {s["name"]: s for s in trace.to_dict()["spans"]}
        assert spans["serve.method"]["parent"] == root
        assert spans["serve.method"]["id"] == inner_parent
        assert spans["method.render"]["parent"] == inner_parent
        assert spans["serve.method"]["attrs"] == {"method": "best_of_n"}
        assert not spans["serve.method"]["in_flight"]
        assert not spans["method.render"]["in_flight"]

    def test_without_a_trace_it_only_annotates(self):
        assert trace_current() is None
        with span("engine.iteration"):
            assert trace_current() is None
        assert trace_current() is None

    def test_merged_work_writes_the_same_interval_into_each_trace(self):
        first, second = TraceContext("s-2"), TraceContext("s-3")
        a = first.begin("engine_embed")
        b = second.begin("engine_embed")
        with span("engine.dispatch", traces=[(first, a), (second, b)],
                  kind="embed", rows=6):
            with span("backend.launch", program="_embed_forward"):
                time.sleep(0.002)
        assert trace_current() is None
        for trace, parent in ((first, a), (second, b)):
            spans = {s["name"]: s for s in trace.to_dict()["spans"]}
            assert spans["engine.dispatch"]["parent"] == parent
            assert spans["engine.dispatch"]["attrs"] == {
                "kind": "embed", "rows": 6}
            assert (spans["backend.launch"]["parent"]
                    == spans["engine.dispatch"]["id"])
            assert spans["backend.launch"]["duration_s"] >= 0.002

    def test_a_span_the_cap_dropped_leaves_its_parent_in_charge(self):
        trace = TraceContext("s-4")
        root = trace.begin("http_request")
        for _ in range(MAX_SPANS_PER_TRACE - 1):
            trace.begin("filler", parent=root)
        with use_trace(trace, root), span("serve.method"):
            assert trace_current() == (trace, root)
        assert trace.dropped_spans == 1

    def test_what_the_body_learns_goes_onto_the_span_when_it_ends(self):
        trace = TraceContext("s-6")
        root = trace.begin("http_request")
        with use_trace(trace, root), span("backend.tokenize", rows=3) as late:
            assert late == {}
            late["encoded"] = 2
        with span("backend.tokenize", rows=1) as late:  # no trace: no one reads
            late["encoded"] = 1
        assert trace.to_dict()["spans"][1]["attrs"] == {"rows": 3, "encoded": 2}

    def test_ends_its_span_when_the_body_raises(self):
        trace = TraceContext("s-5")
        root = trace.begin("http_request")
        with pytest.raises(ValueError):
            with use_trace(trace, root), span("serve.evaluate"):
                raise ValueError("boom")
        assert trace_current() is None
        assert not trace.to_dict()["spans"][1]["in_flight"]


class TestTraceStore:
    def test_lru_bound_and_recency(self):
        store = TraceStore(capacity=3)
        for i in range(5):
            store.put(TraceContext(f"t{i}"))
        assert len(store) == 3
        assert store.get("t0") is None and store.get("t1") is None
        assert store.get("t2") is not None
        # touching t2 makes t3 the eviction victim
        store.put(TraceContext("t5"))
        assert store.get("t3") is None
        assert store.get("t2") is not None


# ---------------------------------------------------------------------------
# IterationLedger / RollingWindow / FlightRecorder units
# ---------------------------------------------------------------------------


class TestIterationLedger:
    def test_residual_is_attributed_and_coverage_full(self):
        ledger = IterationLedger()
        ledger.record(
            start_s=10.0, end_s=10.1, idle_s=0.0, device_s=0.06,
            host={"sweep": 0.01, "admit": 0.005, "prefill": 0.0,
                  "cohort": 0.005, "merge": 0.01},
            tokens=32, cohort=4, queue_depth=2, pages_in_use=16,
        )
        ledger.record(
            start_s=10.15, end_s=10.25, idle_s=0.05, device_s=0.08,
            host={"sweep": 0.005, "admit": 0.0, "prefill": 0.0,
                  "cohort": 0.0, "merge": 0.005},
            tokens=16, cohort=2, queue_depth=0, pages_in_use=8,
        )
        report = ledger.mfu_attribution()
        assert report["iterations"] == 2
        assert report["tokens"] == 48
        assert report["coverage"] >= 0.95
        # residual host time (0.1 - 0.06 - 0.03 = 0.01) lands in "other"
        assert report["host_breakdown"]["other"] == pytest.approx(
            0.02, abs=1e-6)
        fractions = (report["device_fraction"] + report["host_fraction"]
                     + report["idle_fraction"])
        assert fractions == pytest.approx(1.0, abs=0.02)
        assert ledger.recent(1)[0]["iteration"] == 2


class TestRollingWindow:
    def test_buckets_availability_and_p95(self):
        window = RollingWindow(bucket_s=1.0)
        for t in (0.1, 0.5, 0.9):
            window.observe(t, ok=True, latency_s=0.010)
        window.observe(1.2, ok=False)
        window.observe(1.8, ok=True, latency_s=0.100)
        curve = window.curve()
        assert [row["t_s"] for row in curve] == [0.0, 1.0]
        assert curve[0]["offered"] == 3 and curve[0]["availability"] == 1.0
        assert curve[1]["availability"] == 0.5
        assert curve[1]["p95_ms"] == pytest.approx(100.0)
        assert curve[0]["rps"] == pytest.approx(3.0)


class TestFlightRecorderUnit:
    def test_dump_without_path_is_noop(self):
        recorder = FlightRecorder()
        recorder.record_event("replica_lost", replica="r0")
        assert recorder.dump("test") is None
        assert recorder.dumps == 0

    def test_dump_writes_parseable_blackbox(self, tmp_path):
        path = str(tmp_path / "blackbox.json")
        recorder = FlightRecorder(path=path)
        recorder.record_event("breaker_open", breaker="fake")
        recorder.record_iteration({"iteration": 1, "total_s": 0.01})
        assert recorder.dump("unit_test") == path
        with open(path, encoding="utf-8") as handle:
            blackbox = json.load(handle)
        assert blackbox["schema"] == FlightRecorder.SCHEMA
        assert blackbox["reason"] == "unit_test"
        assert blackbox["events"][0]["kind"] == "breaker_open"
        assert blackbox["iterations"][0]["iteration"] == 1
        assert recorder.dumps == 1

    def test_rings_are_bounded(self):
        recorder = FlightRecorder(max_events=4, max_iterations=2)
        for i in range(10):
            recorder.record_event("scale_up", replica=f"r{i}")
            recorder.record_iteration({"iteration": i})
        snapshot = recorder.snapshot()
        assert len(snapshot["events"]) == 4
        assert len(snapshot["iterations"]) == 2
        assert snapshot["events"][-1]["replica"] == "r9"


# ---------------------------------------------------------------------------
# End-to-end: HTTP -> scheduler -> engine span tree
# ---------------------------------------------------------------------------


def _scheduled_seconds(registry):
    """Seconds from submit to done of every request the scheduler has
    finished (``serve_request_latency_seconds``)."""
    family = registry.snapshot()["families"].get(
        "serve_request_latency_seconds", {})
    return sum(series["sum"] for series in family.get("series", ()))


class TestEndToEndTrace:
    def test_trace_block_endpoint_and_critical_path_sum(self):
        registry = Registry()
        server = create_server(
            backend=FakeBackend(), port=0, registry=registry).start()
        try:
            # warm the stack (connection setup, lazy imports, first-flush
            # compile) so the measured request's latency is the span's
            _post(server.base_url, _payload(seed=30))
            scheduled_before = _scheduled_seconds(registry)
            start = time.perf_counter()
            status, body = _post(server.base_url, _payload(
                seed=31, request_id="trace-e2e-1", trace=True))
            latency_s = time.perf_counter() - start
            assert status == 200
            trace_block = body["trace"]
            assert trace_block["trace_id"] == "trace-e2e-1"
            names = {s["name"] for s in trace_block["spans"]}
            assert {"http_request", "queue_wait", "handler"} <= names
            assert "engine_row" in names  # slot lifecycle reached
            path = trace_block["critical_path"]
            total = path["total_s"]
            assert abs(sum(path["phases"].values()) - total) < 1e-4
            # the root span's wall is the request's: it holds the
            # scheduler's own submit-to-done time for the request and lies
            # inside the client's latency (both clocks monotonic; no bound
            # leans on how long the HTTP hop takes on a loaded host)
            scheduled = _scheduled_seconds(registry) - scheduled_before
            assert 0.0 < scheduled <= total <= latency_s

            status, exported = _get(server.base_url, "/v1/trace/trace-e2e-1")
            assert status == 200
            assert exported["trace_id"] == "trace-e2e-1"
            assert {s["name"] for s in exported["spans"]} >= {
                "http_request", "handler"}
            assert "critical_path" in exported

            status, error = _get(server.base_url, "/v1/trace/never-existed")
            assert status == 404
            assert error["error"]["type"] == "trace_not_found"
        finally:
            server.stop(drain=False, timeout=5.0)

    def test_trace_off_responses_have_no_trace_block(self):
        server = create_server(
            backend=FakeBackend(), port=0, registry=Registry()).start()
        try:
            status, body = _post(server.base_url, _payload(seed=32))
            assert status == 200
            assert "trace" not in body
        finally:
            server.stop(drain=False, timeout=5.0)

    def test_server_mints_request_id_and_echoes_in_success(self):
        server = create_server(
            backend=FakeBackend(), port=0, registry=Registry()).start()
        try:
            payload = _payload(seed=33)
            del payload["request_id"]
            status, body = _post(server.base_url, payload)
            assert status == 200
            assert body["request_id"].startswith("srv-")
            # deterministic digest: same payload -> same digest suffix
            status2, body2 = _post(server.base_url, payload)
            assert body["request_id"].split("-")[2] == \
                body2["request_id"].split("-")[2]
            assert body["request_id"] != body2["request_id"]  # seq differs
        finally:
            server.stop(drain=False, timeout=5.0)

    def test_minted_request_id_echoed_in_rejection_body(self):
        class SlowGen:
            name = "slow"

            def __init__(self):
                self.inner = FakeBackend()

            def __getattr__(self, attr):
                return getattr(self.inner, attr)

            def generate(self, requests):
                time.sleep(0.2)
                return self.inner.generate(requests)

        server = create_server(
            backend=SlowGen(), port=0, registry=Registry(),
            max_inflight=1, max_queue_depth=1).start()
        try:
            results = []

            def fire(seed):
                payload = _payload(seed=seed)
                del payload["request_id"]
                results.append(_post(server.base_url, payload))

            threads = [threading.Thread(target=fire, args=(40 + i,),
                                        daemon=True)
                       for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30.0)
            rejected = [b for s, b in results if s == 429]
            assert rejected, "capacity 1+1 under 8 concurrent posts must 429"
            for body in rejected:
                assert body["error"]["request_id"].startswith("srv-")
        finally:
            server.stop(drain=False, timeout=5.0)

    def _rejection_response(self, server, exc):
        """POST (no client request_id) with submit forced to reject."""
        scheduler = server.scheduler

        def rejecting_submit(request):
            raise exc

        scheduler.submit = rejecting_submit
        payload = _payload(seed=60)
        del payload["request_id"]
        request = urllib.request.Request(
            server.base_url + "/v1/consensus",
            data=json.dumps(payload).encode("utf-8"),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        try:
            with urllib.request.urlopen(request, timeout=10.0):
                raise AssertionError("rejection expected")
        except urllib.error.HTTPError as err:
            return err.code, json.loads(err.read().decode()), err.headers

    def test_breaker_open_503_carries_request_id(self):
        server = create_server(
            backend=FakeBackend(), port=0, registry=Registry()).start()
        try:
            status, body, headers = self._rejection_response(
                server,
                SchedulerRejected("breaker_open",
                                  "circuit breaker open to backend",
                                  retry_after_s=3.0),
            )
            assert status == 503
            error = body["error"]
            assert error["type"] == "rejected"
            assert error["reason"] == "breaker_open"
            assert error["request_id"].startswith("srv-")
            assert headers["Retry-After"] is not None
        finally:
            server.stop(drain=False, timeout=5.0)

    def test_kv_oom_413_carries_request_id(self):
        server = create_server(
            backend=FakeBackend(), port=0, registry=Registry()).start()
        try:
            status, body, headers = self._rejection_response(
                server,
                SchedulerRejected("kv_oom",
                                  "request KV footprint exceeds pool"),
            )
            assert status == 413
            error = body["error"]
            assert error["type"] == "rejected"
            assert error["reason"] == "kv_oom"
            assert error["request_id"].startswith("srv-")
            # Oversized requests don't shrink on retry: no Retry-After.
            assert headers["Retry-After"] is None
        finally:
            server.stop(drain=False, timeout=5.0)


# ---------------------------------------------------------------------------
# Failover span tree (mid-flight replica kill)
# ---------------------------------------------------------------------------


class _SlowBackend:
    """FakeBackend with a per-dispatch delay so kills land mid-flight."""

    name = "slow-fake"

    def __init__(self, delay_s=0.05):
        self.inner = FakeBackend()
        self.delay_s = delay_s

    def __getattr__(self, attr):
        return getattr(self.inner, attr)

    def generate(self, requests):
        time.sleep(self.delay_s)
        return self.inner.generate(requests)

    def score(self, requests):
        time.sleep(self.delay_s)
        return self.inner.score(requests)


@pytest.mark.chaos
class TestFailoverTrace:
    def test_span_tree_holds_both_dispatches_across_kill(self):
        registry = Registry()
        replicas = [
            Replica(f"r{i}", _SlowBackend(), registry=registry,
                    scheduler_options={"max_inflight": 2,
                                       "max_queue_depth": 6,
                                       "default_timeout_s": 30.0})
            for i in range(3)
        ]
        router = FleetRouter(replicas, registry=registry)
        server = ConsensusServer(router, port=0, registry=registry).start()
        try:
            payload = _payload(seed=51, request_id="trace-failover-1",
                               trace=True)
            doomed = router.route_for(parse_request(payload))
            outbox = {}

            def fire():
                outbox["result"] = _post(server.base_url, payload)

            thread = threading.Thread(target=fire, daemon=True)
            thread.start()
            assert _wait_for(
                lambda: doomed.scheduler.stats()["inflight"] > 0)
            router.kill_replica(doomed.name)
            thread.join(timeout=30.0)

            status, body = outbox["result"]
            assert status == 200
            assert body["served_by"] and body["served_by"] != doomed.name

            trace = get_trace_store().get("trace-failover-1")
            assert trace is not None
            spans = trace.to_dict()["spans"]
            dispatches = [s for s in spans if s["name"] == "dispatch"]
            assert len(dispatches) >= 2
            reasons = [s["attrs"]["reason"] for s in dispatches]
            assert reasons[0] == "primary"
            assert any(r != "primary" for r in reasons[1:])
            assert dispatches[0]["attrs"]["replica"] == doomed.name
            finals = [s for s in dispatches if s["attrs"].get("final")]
            assert len(finals) == 1
            assert finals[0]["attrs"]["replica"] == body["served_by"]
            # failover time shows up as an explicit critical-path phase
            path = trace.critical_path()
            assert path["phases"]["failover_overhead"] > 0.0
            assert abs(sum(path["phases"].values())
                       - path["total_s"]) < 1e-4

            # and the whole tree is retrievable over HTTP
            status, exported = _get(
                server.base_url, "/v1/trace/trace-failover-1")
            assert status == 200
            assert len([s for s in exported["spans"]
                        if s["name"] == "dispatch"]) >= 2
        finally:
            server.stop(drain=False, timeout=5.0)


# ---------------------------------------------------------------------------
# One set of names: span() call sites, named_scope call sites, the tuples
# ---------------------------------------------------------------------------

_PACKAGE = pathlib.Path(__file__).resolve().parents[1] / "consensus_tpu"
# obs.trace.span by its bare name; ``tracer.span(`` is the experiment
# engine's accumulator (obs/spans.py), which names whole runs.
_SPAN_CALL = re.compile(r"""(?<![.\w])span\(\s*["']([^"']+)["']""")
_SCOPE_CALL = re.compile(r"""named_scope\(\s*["']([^"']+)["']""")


def _names_used(pattern):
    used = {}
    for path in sorted(_PACKAGE.rglob("*.py")):
        for name in pattern.findall(path.read_text()):
            used.setdefault(name, []).append(path.name)
    return used


class TestNamesAreListed:
    def test_every_span_call_site_is_in_host_spans(self):
        used = _names_used(_SPAN_CALL)
        assert not set(used) - set(HOST_SPANS), {
            n: used[n] for n in set(used) - set(HOST_SPANS)}
        # and the tuple lists nothing that no call site writes
        assert not set(HOST_SPANS) - set(used)
        assert len(set(HOST_SPANS)) == len(HOST_SPANS)
        for name in HOST_SPANS:
            layer, _, what = name.partition(".")
            assert layer in ("serve", "method", "engine", "backend"), name
            assert what and name == name.lower(), name

    def test_every_named_scope_is_in_model_scopes(self):
        from consensus_tpu.models import MODEL_PHASES, MODEL_SCOPES

        used = _names_used(_SCOPE_CALL)
        assert not set(used) - set(MODEL_SCOPES), {
            n: used[n] for n in set(used) - set(MODEL_SCOPES)}
        assert not set(MODEL_SCOPES) - set(used)
        assert set(MODEL_PHASES) < set(MODEL_SCOPES)
        assert not set(MODEL_SCOPES) & set(HOST_SPANS)

    def test_the_recurrent_mixers_names_are_listed(self):
        """The scopes a block with a Mamba-2 mixer adds and the copy of its
        state, each written by a ``named_scope`` call site; and the three
        series of ``obs/backends.py`` that count what they cost."""
        from consensus_tpu.models import MODEL_SCOPES
        from consensus_tpu.obs.backends import BackendInstruments
        from consensus_tpu.obs.metrics import Registry

        mixer = {"ssm_in", "ssm_conv", "ssm_scan", "ssm_out", "state_fork"}
        assert mixer <= set(MODEL_SCOPES)
        assert mixer <= set(_names_used(_SCOPE_CALL))
        registry = Registry()
        instruments = BackendInstruments("tpu", registry=registry)
        instruments.record_state_fork("generate", 32, 32 * 100)
        instruments.record_state_fork("score_matrix", 160, 168 * 100)
        instruments.record_prefix_run_declined("lookup")
        families = registry.snapshot()["families"]
        forks = {s["labels"]["kind"]: s["value"]
                 for s in families["backend_state_fork_rows_total"]["series"]}
        assert forks == {"generate": 32, "score_matrix": 160}
        assert families["backend_recurrent_state_bytes"]["series"][0][
            "value"] == 168 * 100
        assert families["backend_prefix_runs_declined_total"]["series"][0][
            "labels"]["op"] == "lookup"

    def test_the_compile_records_names_are_listed(self):
        """``backend.compile`` (the compile record's span) is a name of
        ``HOST_SPANS`` written at one call site, and the three metric files
        that read the record are ``BENCHMARK.json``'s, with a reader that
        knows their part."""
        import importlib.util

        assert _names_used(_SPAN_CALL)["backend.compile"] == ["backends.py"]
        bench = _PACKAGE.parent / "benchmark"
        listed = {m["name"]: m for m in json.loads(
            (_PACKAGE.parent / "BENCHMARK.json").read_text())["per_layer"]}
        spec = importlib.util.spec_from_file_location(
            "compile_record_reader", bench / "readers" / "compile_record.py")
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        for name, moves in (("setup_compile_s", "setup_s"),
                            ("setup_programs", "setup_s"),
                            ("window_compile_s", "statements_per_s")):
            metric = json.loads((bench / "metrics" / f"{name}.json").read_text())
            assert metric["reader"] == "compile_record"
            assert metric["part"] in reader.PARTS
            assert (metric["layer"], metric["moves"]) == ("programs", moves)
            assert {k: listed[name][k] for k in listed[name]} == {
                k: metric[k] for k in listed[name]}

    def test_the_benchmark_reads_no_name_the_program_does_not_write(self):
        """Every ``<layer>.<what>`` a metric file or a reader of the
        benchmark mentions is a name of ``HOST_SPANS``, and every scope a
        metric file lists is a name of ``MODEL_SCOPES``."""
        from consensus_tpu.models import MODEL_SCOPES

        bench = _PACKAGE.parent / "benchmark"
        dotted = re.compile(r"\b(?:serve|method|engine|backend)\.[a-z_0-9]+\b")
        # (a counter's file may name a key of /healthz, engine.<key>)
        files = [path for path in sorted((bench / "metrics").glob("*.json"))
                 if json.loads(path.read_text())["source"] != "program_counter"]
        files += sorted((bench / "readers").glob("*.py"))
        files.append(bench / "lib" / "xplane_spans.py")
        mentioned = {}
        for path in files:
            for name in dotted.findall(path.read_text()):
                if not name.endswith((".py", ".json")):
                    mentioned.setdefault(name, path.name)
        assert mentioned, "the benchmark's readers mention no span"
        assert not set(mentioned) - set(HOST_SPANS), {
            n: mentioned[n] for n in set(mentioned) - set(HOST_SPANS)}
        scopes = {}
        for path in sorted((bench / "metrics").glob("*.json")):
            for name in json.loads(path.read_text()).get("scopes", ()):
                scopes[name] = path.name
        assert scopes and not set(scopes) - set(MODEL_SCOPES), scopes


# ---------------------------------------------------------------------------
# The span tree of one best_of_n request, fake backend and tiny-gemma2
# ---------------------------------------------------------------------------


def _descends_from(span_row, ancestor_id, by_id):
    while span_row["parent"]:
        if span_row["parent"] == ancestor_id:
            return True
        span_row = by_id[span_row["parent"]]
    return False


class TestRequestSpanTree:
    @pytest.mark.parametrize("backend", ["fake", "tpu"])
    def test_layers_nest_under_the_handler_and_close(self, backend):
        options = {"model": "tiny-gemma2"} if backend == "tpu" else None
        server = create_server(
            backend=backend, backend_options=options, port=0,
            registry=Registry()).start()
        try:
            request_id = f"tree-{backend}"
            status, body = _post(server.base_url, _payload(
                seed=41, request_id=request_id, evaluate=backend == "fake",
                params={"n": 2, "max_tokens": 4}), timeout=300.0)
            assert status == 200, body
        finally:
            server.stop(drain=False, timeout=5.0)
        trace = get_trace_store().get(request_id)
        spans = trace.to_dict()["spans"]
        assert len(spans) < 200 and trace.dropped_spans == 0
        assert not [s["name"] for s in spans if s["in_flight"]]
        by_id = {s["id"]: s for s in spans}
        names = {s["name"] for s in spans}
        handler = next(s for s in spans if s["name"] == "handler")
        expected = {"serve.method", "method.render", "method.generate",
                    "method.score", "method.select", "engine.enqueue",
                    "engine.dispatch"}
        if backend == "fake":
            expected |= {"serve.evaluate"}
        else:
            expected |= {"backend.tokenize", "backend.layout", "backend.h2d",
                         "backend.launch", "backend.d2h",
                         "backend.detokenize"}
        assert expected <= names, expected - names
        for row in spans:
            if row["name"] in expected:
                assert _descends_from(row, handler["id"], by_id), row["name"]
        root = next(s for s in spans if s["name"] == "http_request")
        for name in ("serve.parse", "serve.respond"):
            row = next(s for s in spans if s["name"] == name)
            assert row["parent"] == root["id"]
        # the engine's spans of this thread-less work are inside the call
        for row in spans:
            if row["name"] == "engine.dispatch":
                assert by_id[row["parent"]]["name"].startswith("engine_")
                assert set(row["attrs"]) == {"kind", "rows"}
        for row in spans:
            if row["name"] in HOST_SPANS:
                for value in row["attrs"].values():  # never a prompt
                    assert isinstance(value, (int, float)) or len(value) < 64
        # what a tokenising span learnt as it ran: texts the tokenizer ran on
        tokenised = [s["attrs"] for s in spans if s["name"] == "backend.tokenize"]
        assert all(0 <= a["encoded"] <= a["rows"] for a in tokenised)
        if backend == "tpu":  # the two rows of one prompt: one text encoded
            assert {"rows": 2, "encoded": 1} in tokenised
        path = trace.critical_path()
        assert abs(sum(path["phases"].values()) - path["total_s"]) < 1e-4
        assert path["phases"]["engine_wait"] > 0.0
        assert path["phases"]["score"] > 0.0
        assert path["total_s"] == pytest.approx(
            root["duration_s"], abs=1e-4)


# ---------------------------------------------------------------------------
# named_scope through the model programs: the lowering names every scope
# ---------------------------------------------------------------------------

_LAYER = {"embed", "layers", "attn_qkv", "attention", "attn_out", "ffn",
          "final_norm"}
_PROGRAM_SCOPES = {
    "generate_tokens_shared_trunk": _LAYER | {
        "kv_write", "vocab_projection", "sample", "prefill", "decode_step"},
    "paged_prefill_chunk": _LAYER | {"kv_write"},
    "paged_score_chunk": _LAYER | {
        "kv_write", "vocab_projection", "logsumexp"},
    "_embed_forward": _LAYER,
}


def _lower_program(name):
    import jax
    import jax.numpy as jnp

    from consensus_tpu.backends.tpu import _embed_forward
    from consensus_tpu.models.config import get_model_config
    from consensus_tpu.models.generate import generate_tokens_shared_trunk
    from consensus_tpu.models.stepper import (
        make_page_state,
        paged_prefill_chunk,
        paged_score_chunk,
    )
    from consensus_tpu.models.transformer import init_params

    config = get_model_config("tiny-gemma2")
    params = jax.eval_shape(
        lambda: init_params(config, jax.random.PRNGKey(0), jnp.float32))
    rows, width = 8, 32
    tokens = jnp.zeros((rows, width), jnp.int32)
    valid = jnp.ones((rows, width), bool)
    tables = jnp.zeros((rows, 4), jnp.int32)
    lengths = jnp.full((rows,), width, jnp.int32)
    if name == "generate_tokens_shared_trunk":
        return generate_tokens_shared_trunk.lower(
            params, config, tokens[:1], valid[:1], rows,
            jnp.zeros((rows, 2), jnp.uint32), max_new_tokens=16,
            temperature=jnp.ones((rows,)),
            eos_ids=jnp.asarray([-1], jnp.int32), pad_id=0,
            init_done=jnp.zeros((rows,), bool))
    if name == "_embed_forward":
        return _embed_forward.lower(params, config, tokens, valid)
    state = jax.eval_shape(lambda: make_page_state(config, 32, 16))
    if name == "paged_prefill_chunk":
        return paged_prefill_chunk.lower(
            params, config, tokens, valid, state, tables, lengths, tokens,
            tokens)
    return paged_score_chunk.lower(
        params, config, tokens, tokens, valid, valid, state, tables, lengths,
        tokens, tokens)


class TestModelScopes:
    @pytest.mark.parametrize("program", sorted(_PROGRAM_SCOPES))
    def test_lowering_names_every_scope(self, program):
        from consensus_tpu.models import MODEL_SCOPES

        text = _lower_program(program).as_text(debug_info=True)
        named = {scope for scope in MODEL_SCOPES
                 if re.search(rf'[/"]{scope}[/"]', text)}
        assert named == _PROGRAM_SCOPES[program]


# ---------------------------------------------------------------------------
# Engine iteration ledger: MFU attribution coverage
# ---------------------------------------------------------------------------


class TestEngineMfuAttribution:
    def test_ledger_covers_engine_wall_time(self):
        engine = DecodeEngine(
            FakeBackend(), slots=8, num_pages=512, auto_start=False,
        )
        outboxes = []
        threads = []
        try:
            for i in range(4):
                out = {}

                def worker(i=i, out=out):
                    out["result"] = engine.submit("generate", [
                        GenerationRequest(
                            user_prompt=f"prompt {i} with extra words",
                            max_tokens=8, seed=i,
                        )])

                thread = threading.Thread(target=worker, daemon=True)
                thread.start()
                threads.append(thread)
                outboxes.append(out)
            assert _wait_for(
                lambda: engine.stats()["queue_depth"] == 4)
            for _ in range(3):
                engine.run_iteration()
            for thread in threads:
                thread.join(timeout=10.0)
            assert all("result" in out for out in outboxes)
            report = engine.stats()["mfu_attribution"]
            assert report["iterations"] >= 3
            assert report["tokens"] > 0
            assert report["device_s"] > 0.0
            assert report["coverage"] >= 0.95  # the acceptance bar
            fractions = (report["device_fraction"] + report["host_fraction"]
                         + report["idle_fraction"])
            assert fractions == pytest.approx(1.0, abs=0.05)
            assert set(report["host_breakdown"]) == set(
                IterationLedger.HOST_PHASES)
        finally:
            engine.close()


# ---------------------------------------------------------------------------
# Watchdog trip -> blackbox dump (integration)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestWatchdogBlackbox:
    def test_watchdog_trip_dumps_blackbox(self, tmp_path):
        path = str(tmp_path / "blackbox.json")
        recorder = get_flight_recorder()
        recorder.configure(path)
        plan = FaultPlan(seed=1, faults=[
            FaultSpec(kind="hang", op="generate", call_index=0)])
        faulty = FaultInjectingBackend(FakeBackend(), plan)
        batching = BatchingBackend(
            faulty, engine=True,
            engine_options={"watchdog_timeout_s": 0.2},
        )
        try:
            thread = threading.Thread(
                target=lambda: batching.generate(
                    [GenerationRequest(user_prompt="hello", max_tokens=4)]),
                daemon=True,
            )
            thread.start()
            assert _wait_for(lambda: faulty.hangs_active == 1, timeout=5.0)
            assert _wait_for(
                lambda: batching.engine.watchdog_trips >= 1, timeout=5.0)
            assert _wait_for(lambda: recorder.dumps >= 1, timeout=5.0)
            with open(path, encoding="utf-8") as handle:
                blackbox = json.load(handle)
            assert blackbox["schema"] == FlightRecorder.SCHEMA
            assert blackbox["reason"] == "watchdog_trip"
            kinds = [e["kind"] for e in blackbox["events"]]
            assert "watchdog_trip" in kinds
        finally:
            faulty.release_hangs()
            batching.close()
            recorder.configure(None)
