"""Unified telemetry (ISSUE 4): registry semantics, Prometheus
exposition, Chrome trace export, span overhead, and the distributed
master↔slave instrumentation (trace-id propagation + per-slave
exchange series)."""

import json
import logging
import re
import threading
import time

import pytest

from veles_tpu.telemetry import tracing
from veles_tpu.telemetry.registry import (MetricsRegistry, get_registry,
                                          percentile)


@pytest.fixture
def trace_buffer():
    """Fresh buffer + guaranteed disable/reset afterwards."""
    buf = tracing.TraceBuffer()
    tracing.enable(buffer=buf)
    try:
        yield buf
    finally:
        tracing.disable()
        tracing.set_default_trace_id(None)


# -- registry ---------------------------------------------------------------


def test_percentile_nearest_rank():
    assert percentile([], 50) == 0.0
    assert percentile([7.0], 99) == 7.0
    values = sorted(float(i) for i in range(1, 101))
    assert percentile(values, 0) == 1.0
    assert percentile(values, 50) == 51.0  # nearest rank, 0-indexed
    assert percentile(values, 100) == 100.0


def test_counter_gauge_histogram_label_semantics():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests", labels=("route", "code"))
    c.labels(route="/a", code=200).inc()
    c.labels(route="/a", code=200).inc(2)
    c.labels(route="/b", code=503).inc()
    series = {tuple(sorted(lab.items())): child.value
              for lab, child in c.series()}
    assert series[(("code", "200"), ("route", "/a"))] == 3
    assert series[(("code", "503"), ("route", "/b"))] == 1
    with pytest.raises(ValueError):
        c.labels(route="/a")  # missing label
    with pytest.raises(ValueError):
        c.inc()  # labeled family has no default child

    g = reg.gauge("depth")
    g.set(5)
    g.inc()
    g.dec(2)
    assert g.value == 4

    h = reg.histogram("lat_ms", labels=("u",))
    for i in range(100):
        h.labels(u="x").observe(i)
    assert h.labels(u="x").percentile(50) == pytest.approx(50.0)
    summary = h.labels(u="x").summary()
    # nearest rank over 0..99: round(0.95 * 99) = 94
    assert summary["count"] == 100 and summary["p95"] == 94.0


def test_metric_type_and_label_conflicts():
    reg = MetricsRegistry()
    reg.counter("thing_total", labels=("a",))
    # get-or-create is idempotent for a matching signature
    assert reg.counter("thing_total", labels=("a",)) is \
        reg.get("thing_total")
    with pytest.raises(ValueError):
        reg.gauge("thing_total")  # kind conflict
    with pytest.raises(ValueError):
        reg.counter("thing_total", labels=("b",))  # label conflict
    with pytest.raises(ValueError):
        reg.counter("bad name")


_PROM_LINE = re.compile(
    r'^(?:# (?:HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .+|'
    r'[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(?:\{[a-zA-Z0-9_]+="(?:[^"\\]|\\.)*"'
    r'(?:,[a-zA-Z0-9_]+="(?:[^"\\]|\\.)*")*\})?'
    r' -?[0-9.]+(?:[eE][+-]?[0-9]+)?)$')


def test_prometheus_exposition_line_format():
    reg = MetricsRegistry()
    c = reg.counter("veles_t_requests_total", "total requests",
                    labels=("route",))
    c.labels(route='/a"b\\c').inc(3)
    reg.gauge("veles_t_depth", "queue depth").set(2)
    h = reg.histogram("veles_t_lat_ms", "latency", labels=("u",))
    for i in range(10):
        h.labels(u="n").observe(float(i))
    text = reg.render_prometheus()
    lines = text.strip().split("\n")
    for line in lines:
        assert _PROM_LINE.match(line), "bad exposition line: %r" % line
    assert 'veles_t_requests_total{route="/a\\"b\\\\c"} 3.0' in lines
    assert "# TYPE veles_t_lat_ms summary" in lines
    assert any(line.startswith("veles_t_lat_ms_count{") for line in lines)
    assert any('quantile="0.95"' in line for line in lines)


def test_registry_snapshot_shape():
    reg = MetricsRegistry()
    reg.counter("c_total").inc()
    reg.histogram("h_ms").observe(1.0)
    snap = json.loads(json.dumps(reg.snapshot()))  # JSON-able
    assert snap["counters"]["c_total"]["series"][0]["value"] == 1.0
    hist = snap["histograms"]["h_ms"]["series"][0]
    assert hist["count"] == 1 and "p95" in hist


# -- tracing ----------------------------------------------------------------


def test_chrome_trace_round_trip_and_nesting(trace_buffer, tmp_path):
    with tracing.span("outer", kind="test"):
        time.sleep(0.002)
        with tracing.span("inner"):
            time.sleep(0.001)
    path = str(tmp_path / "trace.json")
    trace_buffer.dump(path, process_name="pytest")
    data = json.loads(open(path).read())
    events = data["traceEvents"]
    assert events, "no events exported"
    for event in events:
        if event["ph"] == "M":  # metadata (process_name) has no ts
            continue
        assert {"ph", "ts", "pid", "tid"} <= set(event)
        if event["ph"] == "X":
            assert event["dur"] >= 0
    spans = {e["name"]: e for e in events if e["ph"] == "X"}
    outer, inner = spans["outer"], spans["inner"]
    assert outer["args"]["kind"] == "test"
    # nesting: the inner span is contained in the outer one
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1


def test_trace_dump_merges_existing_file(trace_buffer, tmp_path):
    path = str(tmp_path / "trace.json")
    with tracing.span("first"):
        pass
    trace_buffer.dump(path)
    other = tracing.TraceBuffer()
    other.add_complete("second", time.perf_counter(), 0.001)
    other.dump(path)  # a second process exiting later merges, not clobbers
    names = {e["name"]
             for e in json.loads(open(path).read())["traceEvents"]}
    assert {"first", "second"} <= names


def test_request_span_bridges_request_id(trace_buffer):
    with tracing.request_span("http:/api", trace_id="req-123"):
        with tracing.span("inner"):
            pass
    by_name = {e["name"]: e for e in trace_buffer.events()}
    assert by_name["http:/api"]["args"]["trace_id"] == "req-123"
    # the id pins the whole thread context, so nested spans carry it too
    assert by_name["inner"]["args"]["trace_id"] == "req-123"
    # ...and it is scoped: spans after the request don't
    with tracing.span("after"):
        pass
    assert "trace_id" not in \
        {e["name"]: e for e in trace_buffer.events()}["after"]["args"]


def test_disabled_span_overhead():
    """The idle cost contract: a disabled span must stay in the
    single-digit-µs class (it is one function call returning a shared
    no-op context manager)."""
    assert not tracing.enabled()
    best = float("inf")
    for _ in range(3):
        n = 10000
        start = time.perf_counter()
        for _ in range(n):
            with tracing.span("idle"):
                pass
        best = min(best, (time.perf_counter() - start) / n)
    assert best < 5e-6, "disabled span costs %.2f us" % (best * 1e6)


# -- instrumentation --------------------------------------------------------


def test_unit_timings_route_through_telemetry():
    """Satellite: ``timings=True`` must produce data without the log
    level being lowered to DEBUG (it lands in the registry histogram;
    the debug line remains for backward compat)."""
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.units import TrivialUnit
    from veles_tpu.workflow import Workflow

    wf = Workflow(DummyLauncher())
    unit = TrivialUnit(wf, name="timed_unit_probe", timings=True)
    unit._initialize_wrapped()
    wf.stopped = False
    level = logging.getLogger().level
    logging.getLogger().setLevel(logging.INFO)  # NOT debug
    try:
        unit._run_wrapped()
    finally:
        logging.getLogger().setLevel(level)
    hist = get_registry().get("veles_unit_run_ms")
    assert hist is not None
    series = {labels["unit"]: child for labels, child in hist.series()}
    assert series["timed_unit_probe"].count >= 1


def test_serving_metrics_schema_unchanged():
    """Satellite: ServingMetrics.snapshot() keeps the PR 3 schema the
    dashboard consumes, while the samples mirror into the registry."""
    from veles_tpu.serving.metrics import ServingMetrics

    sm = ServingMetrics()
    sm.record_request("/api", 200, 1.5)
    sm.record_request("/api", 503)
    sm.record_batch(3, 8)
    snap = sm.snapshot()
    # additive since PR 3: "cached_total" counts requests answered
    # from the result cache (ISSUE 14) and "deadline_shed_total"
    # counts expired-in-queue drops (ISSUE 20); every PR 3 key is
    # untouched
    assert set(snap) == {"uptime_s", "model", "qps", "rejected_total",
                         "cached_total", "deadline_shed_total",
                         "endpoints", "batches", "queue_depth"}
    endpoint = snap["endpoints"]["/api"]
    assert set(endpoint) == {"requests", "responses", "qps", "p50_ms",
                             "p95_ms", "p99_ms"}
    assert set(snap["batches"]) == {"count", "rows", "mean_size",
                                    "occupancy_mean", "occupancy_p50"}
    assert snap["rejected_total"] == 1
    text = get_registry().render_prometheus()
    assert "veles_serving_requests_total{" in text


def test_webstatus_metrics_endpoints():
    from veles_tpu.web_status import WebStatusServer
    import urllib.request

    server = WebStatusServer(host="127.0.0.1", port=0).start()
    try:
        with urllib.request.urlopen(
                "http://127.0.0.1:%d/metrics" % server.port,
                timeout=5) as resp:
            assert resp.status == 200
            assert "text/plain" in resp.headers["Content-Type"]
            text = resp.read().decode()
        counters = [line for line in text.splitlines()
                    if line.startswith("veles_webstatus_http_requests_total{")]
        assert counters, text  # >= 1 counter exposed
        with urllib.request.urlopen(
                "http://127.0.0.1:%d/metrics.json" % server.port,
                timeout=5) as resp:
            snap = json.loads(resp.read())
        assert "veles_webstatus_http_requests_total" in snap["counters"]
    finally:
        server.stop()


def test_webstatus_history_endpoint():
    """/history.json (ISSUE 19): prefix + since-cursor query over the
    global store, and a malformed cursor is a 400, not a stack trace."""
    import urllib.error
    import urllib.request
    from veles_tpu.telemetry.timeseries import get_history
    from veles_tpu.web_status import WebStatusServer

    history = get_history()
    history.record("veles_test_hist_g", {"k": "a"}, 1.0, now=100.0)
    history.record("veles_test_hist_g", {"k": "a"}, 2.0, now=101.0)
    server = WebStatusServer(host="127.0.0.1", port=0).start()
    try:
        base = "http://127.0.0.1:%d" % server.port
        with urllib.request.urlopen(
                base + "/history.json?series=veles_test_hist_",
                timeout=5) as resp:
            reply = json.loads(resp.read())
        (entry,) = reply["series"]
        assert entry["name"] == "veles_test_hist_g"
        assert entry["labels"] == {"k": "a"}
        assert [[100.0, 1.0], [101.0, 2.0]] == entry["points"]
        with urllib.request.urlopen(
                base + "/history.json?series=veles_test_hist_&since=100.5",
                timeout=5) as resp:
            delta = json.loads(resp.read())
        assert [[101.0, 2.0]] == delta["series"][0]["points"]
        try:
            urllib.request.urlopen(
                base + "/history.json?since=nonsense", timeout=5)
            assert False, "malformed cursor must 400"
        except urllib.error.HTTPError as err:
            assert err.code == 400
    finally:
        server.stop()
        history.drop("veles_test_hist_g")


# -- coordinator propagation ------------------------------------------------


def test_coordinator_trace_id_propagation(trace_buffer):
    """Job replies carry (trace_id, span_id); the slave's exchange:job
    span and the master's exchange:result span pair up on them — over a
    real socket pair."""
    from veles_tpu.parallel.coordinator import (CoordinatorClient,
                                                CoordinatorServer,
                                                NoMoreJobsError)

    jobs = [{"i": i} for i in range(3)]
    merged = []

    def job_source(slave):
        if not jobs:
            raise NoMoreJobsError()
        return jobs.pop(0)

    def result_sink(data, slave):
        merged.append(data)

    server = CoordinatorServer(checksum="t", job_source=job_source,
                               result_sink=result_sink)
    try:
        client = CoordinatorClient(server.address, checksum="t").connect()
        assert client.trace_id == server.trace_id  # handshake propagation
        client.serve_forever(lambda job: job["i"] * 2, max_idle=5)
        client.close()
        assert sorted(merged) == [0, 2, 4]
        events = trace_buffer.events()
        job_spans = [e for e in events if e["name"] == "exchange:job"]
        result_spans = [e for e in events
                        if e["name"] == "exchange:result"]
        assert len(job_spans) == 3
        assert len(result_spans) == 3
        assert {e["args"]["trace_id"]
                for e in job_spans + result_spans} == {server.trace_id}
        # each result span names the same job span it resolves
        assert {e["args"]["span_id"] for e in job_spans} == \
            {e["args"]["span_id"] for e in result_spans}
    finally:
        server.stop()


# -- the acceptance run: 2 slaves, master-side series + one trace id --------


def test_two_slave_run_produces_unified_telemetry(trace_buffer, tmp_path):
    """A 2-slave distributed MNIST-small run must leave (1) per-slave
    exchange_bytes / encode_ms / rtt series in the master's registry
    and (2) a Perfetto-valid trace where unit, step, and exchange spans
    share ONE trace id across master and slave records."""
    from test_mnist_e2e import synthetic_digits

    from veles_tpu import prng
    from veles_tpu.launcher import Launcher
    from veles_tpu.models.mnist import MnistWorkflow

    def make(launcher):
        return MnistWorkflow(launcher, provider=synthetic_digits(),
                             layers=(32,), minibatch_size=60,
                             learning_rate=0.08, max_epochs=2)

    prng.get().seed(42)
    prng.get("loader").seed(43)
    master = Launcher(listen_address="127.0.0.1:0", graphics=False)
    make(master)
    master.initialize()
    port = master._server.address[1]
    trace_id = master._server.trace_id

    slaves = []
    for _ in range(2):
        prng.get().seed(42)
        prng.get("loader").seed(43)
        # eager slaves replay jobs through the unit graph, so the trace
        # shows unit spans under the same id; fast heartbeats give the
        # master RTT samples within the short run
        slave = Launcher(master_address="127.0.0.1:%d" % port,
                         graphics=False, eager=True,
                         heartbeat_interval=0.1)
        make(slave)
        slave.initialize()
        slaves.append(slave)
    slave_ids = {s._client.id for s in slaves}
    threads = [threading.Thread(target=s.run, daemon=True)
               for s in slaves]
    for t in threads:
        t.start()
    master.run()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)

    # (1) master-side per-slave series
    snap = get_registry().snapshot()
    exchange = snap["counters"]["veles_exchange_bytes_total"]["series"]
    assert {e["labels"]["slave"] for e in exchange} >= slave_ids
    assert {e["labels"]["direction"] for e in exchange} == \
        {"to_slave", "from_slave"}
    assert all(e["value"] > 0 for e in exchange)
    encode = snap["histograms"]["veles_exchange_encode_ms"]["series"]
    assert {e["labels"]["slave"] for e in encode} >= slave_ids
    rtt = snap["histograms"]["veles_slave_heartbeat_rtt_ms"]["series"]
    assert {e["labels"]["slave"] for e in rtt} >= slave_ids
    assert all(e["count"] >= 1 for e in rtt)

    # (2) one trace id across master and slave records
    events = trace_buffer.events()
    interesting = [e for e in events
                   if e["name"].startswith(("unit:", "step:",
                                            "exchange:"))]
    kinds = {e["name"].split(":")[0] for e in interesting}
    assert kinds == {"unit", "step", "exchange"}
    assert {e["args"].get("trace_id") for e in interesting} == {trace_id}
    # both halves of the exchange are present
    names = {e["name"] for e in interesting}
    assert {"exchange:job", "exchange:result"} <= names

    # the dump is valid Chrome trace-event JSON
    path = str(tmp_path / "distributed_trace.json")
    trace_buffer.dump(path)
    data = json.loads(open(path).read())
    assert isinstance(data["traceEvents"], list) and data["traceEvents"]
    for event in data["traceEvents"]:
        assert {"ph", "ts", "pid", "tid"} <= set(event)


# -- profiler layer integration (ISSUE 7) -----------------------------------


def test_profiler_metrics_land_in_shared_registry():
    """The attribution layer writes through THE registry: phase gauges
    and cost-book series must appear in the same snapshot/exposition
    every other surface scrapes."""
    from veles_tpu.telemetry import profiler

    profiler.reset_phases()
    profiler.reset_cost_book()
    try:
        profiler.record_phase("first_step", 0.2)
        book = profiler.get_cost_book()
        book.note_cost("t_op", 2e9, 1e9)
        book.observe_ms("t_op", 0.004)
        snap = get_registry().snapshot()
        gauges = snap["gauges"]
        phase = {tuple(sorted(s["labels"].items())): s["value"]
                 for s in gauges["veles_phase_ms"]["series"]}
        assert phase[(("phase", "first_step"),)] == pytest.approx(200.0)
        flops = {s["labels"]["op"]: s["value"]
                 for s in gauges["veles_op_flops"]["series"]}
        assert flops["t_op"] == pytest.approx(2e9)
        text = get_registry().render_prometheus()
        assert 'veles_phase_ms{phase="first_step"}' in text
        assert 'veles_op_ms_count{op="t_op"}' in text
    finally:
        profiler.reset_phases()
        profiler.reset_cost_book()


def test_phase_spans_reach_trace_buffer(trace_buffer):
    """phase() is a span too: the cold-start stages show up on the
    --trace-out timeline, not only as gauges."""
    from veles_tpu.telemetry import profiler

    profiler.reset_phases()
    try:
        with profiler.phase("dataset_load"):
            pass
        names = {e["name"] for e in trace_buffer.events()}
        assert "phase:dataset_load" in names
    finally:
        profiler.reset_phases()


def test_flight_recorder_counts_in_registry(tmp_path):
    """Detector trips + written records surface as counters."""
    import numpy

    from veles_tpu.telemetry import flight

    rec = flight.FlightRecorder(out_dir=str(tmp_path),
                                min_dump_interval_s=0.0)
    try:
        rec.check_losses(numpy.array([numpy.nan]), epoch=0)
        snap = get_registry().snapshot()
        trips = {s["labels"]["detector"]: s["value"]
                 for s in snap["counters"]
                 ["veles_flight_detector_trips_total"]["series"]}
        assert trips["non_finite_loss"] >= 1
        records = {s["labels"]["reason"]: s["value"]
                   for s in snap["counters"]
                   ["veles_flight_records_total"]["series"]}
        assert records["non_finite_loss"] >= 1
    finally:
        rec.stop()
