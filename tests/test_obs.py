"""Observability: span tracer, metrics registry, Perfetto export,
trace_report validation, and the MetricLogger sink."""
import importlib.util
import json
import pathlib
import threading

import pytest

from repro.metrics.logging import MetricLogger, read_jsonl
from repro.obs import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NULL_TRACER,
    TraceEvent,
    Tracer,
    events_to_trace_json,
    export_perfetto,
    export_trace_jsonl,
    load_trace_events,
    ProfilerTracer,
    make_tracer,
    mirrored,
)


def _load_trace_report():
    path = (pathlib.Path(__file__).resolve().parent.parent
            / "benchmarks" / "trace_report.py")
    spec = importlib.util.spec_from_file_location("trace_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


trace_report = _load_trace_report()


# --- tracer -----------------------------------------------------------------


def test_tracer_sync_spans_and_instants():
    tr = Tracer(detail="spans")
    with tr.span("step", tid="engine", n=3):
        tr.instant("swap", tid="engine", old=0, new=1)
    evs = tr.events()
    assert [e.ph for e in evs] == ["B", "i", "E"]
    assert evs[0].args == {"n": 3}
    assert evs[0].ts <= evs[1].ts <= evs[2].ts
    assert all(e.pid == "serve" and e.tid == "engine" for e in evs)


def test_tracer_async_spans_carry_id():
    tr = Tracer(detail="spans")
    tr.async_begin("waiting", 7)
    tr.async_end("waiting", 7)
    b, e = tr.events()
    assert (b.ph, e.ph) == ("b", "e")
    assert b.id == e.id == 7


def test_tracer_ring_evicts_and_counts_drops():
    tr = Tracer(capacity=4, detail="spans")
    for i in range(10):
        tr.instant(f"e{i}")
    assert len(tr) == 4
    assert tr.dropped == 6
    assert [e.name for e in tr.events()] == ["e6", "e7", "e8", "e9"]
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_tracer_detail_levels():
    assert make_tracer("off") is NULL_TRACER
    assert make_tracer("spans").full is False
    assert make_tracer("full").full is True
    with pytest.raises(ValueError):
        make_tracer("verbose")
    with pytest.raises(ValueError):
        Tracer(detail="off")


def test_null_tracer_is_inert():
    assert NULL_TRACER.enabled is False and NULL_TRACER.full is False
    with NULL_TRACER.span("x", big_arg=list(range(100))):
        NULL_TRACER.instant("y")
        NULL_TRACER.counter("z", v=1.0)
        NULL_TRACER.async_begin("w", 1)
        NULL_TRACER.async_end("w", 1)
    assert len(NULL_TRACER) == 0


def test_tracer_to_trace_ns_matches_now():
    import time

    tr = Tracer(detail="spans")
    mono = time.monotonic()
    assert abs(tr.to_trace_ns(mono) - tr.now()) < 50_000_000  # 50ms slack


def test_tracer_threaded_appends_all_land():
    tr = Tracer(capacity=1 << 14, detail="spans")

    def work(tid):
        for _ in range(500):
            tr.instant("tick", tid=f"t{tid}")

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tr) == 2000 and tr.dropped == 0


# --- registry ---------------------------------------------------------------


def test_registry_instruments_get_or_create_with_labels():
    reg = MetricsRegistry()
    c = reg.counter("drops", reason="tv_gate")
    c.inc()
    c.inc(2.0)
    assert reg.counter("drops", reason="tv_gate") is c
    assert reg.counter("drops", reason="max_lag") is not c
    with pytest.raises(ValueError):
        c.inc(-1.0)
    g = reg.gauge("depth")
    g.set(4)
    g.dec()
    assert g.value == 3.0
    snap = reg.snapshot()
    assert snap["counters"]["drops{reason=tv_gate}"] == 3.0
    assert snap["gauges"]["depth"] == 3.0


def test_histogram_exact_and_windowed_percentiles():
    h = Histogram()
    for v in range(1, 101):           # 1..100
        h.observe(float(v))
    assert h.percentiles()["p50"] == 50.0
    assert h.percentiles()["p99"] == 99.0
    start = h.count
    for v in (1000.0, 2000.0, 3000.0):
        h.observe(v)
    win = h.percentiles(start=start)
    assert win["p50"] == 2000.0       # only post-start samples
    s = h.summary(start=start)
    assert s["count"] == 3 and s["mean"] == 2000.0
    assert Histogram().percentiles()["p50"] == 0.0  # empty: zeros, no raise


def test_histogram_bounded_retention():
    h = Histogram(max_samples=8)
    for v in range(100):
        h.observe(float(v))
    assert h.count == 100 and len(h.samples) == 8
    assert h.percentiles()["p50"] == 95.0   # window = last 8 (92..99)


def test_registry_producers_merge_and_replace():
    reg = MetricsRegistry()
    reg.register_producer("serve", lambda: {"tokens": 5})
    assert reg.snapshot()["serve"] == {"tokens": 5}
    reg.register_producer("serve", lambda: {"tokens": 9})  # replace
    assert reg.snapshot()["serve"] == {"tokens": 9}
    reg.unregister_producer("serve")
    assert "serve" not in reg.snapshot()


def test_registry_export_jsonl_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("n").inc(3)
    path = str(tmp_path / "m.jsonl")
    reg.export_jsonl(path, step=1)
    reg.export_jsonl(path, step=2)
    rows = read_jsonl(path)
    assert [r["step"] for r in rows] == [1, 2]
    assert rows[0]["counters"]["n"] == 3.0


# --- perfetto export --------------------------------------------------------


def _sample_tracer():
    tr = Tracer(detail="full")
    tr.async_begin("waiting", 0)
    tr.async_end("waiting", 0)
    tr.async_begin("running", 0)
    with tr.span("decode", tid="engine", chunk=4):
        tr.instant("token", tid="tokens", rid=0, v=1, lag=0, tok=42)
    tr.counter("pool_free", free=12.0)
    tr.async_end("running", 0)
    return tr


def test_events_to_trace_json_shape():
    doc = events_to_trace_json(_sample_tracer())
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    names = {(e["name"], e["args"]["name"]) for e in meta}
    assert ("process_name", "serve") in names
    assert ("thread_name", "engine") in names
    body = [e for e in evs if e["ph"] != "M"]
    for e in body:
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    asy = [e for e in body if e["ph"] in ("b", "e")]
    assert all(e["cat"] == "request" and e["id"] == 0 for e in asy)
    inst = next(e for e in body if e["ph"] == "i")
    assert inst["s"] == "t"
    json.dumps(doc)                   # JSON-serializable end to end


def test_export_roundtrip_both_formats(tmp_path):
    tr = _sample_tracer()
    jpath, lpath = str(tmp_path / "t.json"), str(tmp_path / "t.jsonl")
    n_json = export_perfetto(tr, jpath)
    n_jsonl = export_trace_jsonl(tr, lpath)
    assert n_json == n_jsonl == len(tr.events())
    from_json = load_trace_events(jpath)
    from_jsonl = load_trace_events(lpath)
    assert len(from_json) == len(from_jsonl) == n_json
    # same phases and (µs) timestamps from either format
    assert [e["ph"] for e in from_json] == [e["ph"] for e in from_jsonl]
    for a, b in zip(from_json, from_jsonl):
        assert a["ts"] == pytest.approx(b["ts"], abs=1e-6)


def _events(tr):
    return [(e.ph, e.name, e.pid, e.tid, e.args, e.id) for e in tr.events()]


def _spans(tr):
    with tr.span("step", tid="engine", n=3):
        with tr.span("decode", tid="engine", chunk=4):
            tr.instant("swap", tid="engine", old=0, new=1)
        tr.counter("pool_free", free=12.0)
    with tr.span("produce", pid="runtime", tid="producer"):
        pass


def _profiled(log_dir, fn):
    """Host annotation names and their stats from a ``jax.profiler``
    capture of ``fn()``."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(log_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(pathlib.Path(log_dir).glob("**/*.xplane.pb"))[-1]
    data = ProfileData.from_file(str(path))
    return [(ev.name, dict(ev.stats)) for plane in data.planes
            if plane.name.startswith("/host")
            for line in plane.lines for ev in line.events]


def test_null_tracer_mirrors_nothing(tmp_path):
    names = [n for n, _ in _profiled(tmp_path, lambda: _spans(NULL_TRACER))]
    assert not [n for n in names if n.startswith(("serve.", "runtime."))]
    assert not NULL_TRACER.mirror and len(NULL_TRACER) == 0
    prof = mirrored(NULL_TRACER)      # NULL_TRACER itself stays a no-op
    assert isinstance(prof, ProfilerTracer) and prof is not NULL_TRACER
    assert not NULL_TRACER.mirror


def test_profiler_tracer_keeps_no_ring(tmp_path):
    prof = mirrored(None)
    assert isinstance(prof, ProfilerTracer)
    assert prof.mirror and not prof.enabled and mirrored(prof) is prof
    got = _profiled(tmp_path, lambda: _spans(prof))
    mine = [(n, st) for n, st in got if n.startswith(("serve.", "runtime."))]
    # sync spans only, named pid.name, with none of the span's args
    assert [n for n, _ in mine] == ["serve.step", "serve.decode",
                                    "runtime.produce"]
    assert all("n" not in st and "chunk" not in st for _, st in mine)
    assert len(prof) == 0 and prof.events() == []


def test_mirrored_ring_keeps_its_events(tmp_path):
    plain, ring = Tracer(detail="spans"), Tracer(detail="spans")
    assert mirrored(ring) is ring and ring.mirror and not plain.mirror
    _spans(plain)
    got = _profiled(tmp_path, lambda: _spans(ring))
    assert _events(ring) == _events(plain)
    assert [n for n, _ in got if n.startswith(("serve.", "runtime."))] \
        == ["serve.step", "serve.decode", "runtime.produce"]


# --- trace_report validation ------------------------------------------------


def test_check_balance_accepts_balanced():
    doc = events_to_trace_json(_sample_tracer())
    evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    assert trace_report.check_balance(evs) == []


def test_check_balance_rejects_imbalance():
    tr = Tracer(detail="spans")
    tr.begin("decode")                         # never closed
    tr.async_begin("running", 3)               # never closed
    errors = trace_report.check_balance(
        [e for e in events_to_trace_json(tr)["traceEvents"]
         if e["ph"] != "M"])
    assert len(errors) == 2
    assert any("never closed" in e for e in errors)
    assert any("left open" in e for e in errors)


def test_check_balance_rejects_bad_nesting():
    evs = [
        {"ph": "B", "name": "a", "pid": 1, "tid": 1, "ts": 0},
        {"ph": "B", "name": "b", "pid": 1, "tid": 1, "ts": 1},
        {"ph": "E", "name": "a", "pid": 1, "tid": 1, "ts": 2},
        {"ph": "E", "name": "b", "pid": 1, "tid": 1, "ts": 3},
    ]
    assert trace_report.check_balance(evs)


def test_trace_report_cli_check(tmp_path, capsys):
    path = str(tmp_path / "ok.json")
    export_perfetto(_sample_tracer(), path)
    assert trace_report.main([path, "--check"]) == 0
    bad = Tracer(detail="spans")
    bad.begin("oops")
    bad_path = str(tmp_path / "bad.json")
    export_perfetto(bad, bad_path)
    assert trace_report.main([bad_path, "--check"]) == 1
    garbage = tmp_path / "garbage.json"
    garbage.write_text("{not json")
    assert trace_report.main([str(garbage), "--check"]) == 2


def test_trace_report_prints_lag_and_states(tmp_path, capsys):
    path = str(tmp_path / "t.json")
    export_perfetto(_sample_tracer(), path)
    assert trace_report.main([path]) == 0
    out = capsys.readouterr().out
    assert "time in state per request" in out
    assert "lag   0:" in out


# --- MetricLogger sink ------------------------------------------------------


def test_metric_logger_context_manager_and_rows(tmp_path):
    path = str(tmp_path / "log.jsonl")
    with MetricLogger(path) as log:
        log.log(0, loss=1.5, note="warm")
        log.log(1, loss=1.25)
    rows = read_jsonl(path)
    assert [r["step"] for r in rows] == [0, 1]
    assert rows[0]["loss"] == 1.5 and rows[0]["note"] == "warm"
    with MetricLogger(path) as log:   # append mode: old rows survive
        log.log(2, loss=1.0)
    assert len(read_jsonl(path)) == 3


def test_metric_logger_registry_sink(tmp_path):
    reg = MetricsRegistry()
    reg.counter("tokens").inc(7)
    reg.register_producer("serve", lambda: {"swaps": 2})
    path = str(tmp_path / "reg.jsonl")
    with MetricLogger(path, registry=reg) as log:
        row = log.log_registry(5, phase="a")
    assert row["serve"] == {"swaps": 2} and row["phase"] == "a"
    on_disk = read_jsonl(path)[0]
    assert on_disk["counters"]["tokens"] == 7.0
    assert on_disk["step"] == 5
    with MetricLogger(path) as log:
        with pytest.raises(ValueError):
            log.log_registry(0)


def test_metric_logger_close_idempotent(tmp_path):
    log = MetricLogger(str(tmp_path / "x.jsonl"))
    log.log(0, a=1)
    log.close()
    log.close()                       # second close is a no-op
    log.log(1, a=2)                   # post-close writes are dropped
    assert len(read_jsonl(str(tmp_path / "x.jsonl"))) == 1
