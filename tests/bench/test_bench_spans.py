"""The engine's round phases read from a trace (``spans.py``), on the
recorded fixture and on synthetic intervals, and the metric that reads
the program's ``serve.step`` span."""
import bisect
import json
from pathlib import Path

import pytest

import harness
import spans
import xplane

FIXTURE = Path(__file__).parent / "fixtures" / "rollout.xplane.pb"


@pytest.fixture(scope="module")
def got():
    return spans.read(str(FIXTURE))


@pytest.fixture(scope="module")
def red():
    return xplane.reduce(str(FIXTURE))


@pytest.fixture(scope="module")
def raw():
    """The fixture's window, the first device's op intervals and the
    ``bench.*``/``serve.*`` host spans, read apart from ``spans.read``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(FIXTURE))
    window, ops, host = None, None, []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                end = ev.start_ns + ev.duration_ns
                if ev.name == xplane.WINDOW:
                    window = (ev.start_ns, end)
                elif plane.name.startswith("/host") and \
                        ev.name.startswith(("bench.", "serve.")):
                    host.append((ev.start_ns, end, ev.name))
        if xplane.DEVICE_PLANE.match(plane.name) and ops is None:
            ops = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                   for line in plane.lines if line.name == xplane.OPS_LINE
                   for ev in line.events]
    return window, ops, host


def _busy_between(ops, a, b):
    """Busy nanoseconds of ``ops`` inside [a, b): endpoints swept once."""
    points = sorted([(max(s, a), 1) for s, e in ops if e > a and s < b]
                    + [(min(e, b), -1) for s, e in ops if e > a and s < b])
    busy, depth, last = 0.0, 0, None
    for t, d in points:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_interval_pairs_match_an_independent_sweep(got, red, raw):
    (w0, w1), ops, host = raw
    for label in ("bench.step", "serve.decode"):
        mine = [(max(a, w0), min(b, w1)) for a, b, name in host
                if name == label and b > w0 and a < w1]
        pairs = got.pairs(label)
        assert len(pairs) == len(mine) == 15
        for (host_s, busy_s), (a, b) in zip(pairs, mine):
            assert host_s == pytest.approx((b - a) * 1e-9, rel=1e-12)
            assert busy_s == pytest.approx(_busy_between(ops, a, b) * 1e-9,
                                           rel=1e-9)
            assert 0 < busy_s <= host_s
        # the intervals' busy time adds up to what xplane.reduce gives
        assert sum(v for _, v in pairs) == pytest.approx(
            red.busy_in(label)[0], rel=1e-12)
    assert got.pairs("no-such-annotation") is None
    assert got.window_s == pytest.approx(red.window_s, rel=1e-12)


def test_idle_by_label_matches_an_independent_sweep(got, red, raw):
    """Every idle nanosecond of the window goes to the shortest host
    span covering it: counted here segment by segment between all
    endpoints, op depth by bisection, the label by brute force."""
    (w0, w1), ops, host = raw
    live = [(max(a, w0), min(b, w1)) for a, b in ops if b > w0 and a < w1]
    starts = sorted(a for a, _ in live)
    ends = sorted(b for _, b in live)
    points = sorted({w0, w1} | {t for iv in live for t in iv}
                    | {t for a, b, _ in host for t in (a, b) if w0 < t < w1})
    want = {}
    for t, u in zip(points, points[1:]):
        if bisect.bisect_right(starts, t) - bisect.bisect_right(ends, t):
            continue
        mid = (t + u) / 2
        cover = [(b - a, name) for a, b, name in host if a <= mid <= b]
        label = min(cover)[1] if cover else spans.OUTSIDE
        want[label] = want.get(label, 0.0) + (u - t) * 1e-9
    assert set(got.idle_by_label) == set(want)
    for label, seconds in want.items():
        assert got.idle_by_label[label] == pytest.approx(seconds, rel=1e-9)
    assert sum(got.idle_by_label.values()) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-9)
    assert got.idle_s == pytest.approx(red.window_s - red.busy_s, rel=1e-9)
    # the gap labels of xplane's breakdown take the same innermost span
    assert {label for _, label in red.gaps} <= set(got.idle_by_label)


def test_idle_by_innermost_on_nested_intervals():
    nested = [(0, 100, "bench.step"), (5, 95, "serve.step"),
              (10, 20, "serve.schedule"), (20, 30, "serve.upload"),
              (30, 35, "serve.dispatch"), (35, 80, "serve.result_wait"),
              (80, 90, "serve.record"),
              (150, 170, "serve.step"), (160, 165, "serve.record"),
              (200, 260, "a"), (205, 215, "b"), (206, 214, "c")]
    idle = [(0, 12), (25, 33), (40, 50), (85, 98), (120, 130), (155, 175),
            (220, 230)]
    out = spans.idle_by_innermost(idle, nested)
    assert out == {"bench.step": 8, "serve.step": 20, "serve.schedule": 2,
                   "serve.upload": 5, "serve.dispatch": 3,
                   "serve.result_wait": 10, "serve.record": 10,
                   spans.OUTSIDE: 15, "a": 10}
    assert sum(out.values()) == sum(b - a for a, b in idle)
    assert spans.idle_by_innermost([], nested) == {}
    assert spans.idle_by_innermost([(0, 4)], []) == {spans.OUTSIDE: 4}


def test_round_phase_readings():
    got = spans.Spans(window_s=1.0, idle_s=0.5, intervals={
        "serve.step": [(0.100, 0.090), (0.110, 0.090), (0.300, 0.090)],
        "serve.result_wait": [(0.050, 0.045), (0.060, 0.045),
                              (0.250, 0.050)],
        "serve.schedule": [(0.002, 0.0), (0.004, 0.0), (0.009, 0.0)]})
    # idle inside each round: 10, 20, 210 ms; the median is the middle;
    # 5 + 15 + 200 ms idle while waiting, over three rounds
    assert got.summary() == pytest.approx({
        "step_device_ms": 90.0, "step_host_ms": 20.0,
        "result_wait_ms": 220.0 / 3, "schedule_ms": 4.0,
        "upload_ms": None})
    assert got.idle_ms_median("serve.upload") is None
    assert got.idle_ms_per("serve.upload", "serve.step") is None
    assert got.idle_ms_per("serve.result_wait", "serve.upload") is None
    assert got.busy_ms_mean("serve.upload") is None


def test_fixture_has_no_program_spans(got, capsys):
    """The program that recorded the fixture had no ``serve.step`` span:
    the round's phases read nothing, and the tool says so."""
    assert set(got.summary().values()) == {None}
    assert spans.main([str(FIXTURE)]) == 0
    line = json.loads(capsys.readouterr().out)
    assert line["summary"]["step_host_ms"] is None
    assert line["idle_s"] == pytest.approx(got.idle_s)


def test_step_device_ms_reads_the_program_span(red):
    metric = harness.load_module("metrics", "step_device_ms.gen")
    conf = harness.load_config("qwen2.5-0.5b")
    run = harness.RunRecord(cell={}, config=conf, seed=0, trace=red)
    assert metric.read(run) is None          # no serve.step in the fixture
    trace = xplane.Reduced(window_s=1.0, busy_s=0.5)
    trace.annotated = {"serve.step": [0.270, 3], "bench.step": [0.3, 3]}
    run.trace = trace
    assert metric.read(run) == pytest.approx(90.0)
    run.trace = None
    assert metric.read(run) is None


def test_existing_metrics_keep_their_fixture_values(red):
    """The six per-layer metrics of the first benchmark, on the fixture
    and a fixed set of rounds, read what they read when it was
    accepted."""
    conf = harness.load_config("qwen2.5-0.5b")
    rounds = [harness.Round(0, 1, running=115, pages_used=4400, rows=115,
                            ctx_rows=115 * 700, kv_rows=115 * 700)
              for _ in range(15)]
    run = harness.RunRecord(
        cell={}, config=conf, seed=0, t_start=100.0, t_end=102.0,
        max_batch=128, num_blocks=40960, rounds=rounds, trace=red,
        peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    pinned = {"slot_occupancy.gen": 89.84375,
              "kv_pages_used.gen": 10.7421875,
              "round_device_ms.gen": 127.20229446666667,
              "paged_attn_roofline.gen": 0.9908770276740365,
              "step_mfu.gen": 0.26074036629441627,
              "device_idle.gen": 6.857447385953719}
    for name, value in pinned.items():
        read = harness.load_module("metrics", name).read(run)
        assert read == pytest.approx(value, rel=1e-12), name
