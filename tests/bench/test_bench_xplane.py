"""The trace reduction on a small trace recorded on one v5e: a 2-s
window of the rollout.qwen2.5-0.5b cell (``run.py --trace 1
--keep-trace``)."""
from pathlib import Path

import pytest

import harness
import xplane

FIXTURE = Path(__file__).parent / "fixtures" / "rollout.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return xplane.reduce(str(FIXTURE))


def _merged_busy(path):
    """Busy seconds of the first device inside the window, computed
    apart from ``xplane.reduce``: every op interval on a 1-ns grid of
    sorted endpoints, swept once."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    window = ops = None
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == xplane.WINDOW:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
        if xplane.DEVICE_PLANE.match(plane.name) and ops is None:
            ops = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                   for line in plane.lines if line.name == xplane.OPS_LINE
                   for ev in line.events]
    w0, w1 = window
    points = sorted([(max(a, w0), 1) for a, b in ops if b > w0 and a < w1]
                    + [(min(b, w1), -1) for a, b in ops
                       if b > w0 and a < w1])
    busy, depth, last = 0.0, 0, None
    for t, d in points:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy * 1e-9, (w1 - w0) * 1e-9


def test_window_and_busy_time(red):
    busy, window = _merged_busy(FIXTURE)
    assert red.window_s == pytest.approx(window)
    assert red.busy_s == pytest.approx(busy, rel=1e-9)
    assert 0 < red.busy_s <= red.window_s


def test_kernels_and_programs_are_found(red):
    attn = harness.load_module("metrics", "paged_attn_roofline.gen")
    assert red.op_seconds(attn.KERNELS) > 0
    rounds = harness.load_module("metrics", "round_device_ms.gen")
    seconds, runs = red.busy_in(rounds.LABEL)
    # every round of the fixture is a decode step, and the device works
    # only inside rounds: all of its busy time falls inside them
    assert runs == 15
    assert seconds == pytest.approx(red.busy_s, rel=1e-9)
    assert red.busy_in("serve.decode")[0] == pytest.approx(seconds)
    assert red.busy_in("no-such-annotation") is None
    assert red.op_seconds(["no-such-kernel"]) is None


def test_busy_inside_annotations_is_the_overlap():
    busy = [(0.0, 10.0), (20.0, 30.0)]
    assert xplane._overlap([(5.0, 25.0)], busy) == 10.0
    assert xplane._overlap([(0.0, 10.0), (12.0, 18.0)], busy) == 10.0
    assert xplane._overlap([(31.0, 40.0)], busy) == 0.0
    assert xplane._overlap([(-5.0, 50.0)], busy) == 20.0


def test_breakdown(red):
    b = red.breakdown()
    assert 1 <= len(b["device_ops"]) <= 10
    assert len(b["idle_gaps"]) <= 10
    ops = [s for _, s in b["device_ops"]]
    assert ops == sorted(ops, reverse=True)
    assert sum(s for _, s in b["idle_gaps"]) <= (
        red.window_s - red.busy_s) * (1 + 1e-9)
    for label, _ in b["idle_gaps"]:
        assert label.startswith(("bench.", "serve.", "outside"))


def test_describe_names_the_device(red):
    text = xplane.describe(str(FIXTURE))
    assert "/device:TPU:0" in text and xplane.OPS_LINE in text
