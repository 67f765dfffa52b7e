"""What each metric file computes, on records made by hand."""
from types import SimpleNamespace

import pytest

import harness
import readers
import work
from harness import Round, RunRecord, Sent

CONF = harness.load_config("qwen2.5-0.5b")
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _req(first, last, n):
    return SimpleNamespace(first_token_time=first, last_emit_time=last,
                           tokens=[0] * n)


def _run(**kw):
    r = RunRecord(cell={}, config=CONF, seed=0, t_start=100.0, t_end=110.0,
                  max_batch=4, num_blocks=10, peaks=PEAKS)
    for k, v in kw.items():
        setattr(r, k, v)
    return r


def test_ttft_counts_from_due_and_waiting_requests():
    sent = [Sent(due=101.0, prompt=None, max_new=1, req=_req(101.5, 102, 2)),
            Sent(due=108.0, prompt=None, max_new=1, req=_req(None, None, 0)),
            Sent(due=99.0, prompt=None, max_new=1, req=_req(99.5, 99.6, 2))]
    # the third was due before the window and is left out; the second
    # has no token by the window's end and counts the 2 s it waited
    assert sorted(readers.ttft_s(_run(sent=sent))) == [0.5, 2.0]
    ttft = harness.load_module("metrics", "ttft_p90_ms").read
    assert ttft(_run(sent=sent)) == pytest.approx(2000.0)


def test_tpot_is_per_token_after_the_first():
    sent = [Sent(due=0, prompt=None, max_new=1, req=_req(1.0, 3.0, 5)),
            Sent(due=0, prompt=None, max_new=1, req=_req(1.0, 1.0, 1))]
    assert readers.tpot_s(_run(sent=sent)) == [0.5]


def test_nearest_rank():
    assert readers.nearest_rank(list(range(1, 11)), 90) == 9
    assert readers.nearest_rank([], 90) is None


def test_occupancy_pages_and_mfu():
    rounds = [Round(0, 1, running=4, pages_used=5, rows=4, ctx_rows=40,
                    kv_rows=40),
              Round(1, 2, running=2, pages_used=10, rows=2, ctx_rows=20,
                    kv_rows=20)]
    run = _run(rounds=rounds)
    assert readers.occupancy_pct(run) == pytest.approx(75.0)
    assert readers.pages_pct(run) == pytest.approx(75.0)
    want = 100 * work.forward_flops(CONF, 6, 60) / (10.0 * 197e12)
    assert readers.mfu_pct(run) == pytest.approx(want)


def test_trace_metrics_leave_nothing_when_nothing_ran():
    run = _run(rounds=[Round(0, 1, 1, 1, 1, 1, 1)])
    assert readers.roofline_pct(run, ["x"]) is None
    assert readers.idle_pct(run) is None
    assert readers.busy_ms(run, "bench.step") is None
    import xplane

    run.trace = xplane.Reduced(window_s=2.0, busy_s=1.5)
    assert readers.roofline_pct(run, ["paged"]) is None
    assert readers.busy_ms(run, "bench.step") is None
    assert readers.idle_pct(run) == pytest.approx(25.0)


def test_roofline_divides_by_the_named_count():
    import xplane

    rounds = [Round(0, 1, running=2, pages_used=1, rows=17, ctx_rows=266,
                    kv_rows=146, slots=((130, 1), (16, 16)))]
    run = _run(rounds=rounds)
    run.trace = xplane.Reduced(window_s=2.0, busy_s=1.5,
                               ops={"%paged_attention_varlen.1": [1e-3, 1]})
    done = readers.window_work(run)
    assert done == {"rows": 17, "ctx_rows": 266, "kv_rows": 146,
                    "slots": [(130, 1), (16, 16)]}
    w = work.paged_attention(CONF, 17, 266, 146)
    least = work.roofline_seconds(w["flops"], w["bytes"], PEAKS)["seconds"]
    kernels = [r"^%paged_attention_varlen\b"]
    assert readers.roofline_pct(run, kernels) == pytest.approx(
        100 * least / 1e-3)
    assert readers.roofline_pct(run, kernels, "paged_attention") == \
        readers.roofline_pct(run, kernels)
    with pytest.raises(SystemExit, match="no_such_count"):
        readers.roofline_pct(run, kernels, "no_such_count")
