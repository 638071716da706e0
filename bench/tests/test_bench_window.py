"""Window arithmetic on hand-made records."""

import pytest

from bench import window


def record():
    rec = window.Record(start=10.0, end=20.0)
    # request 1: due before the window, tokens straddle its start
    rec.due[1] = 5.0
    rec.tokens[1] = [(9.0, 0, "prefill"), (11.0, 1, "decode"),
                     (12.5, 2, "decode")]
    # request 2: due inside, first token and a same-step second token
    rec.due[2] = 12.0
    rec.tokens[2] = [(13.0, 0, "prefill"), (13.0, 1, "decode"),
                     (14.0, 2, "decode"), (21.0, 3, "decode")]
    rec.admitted[2] = 12.5
    # request 3: due inside, never served: censored at the window's end
    rec.due[3] = 18.0
    # request 4: due after the window closed
    rec.due[4] = 20.5
    rec.counters = {"decode_calls": [4, 10], "decode_seconds": [1.0, 2.5]}
    return rec


def test_ttft_from_due_time_with_censoring():
    assert window.due_in_window(record()) == [2, 3]
    assert window.ttft_s(record()) == pytest.approx([1.0, 2.0])


def test_queue_wait_censored():
    assert window.queue_wait_s(record()) == pytest.approx([0.5, 2.0])


def test_gaps_inside_the_window_only():
    # request 1: 11.0 -> 12.5; request 2: 13 -> 13 (same step), 13 -> 14;
    # 14 -> 21 ends after the window
    assert sorted(window.gaps_s(record())) == pytest.approx([0.0, 1.0, 1.5])


def test_delivered_tokens_and_edges():
    rec = record()
    assert window.delivered_tokens(rec) == 5
    assert rec.inside(10.0) and rec.inside(20.0) and not rec.inside(20.01)
    assert rec.seconds == 10.0


def test_counter_differences():
    rec = record()
    assert window.delta(rec, "decode_calls") == 6
    assert window.delta(rec, "decode_seconds") == pytest.approx(1.5)


def test_p95():
    assert window.p95([]) is None
    assert window.p95(list(range(101))) == pytest.approx(95.0)
