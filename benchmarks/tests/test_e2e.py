import pytest

from harness import e2e


def rec(cls, t_send, token_t, finished=True, failed=False, **kw):
    return dict({"class": cls, "t_send": t_send, "token_t": token_t,
                 "finished": finished, "failed": failed,
                 "t_end": token_t[-1] if token_t else t_send}, **kw)


CLASSES = [{"name": "short", "weight": 0.75}, {"name": "long", "weight": 0.25}]


def test_stratified_mean_is_the_mix_not_the_sample():
    # short: 0.1 and 0.3 -> mean 0.2; long: 1.0 alone. By hand:
    # 0.75 * 200 + 0.25 * 1000 = 400 ms, whatever the sample's own mix
    # (its plain mean would be (0.1 + 0.3 + 1.0) / 3 = 467 ms).
    recs = [rec("short", 10.0, [10.1]), rec("short", 11.0, [11.3]),
            rec("long", 12.0, [13.0])]
    assert e2e.stratified_ttft_mean_ms(recs, 0.0, 20.0, CLASSES) \
        == pytest.approx(400.0)
    # three more short requests change nothing but the short mean
    more = recs + [rec("short", 14.0, [14.2])] * 3
    assert e2e.stratified_ttft_mean_ms(more, 0.0, 20.0, CLASSES) \
        == pytest.approx(400.0)


def test_stratified_mean_counts_only_the_window():
    recs = [rec("short", 1.0, [1.2]),            # sent before the window
            rec("short", 10.0, [10.2]),
            rec("long", 12.0, [13.0]),
            rec("long", 19.5, [20.5]),           # first token after it
            rec("long", 13.0, [], failed=True)]  # failed: no latency
    assert e2e.stratified_ttft_mean_ms(recs, 5.0, 20.0, CLASSES) \
        == pytest.approx(0.75 * 200 + 0.25 * 1000)


def test_an_empty_class_fails_the_run():
    recs = [rec("short", 10.0, [10.1])]
    with pytest.raises(e2e.MetricError, match="long"):
        e2e.stratified_ttft_mean_ms(recs, 0.0, 20.0, CLASSES)


def test_open_loop_latency_counts_from_the_due_time():
    r = rec("short", 10.5, [10.6], t_due=10.0)
    assert e2e.ttft_s(r) == pytest.approx(0.6)


def test_tpot_whole_and_cut_requests():
    whole = rec("a", 1.0, [2.0 + 0.1 * i for i in range(11)])   # 100 ms
    # cut by the window's end at t=10: 41 tokens inside, 40 gaps of
    # 50 ms count; the 20 tokens after the end do not
    cut = rec("a", 7.0, [8.0 + 0.05 * i for i in range(61)], finished=False)
    # cut with too few gaps inside: ignored
    few = rec("a", 9.0, [9.5 + 0.2 * i for i in range(10)], finished=False)
    xs = e2e.tpot_samples([whole, cut, few], 0.0, 10.0 + 1e-9)
    assert sorted(xs) == pytest.approx([0.05, 0.1])
    assert e2e.tpot_p50_ms([whole, cut, few], 0.0, 10.0 + 1e-9) \
        == pytest.approx(75.0)


def test_tpot_request_in_flight_at_the_window_start():
    r = rec("a", 0.0, [0.5 + 0.1 * i for i in range(60)])
    # window opens at 2.0: 45 tokens inside, finished, but not whole
    xs = e2e.tpot_samples([r], 2.0 - 1e-9, 100.0)
    assert xs == pytest.approx([0.1])


def test_itl_and_rate_over_the_whole_window():
    a = rec("a", 0.0, [1.0 + 0.01 * i for i in range(300)])
    gaps = e2e.gaps_in_window([a], 0.0, 10.0)
    assert len(gaps) == 299
    assert e2e.itl_p95_ms([a], 0.0, 10.0) == pytest.approx(10.0)
    assert e2e.out_tok_s([a], 0.0, 10.0) == pytest.approx(30.0)
    failed = rec("a", 0.0, [1.0, 2.0], failed=True)
    assert e2e.out_tok_s([a, failed], 0.0, 10.0) == pytest.approx(30.0)


def test_percentile_interpolates():
    assert e2e.percentile([1, 2, 3, 4], 50) == pytest.approx(2.5)
    assert e2e.percentile([5], 95) == 5


def test_unknown_end_to_end_metric_is_an_error():
    with pytest.raises(e2e.MetricError):
        e2e.end_to_end(["nope"], [], 0, 1, {"prompt_classes": []}, 1.0)
