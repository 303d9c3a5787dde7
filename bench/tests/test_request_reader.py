"""readers/request.py over observatory records: the ratio of sums, the
95th percentile of per-record values, and None where nothing can be read
(a parent commit's records have no `deliver`)."""

import pytest

import readers

RECORDS = [
    {"ttft_s": 0.1 * (i + 1),
     "deliver": {"chunks": i + 1, "lag_s_sum": 0.01 * (i + 1) ** 2,
                 "first_lag_s": 0.002}}
    for i in range(20)
] + [{"ttft_s": None, "method": "a failed request, no first token"}]
LAG = {"num": "deliver.lag_s_sum", "den": "deliver.chunks", "scale": 1e3}


@pytest.mark.parametrize("records, spec, want", [
    # 0.01 * sum(k^2) / sum(k), k = 1..20, in ms: long requests weigh more.
    (RECORDS, {**LAG, "stat": "mean"}, 1e3 * 0.01 * 2870 / 210),
    # No den: the sum over the records that have the field.
    (RECORDS, {"num": "deliver.first_lag_s", "stat": "mean", "scale": 1e3},
     2.0),
    # Per-record values 0.1 .. 2.0; the client's interpolated quantile.
    (RECORDS, {"num": "ttft_s", "stat": "p95"}, 0.1 + 0.95 * 1.9),
    (RECORDS, {**LAG, "stat": "p95"}, 10.0 * (1 + 0.95 * 19)),
    # A field no record has, records without the source, no records.
    (RECORDS, {"num": "deliver.nope", "stat": "mean"}, None),
    ([{"ttft_s": 0.3, "phases": {}}], {**LAG, "stat": "mean"}, None),
    ([], {"num": "ttft_s", "stat": "p95"}, None),
    (None, {"num": "ttft_s", "stat": "p95"}, None),
])
def test_request_reader(records, spec, want):
    got = readers.read("request", {"observatory": records}, spec)
    assert got == (None if want is None else pytest.approx(want))


def test_request_reader_refuses_an_unknown_stat():
    with pytest.raises(ValueError, match="unknown request stat"):
        readers.read("request", {"observatory": RECORDS},
                     {"num": "ttft_s", "stat": "p99"})
