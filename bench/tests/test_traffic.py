"""The generator: the same seed gives the same requests, and another seed
the same schedule of lengths and arrivals with other token ids."""

from traffic import generate


def test_same_seed_same_requests_and_other_seed_same_work():
    mix = generate.load_mix("chat-open-poisson")
    a = generate.serve_requests(mix, 2**31 + 7, 20.0, 1000)
    b = generate.serve_requests(mix, 2**31 + 7, 20.0, 1000)
    c = generate.serve_requests(mix, 11, 20.0, 1000)
    assert a == b
    shape = lambda r: [(x["prompt_len"], x["max_new"], x["t"]) for x in r["window"]]  # noqa: E731
    assert shape(a) == shape(c) and a["window"] != c["window"]
    assert len(a["window"]) == round(mix["arrival"]["rate_per_s"] * 20.0)
    assert all(0.0 <= x["t"] < 20.0 for x in a["window"])
    assert all(-mix["ramp_s"] <= x["t"] < 0.0 for x in a["ramp"])
    lo, hi = mix["prompt"]["lo"], mix["prompt"]["hi"]
    assert all(lo <= x["prompt_len"] == len(x["prompt"]) <= hi
               for x in a["window"])


def test_closed_pool_is_the_same_for_every_seed():
    mix = generate.load_mix("chat-closed-64")
    a = generate.serve_requests(mix, 1, 10.0, 1000)
    b = generate.serve_requests(mix, 2, 10.0, 1000)
    key = lambda r: [(x["prompt_len"], x["max_new"]) for x in r["pool"]]  # noqa: E731
    assert key(a) == key(b) and len(a["pool"]) == mix["closed_pool"]
