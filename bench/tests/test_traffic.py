"""The generator: the same seed gives the same requests, and another seed
the same schedule of lengths and arrivals with other token ids."""

import hashlib
import json

import pytest

import spec
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


# sha256 of `serve_requests`, taken on the parent of the PR that added the
# flash-crowd step (PR 26): a mix without the parameter offers, for every
# seed, the requests it offered before. (mix, platform, seed) -> digest; the
# chip's sizes over 50 s with Qwen3's vocabulary, the CPU preset's over 3 s.
PINNED = {
    ("chat-open-poisson", "tpu", 7): "2f91ee747593216e",
    ("chat-open-poisson", "tpu", 2**31 + 11): "ea5bf27c9b726d46",
    ("chat-open-poisson", "cpu", 7): "c8fe3d6dbfeba5b6",
    ("chat-open-poisson", "cpu", 2**31 + 11): "d0b9b1a2e33a67aa",
    ("chat-closed-64", "tpu", 7): "917bf1388d127f2e",
    ("chat-closed-64", "tpu", 2**31 + 11): "6edbde75c94c0222",
    ("chat-closed-64", "cpu", 7): "238e393709fa451c",
    ("chat-closed-64", "cpu", 2**31 + 11): "4472ed3200221714",
}


@pytest.mark.parametrize("name,platform,seed", PINNED)
def test_a_mix_without_a_flash_step_offers_what_it_offered(name, platform, seed):
    seconds, vocab = (50.0, 151936) if platform == "tpu" else (3.0, 256)
    mix = spec._with_preset(generate.load_mix(name), platform)
    requests = generate.serve_requests(mix, seed, seconds, vocab)
    digest = hashlib.sha256(
        json.dumps(requests, sort_keys=True).encode()).hexdigest()[:16]
    assert digest == PINNED[name, platform, seed]


@pytest.mark.parametrize("process", ["poisson", "pareto"])
def test_flash_crowd_step_keeps_the_mean_rate(process):
    mix = generate.load_mix("chat-open-poisson")
    even = dict(mix, arrival={"process": process, "rate_per_s": 20.0,
                              "pareto_alpha": 1.5})
    step = {"start_share": 0.4, "length_s": 5.0, "mult": 4.0}
    crowd = dict(even, arrival=dict(even["arrival"], flash=step))
    a = generate.serve_requests(even, 3, 50.0, 1000)
    b = generate.serve_requests(crowd, 3, 50.0, 1000)
    # The same requests in the same order, the ramp untouched, only the
    # window's clock changed: as many arrivals, so the same mean rate.
    strip = lambda r: [{k: v for k, v in x.items() if k != "t"} for x in r]  # noqa: E731
    assert strip(a["window"]) == strip(b["window"]) and a["ramp"] == b["ramp"]
    ts = [x["t"] for x in b["window"]]
    assert len(ts) == 1000 and ts == sorted(ts) and 0.0 <= ts[0] and ts[-1] < 50.0
    # Inside [20, 25) the rate is four times the rate outside, which is
    # 50 / (45 + 4 * 5) of the mean: the step holds the arrivals that the
    # even clock spreads over [20, 40) of those slower seconds.
    share = 50.0 / (45.0 + 4.0 * 5.0)
    inside = sum(1 for t in ts if 20.0 <= t < 25.0)
    assert inside == sum(1 for y in a["window"]
                         if 20.0 * share <= y["t"] < 40.0 * share)
    assert inside > 2.5 * 5.0 * 20.0  # 3.08 times the mean rate, expected
    with pytest.raises(ValueError, match="flash step"):
        generate.serve_requests(
            dict(even, arrival=dict(even["arrival"], flash=dict(step, start_share=1.0))),
            3, 50.0, 1000)
