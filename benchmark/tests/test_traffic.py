"""The generator: same seed same requests, every seed the same work."""
import json
import os

from benchmark import traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mix():
    with open(os.path.join(HERE, "traffic",
                           "chat_saturated_p128-1024_o64-256.json")) as f:
        return json.load(f)


def shape(reqs):
    return (sorted(len(r.prompt) for r in reqs),
            sorted(r.max_new_tokens for r in reqs),
            sum(r.greedy for r in reqs),
            sorted(round(b.due_s - a.due_s, 9)
                   for a, b in zip(reqs, reqs[1:])))


def test_same_seed_same_requests():
    a = traffic.generate(mix(), 10.0, 3_000_000_019, 50257)
    b = traffic.generate(mix(), 10.0, 3_000_000_019, 50257)
    assert a == b


def test_every_seed_holds_the_same_work():
    m = mix()
    a = traffic.generate(m, 10.0, 1, 50257)
    b = traffic.generate(m, 10.0, 2 ** 31 + 12345, 50257)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    sa, sb = shape(a), shape(b)
    assert sa[:3] == sb[:3]
    # the gaps are the same multiset but for the first, which follows
    # the window's start
    assert len(set(sa[3]) ^ set(sb[3])) <= 4
    n = round(m["arrival"]["rate_qps"] * 10.0)
    assert len(a) == len(b) == n
    assert 0.0 < a[0].due_s and a[-1].due_s < 10.0
    lo, hi = m["prompt_len"]["lo"], m["prompt_len"]["hi"]
    assert all(lo <= len(r.prompt) <= hi for r in a)
    assert all(1 <= t < 50257 for r in a for t in r.prompt)


def test_the_ramp_is_the_same_work_for_every_seed():
    m = mix()
    a = traffic.ramp(m, 5, 50257)
    b = traffic.ramp(m, 2 ** 31 + 5, 50257)
    assert len(a) == len(b) == m["ramp"]["burst"]
    assert shape(a)[:3] == shape(b)[:3]
    assert all(r.due_s < 0 for r in a)
    assert traffic.ramp(dict(m, ramp={"burst": 0}), 5, 50257) == []
