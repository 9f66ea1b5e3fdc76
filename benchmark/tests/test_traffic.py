"""The generator: same seed same requests, every seed the same work."""
import json
import os

import pytest

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


def test_the_shipped_mix_offers_every_seed_the_same_load_over_a_full_window():
    """At the rate the file ships, over the 30 s a run lasts: the same count
    of arrivals, the same multiset of gaps, prompt and output lengths and as
    many greedy requests for every seed — no seed offers more load than
    another — and lengths the engine's context and the reference's padding
    hold."""
    m = mix()
    n = round(m["arrival"]["rate_qps"] * 30.0)
    seeds = (7, 2 ** 31 + 4242, 3_000_000_019)
    runs = [traffic.generate(m, 30.0, s, 50257) for s in seeds]
    shapes = [shape(r) for r in runs]
    for reqs, sh in zip(runs, shapes):
        assert len(reqs) == n
        assert sh[:3] == shapes[0][:3]
        assert len(set(sh[3]) ^ set(shapes[0][3])) <= 4
        assert 0.0 < reqs[0].due_s and reqs[-1].due_s < 30.0
        assert [r.due_s for r in reqs] == sorted(r.due_s for r in reqs)
        longest = max(len(r.prompt) + r.max_new_tokens for r in reqs)
        assert longest <= m["reference_pad_to"] <= \
            m["engine"]["max_model_len"]
    assert shapes[0][2] == round(m["sampling"]["greedy_share"] * n)
    assert runs[0] != runs[1] != runs[2]
    # above the knee the mean offered load is the rate times the mean request
    tokens = sum(shapes[0][1]) / 30.0
    assert tokens == pytest.approx(m["arrival"]["rate_qps"] * 160, rel=0.05)


def test_the_rehearsal_block_still_drives_the_runner_on_the_cpu(capsys):
    from benchmark import run
    assert run.main(["--workload", "gpt355m_serve_saturated", "--seed",
                     "3000000019", "--seconds", "3", "--rehearse-cpu"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line
    assert line["attempted"] == round(
        mix()["rehearsal"]["arrival"]["rate_qps"] * 3)
    assert line["not_a_measurement"]
    assert any("token gaps by prefills" in n for n in line["notes"])
    assert any("waiting queue" in n for n in line["notes"])
