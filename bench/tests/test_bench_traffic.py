"""The traffic generator: a seed reproduces its requests exactly, every
seed gets the same lengths and gaps in another order, and the clips hold."""
import collections

import numpy as np
import pytest

from bench import traffic

MIXES = ["chat-backlog", "code-rate"]


def _key(reqs):
    return [(r.uid, r.due_s, r.max_new, r.prompt.tobytes()) for r in reqs]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    spec = traffic.load(mix)
    a = traffic.generate(spec, 2**33 + 17, 10.0, 50000)
    b = traffic.generate(spec, 2**33 + 17, 10.0, 50000)
    assert _key(a) == _key(b)
    c = traffic.generate(spec, 2**33 + 18, 10.0, 50000)
    assert _key(a) != _key(c)


@pytest.mark.parametrize("mix", MIXES)
def test_clips_and_context(mix):
    spec = traffic.load(mix)
    reqs = traffic.generate(spec, 12345, 30.0, 49152)
    p, o = spec["prompt"], spec["output"]
    for r in reqs:
        assert p["min"] <= len(r.prompt) <= p["max"]
        assert 1 <= r.max_new <= o["max"]
        assert len(r.prompt) + r.max_new <= spec["max_total"]
        assert r.prompt.dtype == np.int32
        assert 0 <= r.prompt.min() and r.prompt.max() < 49152
    assert [r.uid for r in reqs] == list(range(len(reqs)))


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_the_same_work(mix):
    """Every seed gets the same sizes and due times in the same order, and
    each whole block holds the whole length set."""
    spec = traffic.load(mix)
    n = spec["set_size"]
    runs = [traffic.generate(spec, seed, 20.0, 1000)
            for seed in (1, 2**31 + 9, 2**34)]
    shapes = [[(len(r.prompt), r.max_new, r.due_s) for r in reqs]
              for reqs in runs]
    assert shapes[0] == shapes[1] == shapes[2]
    prompts, outs = traffic.length_set(spec)
    reqs = runs[0]
    for b in range(len(reqs) // n):
        block = reqs[b * n:(b + 1) * n]
        assert collections.Counter((len(r.prompt), r.max_new)
                                   for r in block) == collections.Counter(
            zip(prompts.tolist(), outs.tolist()))
    if spec["arrival"]["kind"] == "poisson":
        due = np.asarray([r.due_s for r in reqs[:n]])
        gaps = np.diff(np.concatenate([[0.0], due]))
        assert sorted(np.round(gaps, 9)) == sorted(
            np.round(traffic.gap_set(spec), 9))


def test_medians_follow_the_source():
    for mix, (pm, om) in {"chat-backlog": (1020, 129),
                          "code-rate": (1500, 13)}.items():
        prompts, outs = traffic.length_set(traffic.load(mix))
        assert abs(np.median(prompts) - pm) / pm < 0.1
        assert abs(np.median(outs) - om) / om < 0.25


def test_backlog_is_due_at_once_and_poisson_spreads():
    chat = traffic.generate(traffic.load("chat-backlog"), 3, 10.0, 1000)
    assert {r.due_s for r in chat} == {0.0}
    spec = traffic.load("code-rate")
    code = traffic.generate(spec, 3, 30.0, 1000)
    due = [r.due_s for r in code]
    assert due == sorted(due) and due[-1] <= 30.0
    rate = len(code) / 30.0
    assert 0.6 * spec["arrival"]["rate_per_s"] < rate \
        < 1.4 * spec["arrival"]["rate_per_s"]
