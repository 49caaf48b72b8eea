"""The schedule is a pure function of the mix and the window; the seed
draws what the requests say."""
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..", "..")))

from collections import Counter  # noqa: E402

import pytest  # noqa: E402

from chipbench import spec, traffic  # noqa: E402

BIG = 2 ** 33 + 12345


# Long prompts and short answers (a document-QA mix, not yet a cell).
LONGDOC = {"kind": "open", "arrivals": {"kind": "poisson", "rate_rps": 3.5},
           "lengths": {"isl": {"choices": [2048, 4096],
                               "weights": [0.5, 0.5]},
                       "osl": {"lo": 8, "hi": 32}},
           "engine": {"slots": 8, "max_seq": 4136}}


def _mix(name):
    if name == "longdoc":
        return LONGDOC
    return spec.load_json(os.path.join(spec.HERE, "traffic", name + ".json"))


@pytest.mark.parametrize("mix", ["chat", "longdoc"])
def test_open_loop_same_seed_same_requests(mix):
    m = _mix(mix)
    a = traffic.open_loop(m, 45.0)
    assert a == traffic.open_loop(m, 45.0)
    p = [traffic.prompt(BIG, i, r.isl, 1000) for i, r in enumerate(a[:4])]
    assert p == [traffic.prompt(BIG, i, r.isl, 1000)
                 for i, r in enumerate(a[:4])]
    assert p != [traffic.prompt(BIG + 1, i, r.isl, 1000)
                 for i, r in enumerate(a[:4])]


@pytest.mark.parametrize("mix", ["chat", "longdoc"])
def test_every_seed_offers_the_same_work(mix):
    """Every run gets the same schedule, whose lengths are the strata of
    the mix's distributions in a shuffled order."""
    m = _mix(mix)
    a = traffic.open_loop(m, 45.0)
    n = round(m["arrivals"]["rate_rps"] * 45.0)
    assert len(a) == n
    assert sorted(r.isl for r in a) == traffic._strata(m["lengths"]["isl"], n)
    assert sorted(r.osl for r in a) == traffic._strata(m["lengths"]["osl"], n)
    assert [r.isl for r in a] != sorted(r.isl for r in a)
    assert all(0.0 <= r.due_s < 45.0 for r in a)
    assert [r.due_s for r in a] == sorted(r.due_s for r in a)


def test_chat_lengths_follow_the_weights():
    a = traffic.open_loop(_mix("chat"), 45.0)
    n = len(a)
    c = Counter(r.isl for r in a)
    assert abs(c[256] - 0.5 * n) <= 1 and abs(c[1024] - 0.2 * n) <= 1
    assert min(r.osl for r in a) >= 64 and max(r.osl for r in a) <= 256


def test_prompts_are_seeded():
    p = traffic.prompt(BIG, 4, 16, 1000)
    assert p == traffic.prompt(BIG, 4, 16, 1000)
    assert p != traffic.prompt(BIG, 5, 16, 1000)
    assert len(p) == 16 and all(0 <= t < 1000 for t in p)


def test_unknown_arrival_kind_is_refused():
    m = dict(_mix("chat"), arrivals={"kind": "bursty", "rate_rps": 3.0})
    with pytest.raises(ValueError, match="bursty"):
        traffic.open_loop(m, 45.0)


def test_warm_up_covers_every_prompt_length():
    assert traffic.prompt_lengths(_mix("chat")) == [256, 512, 1024]
    assert traffic.prompt_lengths(_mix("longdoc")) == [2048, 4096]
