"""The schedule is a pure function of the mix and the window; the seed
draws what the requests say."""
import json
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
    assert traffic.prompt_lengths(_mix("batch")) == [256, 512, 1024]
    assert traffic.longest(_mix("batch")) == traffic.longest(_mix("chat"))


def test_closed_loop_clients_follow_the_mix():
    """Each client's lengths are the mix's strata over its cycle, in an
    order of its own, fixed by the mix and the client's index alone; the
    clients' first sends are staggered evenly."""
    m = _mix("batch")
    a = m["arrivals"]
    cs = traffic.clients(m)
    assert cs == traffic.clients(json.loads(json.dumps(m)))
    assert len(cs) == a["clients"] == m["engine"]["slots"]
    n = a["cycle"]
    for i, c in enumerate(cs):
        assert c.start_s == pytest.approx(i * a["stagger_s"] / len(cs))
        assert len(c.cycle) == n
        assert sorted(x for x, _ in c.cycle) == \
            traffic._strata(m["lengths"]["isl"], n)
        assert sorted(y for _, y in c.cycle) == \
            traffic._strata(m["lengths"]["osl"], n)
        assert c.request(n + 3) == c.request(3) == c.cycle[3]
    assert len({c.cycle for c in cs}) == len(cs)
    assert Counter(x for x, _ in cs[0].cycle) == {256: 10, 512: 6, 1024: 4}


def test_closed_and_open_generators_refuse_each_others_mix():
    with pytest.raises(ValueError, match="closed"):
        traffic.open_loop(_mix("batch"), 45.0)
    with pytest.raises(ValueError, match="poisson"):
        traffic.clients(_mix("chat"))


def test_open_arrivals_send_the_schedule_in_order():
    """Polled at rising times, an open loop sends each request of the
    schedule once, by its due time, and with nothing in flight wakes at
    the next one; a finished request changes nothing."""
    m = _mix("chat")
    plan = traffic.open_loop(m, 45.0)
    arr = traffic.arrivals(m, 45.0)
    got = []
    for el in [0.0, 0.3, 2.0, 2.0, 10.0, 44.0, 60.0]:
        for s in arr.due(el):
            assert s.due_s <= el and s.client is None
            got.append(s)
        arr.finished(None)
        nxt = [a.due_s for a in plan if a.due_s > el]
        assert arr.wake(el) == (nxt[0] if nxt else None)
    assert [(s.due_s, s.isl, s.osl) for s in got] == \
        [(a.due_s, a.isl, a.osl) for a in plan]
    assert [s.key for s in got] == list(range(len(plan)))


def test_closed_arrivals_wait_for_each_client_and_stop_at_the_close():
    """A client sends once started and idle, its k-th prompt keyed by
    client and k; nothing is sent at or after the close, and then there
    is nothing to wake for."""
    m = _mix("batch")
    cs = traffic.clients(m)
    n = len(cs)
    arr = traffic.arrivals(m, 10.0)
    first = list(arr.due(cs[1].start_s))
    assert [s.client for s in first] == [0, 1]
    assert [(s.isl, s.osl) for s in first] == [cs[0].request(0),
                                               cs[1].request(0)]
    assert list(arr.due(cs[1].start_s)) == []       # both in flight
    assert arr.wake(cs[1].start_s) == cs[2].start_s
    arr.finished(0)
    again = list(arr.due(cs[2].start_s))
    assert [(s.client, s.key) for s in again] == [(0, n), (2, 2)]
    assert again[0].due_s == cs[2].start_s
    arr.finished(1)
    assert list(arr.due(10.0)) == [] and arr.wake(10.0) is None


def test_arrivals_refuse_an_unknown_kind():
    m = dict(_mix("chat"), arrivals={"kind": "bursty"})
    with pytest.raises(ValueError, match="bursty"):
        traffic.arrivals(m, 45.0)
