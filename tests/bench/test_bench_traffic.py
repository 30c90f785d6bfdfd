"""The traffic generator: seeded, clipped, and sessions built from the
tokens actually served."""
import numpy as np
import pytest

import _paths  # noqa: F401
from bench import cells, traffic

SPECS = {name: traffic.load(cells.TRAFFIC_DIR / f"{name}.json")
         for name in ("shortdoc", "longdoc", "sessions")}


def _offline(spec, seed, n):
    s = traffic.Offline(spec, seed, 1000)
    return [s.next() for _ in range(n)]


@pytest.mark.parametrize("name", ["shortdoc", "longdoc"])
def test_offline_same_seed_same_requests(name):
    a = _offline(SPECS[name], 2**33 + 1, 70)
    b = _offline(SPECS[name], 2**33 + 1, 70)
    c = _offline(SPECS[name], 2**33 + 2, 70)
    assert all(np.array_equal(x.prompt, y.prompt)
               and x.max_new_tokens == y.max_new_tokens for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name", ["shortdoc", "longdoc"])
def test_offline_lengths_in_clips_and_same_work_every_seed(name):
    spec = SPECS[name]
    block = spec["block"]
    runs = [_offline(spec, seed, block) for seed in (1, 2**40 + 3)]
    for reqs in runs:
        p = [len(r.prompt) for r in reqs]
        o = [r.max_new_tokens for r in reqs]
        assert min(p) >= spec["prompt_len"]["min"]
        assert max(p) <= spec["prompt_len"]["max"]
        assert min(o) >= spec["output_len"]["min"]
        assert max(o) <= spec["output_len"]["max"]
        assert all(r.greedy for r in reqs)
    # a whole block is the same multiset of sizes, in another order
    assert sorted(len(r.prompt) for r in runs[0]) == \
        sorted(len(r.prompt) for r in runs[1])
    assert [len(r.prompt) for r in runs[0]] != [len(r.prompt) for r in runs[1]]


def test_sessions_same_seed_same_schedule():
    spec = SPECS["sessions"]
    a = traffic.Sessions(spec, 7, 1000, 30.0)
    b = traffic.Sessions(spec, 7, 1000, 30.0)
    c = traffic.Sessions(spec, 8, 1000, 30.0)
    key = lambda p: [(s.start_s, s.system, s.replies, s.thinks, s.greedy,
                      [u.tolist() for u in s.users]) for s in p.sessions]
    assert key(a) == key(b)
    assert key(a) != key(c)
    assert all(s.start_s < 30.0 for s in a.sessions)
    # the rate sets the number of sessions: about rate x window
    assert 0.5 * 30 * spec["rate_per_s"] < len(a.sessions) < 2 * 30 * spec["rate_per_s"]


def test_sessions_lengths_in_clips_and_greedy_mix():
    spec = SPECS["sessions"]
    p = traffic.Sessions(spec, 11, 1000, 60.0)
    for s in p.sessions:
        assert len(s.users) <= spec["turns"]["max"]
        for u, r in zip(s.users, s.replies):
            assert spec["user_len"]["min"] <= len(u) <= spec["user_len"]["max"]
            assert spec["reply_len"]["min"] <= r <= spec["reply_len"]["max"]
        assert s.history == list(p.system[s.system])
    every = spec["greedy_every"]
    assert [s.greedy for s in p.sessions] == \
        [i % every == 0 for i in range(len(p.sessions))]


def test_sessions_chain_generated_tokens_and_stop_before_limit():
    spec = SPECS["sessions"]
    p = traffic.Sessions(spec, 5, 1000, 60.0)
    limit = spec["max_context"]
    for s in p.sessions[:40]:
        served = []
        while not s.done:
            req = p.request(s)
            assert len(req.prompt) + req.max_new_tokens <= limit
            if served:     # the previous turn's history, verbatim
                assert list(req.prompt[:len(served)]) == served
            reply = [(7 * len(served) + k) % 1000
                     for k in range(req.max_new_tokens)]
            think = p.finished(s, req.prompt, reply)
            assert think >= 0
            served = list(req.prompt) + reply
        assert s.history == served


def test_stratified_quantiles():
    q = traffic.quantiles({"dist": "uniform", "min": 10, "max": 19}, 10)
    assert q.tolist() == list(range(10, 20))
    g = traffic.quantiles({"dist": "gamma", "mean": 2.0, "cv": 2.0}, 200)
    assert abs(g.mean() - 2.0) < 0.3
    z = traffic.zipf_choice(np.random.default_rng(0), 8, 1.0, 64, 64)
    counts = np.bincount(z, minlength=8)
    assert counts.sum() == 64 and counts[0] == max(counts)
    assert all(counts[i] >= counts[i + 1] for i in range(7))


def test_a_window_with_no_session():
    spec = dict(SPECS["sessions"], rate_per_s=1e-6)
    assert traffic.Sessions(spec, 3, 1000, 1.0).sessions == []


def test_offline_shared_prefix_opens_every_prompt():
    spec = SPECS["shortdoc"]
    n = spec["shared_prefix"]["len"]
    a = _offline(spec, 2**35 + 1, 40)
    b = _offline(spec, 2**35 + 2, 40)
    head = a[0].prompt[:n]
    assert all(np.array_equal(r.prompt[:n], head) for r in a)
    assert not np.array_equal(b[0].prompt[:n], head)
    # the document after the template differs from request to request
    assert len({r.prompt[n:n + 8].tobytes() for r in a}) == len(a)
    assert min(len(r.prompt) for r in a) >= spec["prompt_len"]["min"]
    assert max(len(r.prompt) for r in a) <= spec["prompt_len"]["max"]


@pytest.mark.parametrize("name,expect", [("shortdoc", 512), ("longdoc", 3839),
                                         ("sessions", 2048)])
def test_max_context_is_what_a_request_can_fill(name, expect):
    assert traffic.max_context(SPECS[name]) == expect


def test_shared_prefix_must_leave_room(tmp_path):
    spec = dict(SPECS["shortdoc"], shared_prefix={"len": 128})
    path = tmp_path / "bad.json"
    path.write_text(__import__("json").dumps(spec))
    with pytest.raises(ValueError, match="shared_prefix"):
        traffic.load(path)
