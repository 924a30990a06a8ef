"""The request generator: a pure function of the seed that honours
every clip and the prompt + output bound, and gives every seed the same
multiset of sizes and gaps."""
import numpy as np
import pytest

from benchmark import harness, traffic


@pytest.fixture(scope="module")
def mix():
    return harness.Cell("lm2048.serve-chat-backlog").traffic


OPEN_LOOP = {"kind": "poisson", "rate": 0.86}


def _make(mix, seed, arrival=OPEN_LOOP, horizon=35.0):
    return traffic.make_requests(mix, arrival, 32768, seed, horizon)


def test_pure_function_of_seed(mix):
    a, b = _make(mix, 7), _make(mix, 7)
    assert [(r.due, r.prompt, r.max_tokens) for r in a] == \
        [(r.due, r.prompt, r.max_tokens) for r in b]
    c = _make(mix, 8)
    assert [r.prompt for r in a] != [r.prompt for r in c]


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 11, 2**40 + 3])
def test_clips_and_bound(mix, seed):
    reqs = _make(mix, seed)
    p, o = mix["prompt"], mix["output"]
    for r in reqs:
        assert p["lo"] <= len(r.prompt) <= p["hi"]
        assert 1 <= r.max_tokens <= o["hi"]
        assert len(r.prompt) + r.max_tokens <= mix["limit"]
        assert min(r.prompt) >= 0 and max(r.prompt) < 32768
    assert all(r.max_tokens >= o["lo"] for r in reqs
               if len(r.prompt) + o["lo"] <= mix["limit"])


def test_every_seed_and_every_block_holds_the_same_work(mix):
    size = mix["block"]
    a, b = _make(mix, 3), _make(mix, 2**33 + 5)
    assert len(a) == len(b) >= 3 * size

    def shape(reqs):
        return [(r.due, len(r.prompt), r.max_tokens, r.shared)
                for r in reqs]
    # the seed changes the token ids and nothing else
    assert shape(a) == shape(b)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    # every block is the mix's block, in the mix's order
    prompt, output, shared, gaps = traffic.block(mix)
    for k in range(0, len(a), size):
        assert [len(r.prompt) for r in a[k:k + size]] == list(prompt)
        assert [r.max_tokens for r in a[k:k + size]] == list(output)
    dues = np.diff([0.0] + [r.due for r in a])
    np.testing.assert_allclose(dues[:size],
                               gaps / OPEN_LOOP["rate"])
    assert a[size - 1].due == pytest.approx(
        size / OPEN_LOOP["rate"])


def test_shared_prefixes(mix):
    reqs = _make(mix, 5)
    shared = [r for r in reqs if r.shared]
    assert len(shared) / len(reqs) == mix["share"]
    heads = {tuple(r.prompt[:mix["prefix_len"]]) for r in shared}
    assert 1 < len(heads) <= mix["prefixes"]
    assert all(len(r.prompt) > mix["prefix_len"] for r in shared)


def test_lengths_follow_the_mix(mix):
    prompt, output, shared, gaps = traffic.block(mix)
    own = prompt - np.where(shared, mix["prefix_len"], 0)
    assert 0.85 * mix["prompt"]["median"] < np.median(own) \
        < 1.15 * mix["prompt"]["median"]
    assert 0.85 * mix["output"]["median"] < np.median(output) \
        < 1.15 * mix["output"]["median"]
    assert shared.sum() == round(mix["share"] * mix["block"])
    assert gaps.sum() == pytest.approx(mix["block"])
    assert prompt.max() <= mix["prompt"]["hi"]
    assert (prompt + output).max() <= mix["limit"]


def test_backlog_is_all_due_at_zero(mix):
    reqs = _make(mix, 2, {"kind": "backlog", "requests_per_s": 4}, 10.0)
    assert len(reqs) == 48 and all(r.due == 0.0 for r in reqs)


def test_poisson_rate_and_cover(mix):
    reqs = _make(mix, 9, {"kind": "poisson", "rate": 10.0}, 100.0)
    assert reqs[-1].due > 100.0
    assert [r.due for r in reqs] == sorted(r.due for r in reqs)
    assert len(reqs) / reqs[-1].due == pytest.approx(10.0)
