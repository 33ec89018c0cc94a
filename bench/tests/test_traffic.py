"""The traffic generator: deterministic per seed, on its grid, and the same
amount of work for every seed."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["prefill-heavy", "decode-heavy"])
def test_same_seed_same_requests(name):
    m = mix(name)
    a = traffic.phase_items(m, 1000, 2**31 + 3, 2, 50, 10.0, 5.0)
    b = traffic.phase_items(m, 1000, 2**31 + 3, 2, 50, 10.0, 5.0)
    c = traffic.phase_items(m, 1000, 4, 2, 50, 10.0, 5.0)
    assert [(i.prompt, i.max_new_tokens, i.offset_s) for i in a] == \
        [(i.prompt, i.max_new_tokens, i.offset_s) for i in b]
    assert [i.prompt for i in a] != [i.prompt for i in c]


@pytest.mark.parametrize("name", ["prefill-heavy", "decode-heavy"])
def test_lengths_on_grid_and_same_multiset_per_seed(name):
    m = mix(name)
    runs = [traffic.phase_items(m, 1000, s, 2, 200, 20.0, 10.0) for s in (1, 2**33 + 1)]
    for items in runs:
        assert {len(i.prompt) for i in items} <= set(m["prompt"]["grid"])
        assert all(m["output"]["min"] <= i.max_new_tokens <= m["output"]["max"]
                   for i in items)
        assert all(0 <= t < 1000 for i in items for t in i.prompt)
    assert sorted(len(i.prompt) for i in runs[0]) == sorted(len(i.prompt) for i in runs[1])
    assert sorted(i.max_new_tokens for i in runs[0]) == \
        sorted(i.max_new_tokens for i in runs[1])


def test_open_loop_arrivals_span_the_phase():
    m = mix("prefill-heavy")
    items = traffic.phase_items(m, 1000, 7, 2, 120, 12.0, 10.0)
    t = np.array([i.offset_s for i in items])
    assert t[0] == 0.0 and np.all(np.diff(t) > 0) and t[-1] < 12.0
    other = traffic.phase_items(m, 1000, 8, 2, 120, 12.0, 10.0)
    gaps = lambda its: sorted(np.round(np.diff([i.offset_s for i in its] + [12.0]), 9))
    assert gaps(items) == pytest.approx(gaps(other))


def test_prompt_lengths_follow_the_mix():
    m = mix("prefill-heavy")
    q = traffic.quantiles(m["prompt"], 1001)
    assert q[500] == 384 and q.min() == 128 and q.max() == 2048


def test_closed_source_first_requests_are_staggered():
    m = mix("decode-heavy")
    first = traffic.first_outputs(m, 5)
    assert len(first) == m["clients"] and len(set(first.tolist())) > m["clients"] // 2
    src = traffic.ClosedSource(m, 100, 5)
    items = [src.next() for _ in range(300)]
    assert len({len(i.prompt) for i in items}) > 1
