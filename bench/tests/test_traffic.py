"""The generator gives every seed the same work, in the mix's own order,
on other tokens: an open-loop window holds exactly one stratified block,
and a closed loop's first sends are one whole block."""
import json

import pytest

from bench.spec import ROOT
from bench.traffic import Traffic, _lognormal_levels

SEEDS = (3000000001, 2**31 + 17, 5)


def mix(name):
    return json.loads((ROOT / "bench/traffic" / f"{name}.json").read_text())


def window_items(spec, seed, seconds):
    items = Traffic(spec, seed, 1000, seconds).items()
    warm, out = float(spec["warmup_s"]), []
    for it in items:
        if it.at_s >= warm + seconds:
            return out
        if it.at_s >= warm:
            out.append(it)


@pytest.mark.parametrize("seconds", [51.0, 20.0])
def test_open_loop_window_is_one_block_for_every_seed(seconds):
    spec = mix("docqa")
    n = Traffic(spec, 1, 1000, seconds).block_size(seconds)
    sizes, orders, tokens = [], set(), set()
    for seed in SEEDS:
        win = window_items(spec, seed, seconds)
        assert len(win) == n and win[0].at_s == spec["warmup_s"]
        sizes.append(sorted((len(i.tokens), i.max_new) for i in win))
        orders.add(tuple((len(i.tokens), i.max_new, i.at_s) for i in win))
        tokens.add(tuple(win[0].tokens[:8]))
        docs = [i.doc for i in win]
        # each document opened in the window is asked 1 / new_share times
        assert {docs.count(d) for d in docs} == {4}
    assert sizes[0] == sizes[1] == sizes[2]
    assert len(orders) == 1 and len(tokens) == len(SEEDS)
    assert sorted(i.max_new for i in win) == sorted(
        _lognormal_levels(spec["output"], n))


def test_closed_loop_first_sends_are_one_block():
    spec = mix("longgen")
    firsts = []
    for seed in SEEDS:
        items = Traffic(spec, seed, 1000, 51.0).items()
        first = [next(items) for _ in range(spec["clients"])]
        firsts.append(sorted((len(i.tokens), i.max_new) for i in first))
    assert firsts[0] == firsts[1] == firsts[2]


@pytest.mark.parametrize("name", ["docqa", "longgen"])
def test_every_prompt_length_is_known_before_serving(name):
    spec = mix(name)
    t = Traffic(spec, 42, 1000, 51.0)
    items = t.items()
    seen = {len(next(items).tokens) for _ in range(200)}
    assert seen <= set(t.prompt_lengths())
