"""The traffic generator: the seed orders the work, it does not size it."""

import json
import os

import numpy as np
import pytest

import traffic as tg

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


BACKLOG = _load("backlog-short-in-long-out")
OPENLOOP = _load("openloop-long-in-short-out")
VOCAB = 50257


def _multiset(reqs):
    return sorted((len(r.prompt) - r.aged_tokens, r.new_tokens + r.aged_tokens)
                  for r in reqs)


def test_backlog_same_multiset_for_two_seeds_in_another_order():
    a = tg.Backlog(BACKLOG, VOCAB, 7).next_generation()
    b = tg.Backlog(BACKLOG, VOCAB, 3000000019).next_generation()
    assert _multiset(a) == _multiset(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    # and every later generation is the same multiset again
    src = tg.Backlog(BACKLOG, VOCAB, 7)
    first, second = src.next_generation(), src.next_generation()
    assert _multiset(first) == _multiset(second)


def test_backlog_first_generation_is_pre_aged_and_stratified():
    src = tg.Backlog(BACKLOG, VOCAB, 11)
    first, second = src.next_generation(), src.next_generation()
    n = BACKLOG["generation"]
    assert len(first) == n
    shares = sorted(r.aged_tokens / (r.new_tokens + r.aged_tokens)
                    for r in first)
    # one request in each stratum of width 1/n (floor on whole tokens)
    for i, s in enumerate(shares):
        assert i / n - 0.01 <= s < (i + 1) / n
    assert all(r.new_tokens >= 1 for r in first)
    assert all(r.aged_tokens == 0 for r in second)
    # aged output is delivered as prompt tokens
    assert all(len(r.prompt) >= r.aged_tokens for r in first)


@pytest.mark.parametrize("traffic", [BACKLOG, OPENLOOP])
def test_lengths_stay_inside_the_stated_ranges_and_the_context(traffic):
    pairs = tg.length_multiset(traffic, 120)
    for p, o in pairs:
        assert traffic["prompt"]["lo"] <= p <= traffic["prompt"]["hi"]
        assert traffic["output"]["lo"] <= o <= traffic["output"]["hi"]
        assert p + o <= traffic["max_total_len"] <= \
            traffic["page_size"] * traffic["pages_per_seq"]
        assert p <= traffic["max_prompt_len"]


def test_openloop_exact_count_same_multiset_and_sorted_due_times():
    seconds = 30.0
    a = tg.openloop_schedule(OPENLOOP, VOCAB, 1, seconds)
    b = tg.openloop_schedule(OPENLOOP, VOCAB, 2147483659, seconds)
    rate, ramp = OPENLOOP["rate_per_s"], OPENLOOP["ramp_seconds"]
    for plan in (a, b):
        measured = [r for r in plan if r.measured]
        warm = [r for r in plan if not r.measured]
        assert len(measured) == round(rate * seconds)
        assert len(warm) == round(rate * ramp)
        assert all(0 <= r.due_s < ramp for r in warm)
        assert all(ramp <= r.due_s < ramp + seconds for r in measured)
        dues = [r.due_s for r in plan]
        assert dues == sorted(dues)
    assert _multiset([r for r in a if r.measured]) == \
        _multiset([r for r in b if r.measured])
    assert [r.due_s for r in a] != [r.due_s for r in b]
    # the same inter-arrival gaps too, in another order (a request falls
    # due in the middle of its gap, so the gaps are read back in pairs)
    for plan in (a, b):
        dues = [r.due_s for r in plan if r.measured]
        assert dues[0] - ramp < 1.0 and ramp + seconds - dues[-1] < 1.0
    gaps = sorted(tg.arrival_gaps(round(rate * seconds), seconds))
    assert sum(gaps) == pytest.approx(seconds)
    assert gaps[0] < 0.01 < 0.2 < gaps[-1]           # exponential: they bunch


def test_stratified_order_uses_every_index_once_and_spreads_them():
    rng = tg.rng_for(3)
    order = tg.stratified_order(rng, 120, 8)
    assert sorted(order) == list(range(120))
    for b in range(15):                 # every block spans the distribution
        block = order[8 * b: 8 * b + 8]
        assert sorted(i // 15 for i in block) == list(range(8))
    assert order != tg.stratified_order(tg.rng_for(4), 120, 8)
    assert sorted(tg.stratified_order(rng, 10, 4)) == list(range(10))


def test_openloop_median_prompt_is_the_stated_one():
    prompts = tg.quantile_values(OPENLOOP["prompt"], 120)
    assert abs(float(np.median(prompts)) - OPENLOOP["prompt"]["median"]) <= 4


def test_tokens_are_zipf_inside_the_unpadded_vocabulary():
    toks = tg.zipf_tokens(tg.rng_for(5), VOCAB, 200000)
    assert toks.min() >= 0 and toks.max() < VOCAB
    counts = np.bincount(toks, minlength=VOCAB)
    assert counts[0] > counts[9] > counts[99] > 0     # p ~ 1 / rank
    assert np.array_equal(toks, tg.zipf_tokens(tg.rng_for(5), VOCAB, 200000))


def test_train_batches_targets_are_tokens_rolled():
    tr = {"batch_pool": 2, "seq": 16}
    pool = tg.train_batches(tr, 100, 3000000019, 4)
    assert len(pool) == 2 and pool[0][0].shape == (4, 16)
    assert np.array_equal(pool[0][1], np.roll(pool[0][0], -1, axis=1))
    assert not np.array_equal(pool[0][0], pool[1][0])
