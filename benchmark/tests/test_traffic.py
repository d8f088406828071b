"""The traffic generator: the same seed gives the same inputs, the seed
never changes how much work a serving mix offers, and every committed mix
generates (large seeds included)."""

import glob
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import traffic as tg  # noqa: E402

MIXES = {os.path.basename(p)[:-5]: json.load(open(p))
         for p in sorted(glob.glob(os.path.join(ROOT, "benchmark", "traffic", "*.json")))}
BIG = 2**31 + 12345


@pytest.mark.parametrize("name", [n for n, m in MIXES.items() if m["kind"] == "pretrain"])
def test_pretrain_batches(name):
    mix = MIXES[name]
    a = tg.train_batch(mix, 50257, 16, BIG, 0)
    assert a.shape == (16, mix["seq_len"] + 1) and a.dtype == np.int32
    assert a.min() >= 0 and a.max() < 50257
    assert np.array_equal(a, tg.train_batch(mix, 50257, 16, BIG, 0))
    assert not np.array_equal(a, tg.train_batch(mix, 50257, 16, BIG, 1))
    assert not np.array_equal(a, tg.train_batch(mix, 50257, 16, BIG + 1, 0))
    assert len({row.tobytes() for row in a}) == 16  # every row differs


@pytest.mark.parametrize("name", [n for n, m in MIXES.items() if m["kind"] == "requests"])
def test_request_streams(name):
    mix = MIXES[name]
    a = tg.arrivals(mix, 50257, BIG, 40)
    b = tg.arrivals(mix, 50257, BIG, 40)
    c = tg.arrivals(mix, 50257, 7, 40)
    assert [(x.due_s, x.prompt, x.max_new_tokens) for x in a] == [
        (x.due_s, x.prompt, x.max_new_tokens) for x in b]
    # Another seed: other tokens, the same lengths at the same times.
    assert [x.prompt for x in a] != [x.prompt for x in c]
    assert [(x.due_s, len(x.prompt), x.max_new_tokens) for x in a] == [
        (x.due_s, len(x.prompt), x.max_new_tokens) for x in c]
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    assert all(lo <= len(x.prompt) <= hi for x in a)
    assert all(mix["output_len"]["min"] <= x.max_new_tokens <= mix["output_len"]["max"]
               for x in a)
    # No request outgrows a slot of 1024 positions.
    assert max(len(x.prompt) + x.max_new_tokens for x in a) <= 1024
    assert [x.due_s for x in a] == sorted(x.due_s for x in a)
    if tg.process_of(mix).OPEN_LOOP:
        span = mix["lead_in_s"] + 40 + mix["answer_cap_s"]
        assert a[-1].due_s == pytest.approx(span, rel=0.05)  # the mean rate holds


def test_blocks_are_balanced():
    rng = np.random.default_rng(0)
    out = tg.balanced(np.arange(160), 16, rng)
    assert sorted(out) == list(range(160))
    for b in range(10):
        block = out[16 * b:16 * (b + 1)]
        assert sorted(v // 10 for v in block) == list(range(16))  # one per stratum


def test_poisson_process_is_not_smoothed():
    """The same mix on ``poisson`` with no ``balance_block``: independent
    gaps and lengths from ``schedule_seed`` (so every seed still gets the
    same schedule), with the clusters that ``stratified`` takes away."""
    base = MIXES["prefill-steady"]
    assert base["process"] == "stratified" and base["balance_block"] == 8
    mix = {k: v for k, v in base.items() if k != "balance_block"}
    mix["process"] = "poisson"
    a = tg.arrivals(mix, 50257, 1, 400)
    b = tg.arrivals(mix, 50257, BIG, 400)
    assert [(x.due_s, len(x.prompt)) for x in a] == [(x.due_s, len(x.prompt)) for x in b]
    smooth = tg.arrivals(base, 50257, 1, 400)
    per8 = lambda s: np.diff([x.due_s for x in s])[:8 * (len(s) // 8 - 1)].reshape(-1, 8).sum(1)
    # Eight stratified gaps, and the eight prompts due in them, add up to
    # much the same every time; eight independent ones do not.
    assert np.std(per8(smooth)) < 0.7 * np.std(per8(a))
    tokens8 = lambda s: np.asarray([len(x.prompt) for x in s])[
        :8 * (len(s) // 8)].reshape(-1, 8).sum(1)
    assert np.std(tokens8(smooth)) < 0.25 * np.std(tokens8(a))
    assert np.mean(np.diff([x.due_s for x in a])) == pytest.approx(1 / mix["rate_per_s"], rel=0.1)
