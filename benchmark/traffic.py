"""The one generator of traffic: reads a mix's parameters, makes its inputs.

A traffic mix is a data file under ``benchmark/traffic/``; nothing here
knows a mix by name. Two kinds:

- ``"pretrain"``: token batches ``[rows, seq_len + 1]`` for a training
  step, every row different, drawn per step from ``(seed, step)``.
- ``"requests"``: generation requests with their due times. The length
  arithmetic is copied from ``mpit_tpu/serve/loadgen.py`` (ranges of
  prompt and output lengths, tokens uniform over the vocabulary) with
  one change: the schedule belongs to the mix and not to the seed. Due
  times come from the mix's ``process``, a module of that name under
  ``benchmark/arrivals/`` (a new process is a new file there). Lengths
  are drawn from the mix's ``schedule_seed``: independently, or, where
  the mix gives a ``balance_block``, as a fixed grid of the
  distribution's quantiles in an order in which every ``balance_block``
  consecutive requests hold one value from each stratum. ``--seed``
  draws what the tokens are, never how many there are or when they are
  due: every run of a cell offers the same work, and what differs
  between runs is the system.
"""

from __future__ import annotations

import dataclasses
import importlib
import math

import numpy as np


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


# -- pretrain ---------------------------------------------------------------


def token_cdf(vocab: int, dist: dict) -> np.ndarray:
    """Cumulative unigram distribution over token ids."""
    if dist["kind"] == "uniform":
        p = np.ones(vocab)
    elif dist["kind"] == "zipf":
        p = 1.0 / np.arange(1, vocab + 1) ** float(dist["exponent"])
    else:
        raise ValueError(f"unknown token distribution {dist['kind']!r}")
    return np.cumsum(p / p.sum())


def train_batch(mix: dict, vocab: int, rows: int, seed: int, step: int,
                cdf: np.ndarray | None = None) -> np.ndarray:
    """Tokens of one step: ``[rows, seq_len + 1]`` int32."""
    if cdf is None:
        cdf = token_cdf(vocab, mix["tokens"])
    u = _rng(seed, 1, step).random((rows, mix["seq_len"] + 1))
    return np.minimum(np.searchsorted(cdf, u), vocab - 1).astype(np.int32)


# -- requests ---------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Arrival:
    rid: int
    due_s: float  # seconds from the start of the stream
    prompt: list
    max_new_tokens: int


def _quantile_lengths(spec: dict, q: np.ndarray) -> np.ndarray:
    """The lengths at quantiles ``q`` of ``spec``'s distribution."""
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "uniform":
        x = lo + q * (hi + 1 - lo)
    elif spec["dist"] == "loguniform":
        x = np.exp(math.log(lo) + q * (math.log(hi + 1) - math.log(lo)))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(int)


def balanced(values: np.ndarray, block: int, rng) -> np.ndarray:
    """``values`` in an order in which every ``block`` consecutive ones
    hold one value from each of ``block`` strata of the sorted values."""
    v = np.sort(values)
    per = -(-len(v) // block)
    strata = [rng.permutation(v[i * per:(i + 1) * per]) for i in range(block)]
    out = []
    for b in range(per):
        out.extend(rng.permutation([s[b] for s in strata if b < len(s)]))
    return np.asarray(out)


def lengths(mix: dict, spec: dict, n: int, rng) -> np.ndarray:
    block = mix.get("balance_block")
    if block:
        return balanced(_quantile_lengths(spec, (np.arange(n) + 0.5) / n), block, rng)
    return _quantile_lengths(spec, rng.random(n))


def open_loop_count(mix: dict, seconds: float) -> int:
    """Requests enough for the lead-in, the window and the answer cap."""
    span = mix["lead_in_s"] + seconds + mix["answer_cap_s"]
    return max(1, round(mix["rate_per_s"] * span))


def process_of(mix: dict):
    """The mix's arrival process: ``benchmark/arrivals/<process>.py``."""
    return importlib.import_module("benchmark.arrivals." + mix["process"])


def arrivals(mix: dict, vocab: int, seed: int, seconds: float) -> list[Arrival]:
    """The request stream of one run, sorted by due time."""
    process = process_of(mix)
    n = process.count(mix, seconds)
    order = _rng(mix["schedule_seed"], 2)
    prompts = lengths(mix, mix["prompt_len"], n, order)
    outputs = lengths(mix, mix["output_len"], n, order)
    due = process.due_times(mix, n, order)
    values = _rng(seed, 3)
    return [
        Arrival(i, float(due[i]),
                values.integers(0, vocab, size=int(prompts[i])).tolist(),
                int(outputs[i]))
        for i in range(n)
    ]
