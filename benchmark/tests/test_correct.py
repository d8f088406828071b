"""``correct`` comes out false when it should.

Two kinds of test, both on the CPU at a tiny size, past the harness's
look for a chip (``--rehearse``):

- the timed path broken underneath: a train step that returns its state
  unchanged, a train step fed half of its batch twice, a decode tick
  whose tokens are altered where they are produced;
- the control: the reference put in the program's place one precision
  below the configuration's (fp8 operands under bf16, both for the
  trainer and the server) is held to the same limits and fails.

The limits themselves were read on the chip at the cells' own sizes
(PERF.md section 2); these tests keep the mechanism honest.
"""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
TINY = os.path.join(HERE, "tiny.json")

from benchmark import checks  # noqa: E402
from benchmark import run as bench_run  # noqa: E402


def rehearse(capsys, workload, *extra):
    """Run a cell in this process; returns (result, notes by kind)."""
    code = bench_run.main(["--workload", workload, "--seed", "20260927",
                           "--seconds", "0.5", "--trace", "0",
                           "--rehearse", TINY, *extra])
    assert code == 3
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    return lines[-1], {l["note"]: l for l in lines[:-1]}


def stated(config: str, kind: str) -> dict:
    """What the configuration file states for the check of such cells."""
    with open(os.path.join(ROOT, "benchmark", "configs", config + ".json")) as f:
        return json.load(f)["correct"][kind]


def holds(notes, name):
    return notes["correct"]["numbers"][name]["holds"]


def test_sound_train_step_passes_and_control_fails(capsys):
    result, notes = rehearse(capsys, "gpt2s-train-1chip", "--control", "1")
    assert holds(notes, "pretrain.loss_gap")
    assert holds(notes, "pretrain.update_norm_gap")
    control = notes["control"]["numbers"]
    rules = stated("gpt2-small", "pretrain")
    assert notes["control"]["arithmetic"] == rules["control"]
    assert control["pretrain.grad_norm_gap"] > rules["limits"]["pretrain.grad_norm_gap"]


def test_a_step_that_returns_its_state_is_not_correct(capsys, monkeypatch):
    import mpit_tpu.train

    real = mpit_tpu.train.make_train_step

    def broken(*args, **kw):
        init_fn, step_fn, specs = real(*args, **{**kw, "donate": False})

        def stuck(state, batch):
            _, metrics = step_fn(state, batch)
            return state, metrics  # the optimizer's work is thrown away

        stuck._cache_size = step_fn._cache_size
        stuck.grad_sync_mode = step_fn.grad_sync_mode
        return init_fn, stuck, specs

    monkeypatch.setattr(mpit_tpu.train, "make_train_step", broken)
    result, notes = rehearse(capsys, "gpt2s-train-1chip")
    assert result["correct"] is False
    assert not holds(notes, "pretrain.update_norm_gap")
    assert notes["correct"]["numbers"]["pretrain.update_norm_gap"]["value"] == pytest.approx(1.0)


def test_a_step_that_leaves_out_half_the_batch_is_not_correct(capsys, monkeypatch):
    import mpit_tpu.train

    real = mpit_tpu.train.make_train_step

    def broken(*args, **kw):
        init_fn, step_fn, specs = real(*args, **kw)

        def halved(state, batch):
            import jax.numpy as jnp

            t = batch["tokens"]
            half = t.shape[0] // 2
            return step_fn(state, {"tokens": jnp.concatenate([t[:half], t[:half]])})

        halved._cache_size = step_fn._cache_size
        halved.grad_sync_mode = step_fn.grad_sync_mode
        return init_fn, halved, specs

    monkeypatch.setattr(mpit_tpu.train, "make_train_step", broken)
    result, notes = rehearse(capsys, "gpt2s-train-1chip")
    assert result["correct"] is False
    assert not holds(notes, "pretrain.loss_gap")


@pytest.mark.parametrize("workload", ["gpt2l-serve-offline-decode",
                                      "gpt2l-serve-prefill-steady"])
def test_sound_serving_passes(capsys, workload):
    result, notes = rehearse(capsys, workload)
    assert result["correct"] is True


@pytest.mark.parametrize("workload", ["gpt2l-serve-offline-decode",
                                      "gpt2l-serve-prefill-steady"])
def test_a_token_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch, workload):
    from mpit_tpu.serve import Engine

    real = Engine.decode

    def altered(self, *args, **kw):
        toks = np.asarray(real(self, *args, **kw))
        return (toks + 1) % self.cfg.vocab_size

    monkeypatch.setattr(Engine, "decode", altered)
    result, notes = rehearse(capsys, workload)
    assert result["correct"] is False
    assert not holds(notes, "requests.token_gap_max")


def test_lower_precision_control_fails_the_serving_limits():
    """The reference computed one precision down (the configuration's ``control``),
    judged like a served model at a size a test can hold (12 layers of
    256, 16384 tokens of vocabulary, 512 judged positions): the tokens it
    puts first lie further below the float32 reference's best than both
    limits allow. At the cell's own size the chip read 0.124 and more
    against the limit of 0.06 (PERF.md section 2)."""
    import types

    with open(os.path.join(ROOT, "benchmark", "configs", "gpt2-large.json")) as f:
        config = {**json.load(f), "n_layer": 12, "n_embd": 256, "n_head": 4,
                  "n_inner": 1024, "vocab_size": 16384, "n_positions": 128}
    ctx = {"config": config, "seed": 7}
    rng = np.random.default_rng(7)
    sample = [types.SimpleNamespace(
        rid=i, prompt=rng.integers(0, 16384, size=64).tolist(),
        tokens=rng.integers(0, 16384, size=64).tolist()) for i in range(8)]
    rules = stated("gpt2-large", "requests")
    control = checks.requests_numbers(
        checks.token_gaps(ctx, sample, low=rules["control"]))
    assert control["requests.token_gap_max"] > rules["limits"]["requests.token_gap_max"]
    assert control["requests.token_gap_mean"] > rules["limits"]["requests.token_gap_mean"]


def test_a_configuration_without_limits_is_refused():
    """The limits are the configuration's to state: a cell whose
    configuration states none for its kind does not run to a verdict."""
    ctx = {"config": {"correct": {"pretrain": {}}}, "cell": {"config": "x"}}
    with pytest.raises(SystemExit):
        checks.rules(ctx, "requests")
    with pytest.raises(SystemExit):
        checks.rules(ctx, "pretrain")
