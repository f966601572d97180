"""The output check, end to end on the host CPU at small sizes.

Each test skips only the harness's look for a chip and drives the rest
of a run through ``bench.run.main``: a sound program comes out
``correct``; with the timed path broken underneath — a step that
returns its state unchanged, half of each batch left out, the D2D
exchange left out, a served token altered where it is produced — it
does not. The control tests put the plain reference in the program's
place, stored and computed in bfloat16 for training and with float8
matmul operands for serving, and show it depart from the reference.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, harness, run, tthf_reference
from bench.tests import small


def drive(monkeypatch, capsys, cell, seconds=2.0, seed=2**33 + 5):
    monkeypatch.setattr(harness, "load_cell", lambda spec, name: cell)
    monkeypatch.setattr(harness, "require_devices",
                        lambda chips: jax.devices()[:chips])
    assert run.main(["--workload", cell.name, "--seed", str(seed),
                     "--seconds", str(seconds), "--trace", "0"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


# -- training ---------------------------------------------------------------

def frozen(monkeypatch):
    import repro.train.trainer as trainer
    real = trainer.make_tthf_train_step

    def make(*a, **kw):
        step, net = real(*a, **kw)

        def same(params, batch, *rest):
            _, loss = step(jax.tree.map(jnp.copy, params), batch, *rest)
            return params, loss
        return same, net
    monkeypatch.setattr(trainer, "make_tthf_train_step", make)


def half_batch(monkeypatch):
    from repro.models.registry import ModelApi
    real = ModelApi.loss

    def loss(self, params, batch, **kw):
        T = batch["tokens"].shape[-1] // 2
        return real(self, params, {k: v[..., :T] for k, v in batch.items()},
                    **kw)
    monkeypatch.setattr(ModelApi, "loss", loss)


def no_exchange(monkeypatch):
    from repro.core.mixing import MixingPlan
    monkeypatch.setattr(MixingPlan, "apply_pytree",
                        lambda self, params, refresh=None: params)


def test_training_sound_run_is_correct(monkeypatch, capsys):
    line = drive(monkeypatch, capsys, small.train_cell())
    assert line["correct"], line["checks"]
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", [frozen, half_batch, no_exchange])
def test_training_fault_is_caught(monkeypatch, capsys, fault):
    fault(monkeypatch)
    line = drive(monkeypatch, capsys, small.train_cell())
    assert not line["correct"], line["checks"]


def test_training_control_reads_beyond_limits():
    cell = small.train_cell()
    ref = harness.reference_module(cell.config)
    m, job = cell.config["model"], cell.traffic
    p0 = jax.jit(lambda k: ref.init(m, k))(jax.random.PRNGKey(3))
    want = tthf_reference.run(ref, m, job, 3, p0, job["checked_steps"])
    low = tthf_reference.run(ref, m, job, 3, p0, job["checked_steps"],
                             dtype=jnp.bfloat16, precision=None)
    names = [str(i) for i in range(len(want["d1"]))]
    values = compare.training(low, want, names)["values"]
    limits = cell.limits["limits"]
    assert any(values[k] > limits[k] for k in limits), values


# -- serving ----------------------------------------------------------------

def altered_token(monkeypatch):
    import repro.serving.scheduler as scheduler
    real = scheduler.sample_tokens

    def sample(logits, **kw):
        tok = real(logits, **kw)
        return (tok + 1) % logits.shape[-1]
    monkeypatch.setattr(scheduler, "sample_tokens", sample)


@pytest.mark.parametrize("make_cell", [small.chat_cell, small.code_cell])
def test_serving_sound_run_is_correct(monkeypatch, capsys, make_cell):
    line = drive(monkeypatch, capsys, make_cell())
    assert line["correct"], line["checks"]


@pytest.mark.parametrize("make_cell", [small.chat_cell, small.code_cell])
def test_serving_altered_token_is_caught(monkeypatch, capsys, make_cell):
    altered_token(monkeypatch)
    line = drive(monkeypatch, capsys, make_cell())
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("make_cell", [small.chat_cell, small.code_cell])
def test_serving_control_departs(monkeypatch, capsys, make_cell):
    """The control's greedy tokens, judged by the reference at every
    position of random prompts of the mix's longest length, lie at
    least three times as far below the reference's best as the served
    tokens of a sound run at the same size. (At the cells' own sizes on
    the chip the control reads beyond the limits: PERF.md.)"""
    cell = make_cell()
    served = drive(monkeypatch, capsys, cell)["checks"]["served_logit_gap"]
    ref = harness.reference_module(cell.config)
    m = cell.config["model"]
    T = cell.traffic["scheduler"]["max_total"]
    params = jax.jit(lambda k: ref.init(m, k))(jax.random.PRNGKey(4))
    toks = np.random.default_rng(4).integers(1, m["vocab_size"], (1, T))
    rows = np.asarray(ref.logits(params, m, toks))[0]
    low = np.asarray(ref.logits(params, m, toks, precision="fp8"))[0]
    gap = compare.served_gap(rows, low.argmax(-1))
    assert gap > max(3 * served["value"], 1e-2), (gap, served)
