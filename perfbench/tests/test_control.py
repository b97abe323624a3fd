"""The comparison that decides ``correct`` has been shown to fail:

* the lower-precision control, put in the program's place, comes out
  not correct (training: the reference with fp8 matmul operands;
  serving: the program's own int8 KV cache);
* a run driven through the harness (the look for a chip skipped, tiny
  presets) with the timed path broken underneath comes out
  ``correct: false``.

Small sizes, so that a test run can hold them; the same controls were
read on the chip at the cells' own sizes (PERF.md, section 2).
"""
import importlib

import numpy as np
import pytest

from perfbench import harness, rehearse, serving
from perfbench.generators import train_job

SEED = 2 ** 31 + 4242


def test_sound_tiny_training_run_is_correct():
    cell = rehearse.tiny_cell("bert_base.pretrain512")
    assert rehearse.run_tiny(cell, SEED, 1.0)["correct"] is True


def test_training_control_fp8_reference_fails_the_comparison():
    import jax

    cell = rehearse.tiny_cell("bert_base.pretrain512")
    cfg, job = cell.config, cell.traffic
    ref = importlib.import_module("perfbench.reference." + cfg["family"])
    batches = ref.make_batches(cfg, job, SEED, job["check_steps"],
                               job["per_chip_batch"])
    w0 = ref.make_weights(cfg, SEED, jax.devices()[0])
    want = ref.follow(cfg, job, w0, batches, job["optimizer"], 2)
    control = ref.follow(cfg, job, w0, batches, job["optimizer"], 2,
                         lower=True)
    checks = harness.Checks()
    train_job.compare_with_reference(checks, job["limits"], control, want)
    assert not checks.ok
    failed = [r[0] for r in checks.rows if not r[-1]]
    assert any(n.startswith("first_grad_norm") for n in failed)


def test_training_step_that_drops_half_the_batch_is_not_correct(
        monkeypatch):
    cell = rehearse.tiny_cell("bert_base.pretrain512")
    fam = cell.family()

    def half_blind(self, batch):
        batch = list(batch)
        mask = batch[4].copy()
        mask[: mask.shape[0] // 2] = 0.0     # leaves out part of the batch
        batch[4] = mask
        return self.step(*[self._nd(a, dtype=str(a.dtype))
                           for a in batch])._data

    monkeypatch.setattr(fam.Trainer, "__call__", half_blind)
    result = rehearse.run_tiny(cell, SEED, 1.0)
    assert result["correct"] is False


def test_training_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch):
    cell = rehearse.tiny_cell("bert_base.pretrain512")
    fam = cell.family()
    real = fam.Trainer.change_norms

    def frozen(self, before):
        # what a step that returned its state unchanged would read:
        # the weights it was given
        return {n: 0.0 for n in real(self, before)}

    monkeypatch.setattr(fam.Trainer, "change_norms", frozen)
    result = rehearse.run_tiny(cell, SEED, 1.0)
    assert result["correct"] is False


def test_training_window_whose_loss_is_no_number_is_not_correct(
        monkeypatch):
    """The window's losses are reported and not held to a limit (a
    loss that has to fall has no reference); one that is no number
    still fails the run."""
    cell = rehearse.tiny_cell("bert_base.pretrain512")
    fam = cell.family()
    real = fam.Trainer.__call__
    calls = []

    def overflowing(self, batch):
        calls.append(1)
        loss = real(self, batch)
        # past the followed steps and the warm-up: inside the window
        return loss if len(calls) <= 8 else loss * float("nan")

    monkeypatch.setattr(fam.Trainer, "__call__", overflowing)
    result = rehearse.run_tiny(cell, SEED, 1.0)
    assert result["checks"]["nonfinite_losses_in_window"][0] > 0
    assert result["correct"] is False


def test_sound_tiny_serving_run_is_correct():
    cell = rehearse.tiny_cell("mistral_7b.chat")
    assert rehearse.run_tiny(cell, SEED, 2.0)["correct"] is True


def test_serving_with_a_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    cell = rehearse.tiny_cell("mistral_7b.chat")
    fam = cell.family()
    real_step = fam.Served.step

    def flipping(self):
        out = real_step(self)
        for req in self.server._slot_req:
            if req is not None and req.temperature == 0.0 \
                    and len(req.output_tokens) == 2:
                req.output_tokens[-1] = \
                    (req.output_tokens[-1] + 1) % self.cfg["vocab_size"]
        return out

    monkeypatch.setattr(fam.Served, "step", flipping)
    result = rehearse.run_tiny(cell, SEED, 2.0)
    assert result["correct"] is False


def _gaps(cell, control, seed=SEED):
    """Widest served-token gap of a short closed run at the tiny size:
    greedy requests through the server, then the reference."""
    import jax

    cfg, traffic = cell.config, cell.traffic
    fam = cell.family()
    served = fam.build(cfg, traffic["server"], seed, jax.devices()[:1],
                       control=control)
    drv = serving.Driver(served, cfg, traffic, seed,
                         harness.Tracer(False, "", harness.Phases()))
    for n in (20, 28, 12, 30):
        drv.submit(n, 24, False)
    drv.drain()
    checks = harness.Checks()
    out = serving.check_outputs(checks, cfg, traffic, seed, served,
                                drv.done, jax.devices()[:1])
    return out, checks


def test_serving_control_fp8_reference_fails_the_comparison():
    """The reference computed in fp8, put in the program's place on the
    sound run's own prompts and tokens."""
    import jax

    cell = rehearse.tiny_cell("mistral_7b.chat")
    cfg, traffic = cell.config, cell.traffic
    fam = cell.family()
    served = fam.build(cfg, traffic["server"], SEED, jax.devices()[:1])
    drv = serving.Driver(served, cfg, traffic, SEED,
                         harness.Tracer(False, "", harness.Phases()))
    for n in (20, 28, 12, 30):
        drv.submit(n, 24, False)
    drv.drain()
    seqs = [served.tokens(t.req) for t in drv.done]
    served.free()
    ref = importlib.import_module("perfbench.reference." + cfg["family"])
    sound = np.concatenate(ref.served_token_gaps(cfg, SEED, seqs))
    control = np.concatenate(ref.served_token_gaps(cfg, SEED, seqs,
                                                   control=True))
    limit = traffic["limits"]["mean_logit_gap"]
    assert sound.mean() <= limit
    assert control.mean() > 3 * max(float(sound.mean()), limit)


def test_serving_control_int8_kv_cache_fails_the_comparison():
    cell = rehearse.tiny_cell("mistral_7b.chat")
    sound, checks = _gaps(cell, control=False)
    assert checks.ok and sound["widest_gap"] <= 1e-4
    control, checks = _gaps(cell, control=True)
    assert not checks.ok
    assert control["widest_gap"] > 3 * max(sound["widest_gap"], 1e-4)
