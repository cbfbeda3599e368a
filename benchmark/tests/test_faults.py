"""A run with the timed path broken underneath reads `correct` false:
once for each fault a cell can have. The harness's look for a chip is
steered (the `tiny` fixture); everything else is a whole run."""

import pytest

from benchmark import run
from benchmark.tests.conftest import last_line


def _run(capsys, cell):
    # the seed on which `test_harness` sees the sound run come out correct
    assert run.main(["--workload", cell, "--seed", str(2**31 + 7),
                     "--seconds", "1.0", "--trace", "0"]) == 0
    return last_line(capsys)


def state_unchanged(monkeypatch):
    """The step reports a loss and returns its state as it was."""
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    def frozen(self, inputs, labels, fmasks=None, lmasks=None,
               carries=None):
        loss, _ = self._loss_fn(self.params, self.states, inputs, labels,
                                None)
        self.iteration += 1
        self._score = loss
        return loss, None

    monkeypatch.setattr(ComputationGraph, "_train_step", frozen)


def half_batch(monkeypatch):
    """Half of the batch left out, the mean taken over the rest."""
    from deeplearning4j_tpu.engine.step_program import StepProgram

    whole = StepProgram.run

    def half(self, x, y, fm=None, lm=None):
        n = x.shape[0] // 2
        return whole(self, x[:n], y[:n], fm, lm)

    monkeypatch.setattr(StepProgram, "run", half)


@pytest.mark.parametrize("fault", [state_unchanged, half_batch])
def test_broken_training_step_is_not_correct(tiny, monkeypatch, capsys,
                                             fault):
    fault(monkeypatch)
    res = _run(capsys, "tiny-train")
    assert res["correct"] is False, res["compared"]
    over = [k for k, (v, lim) in res["compared"].items() if v > lim]
    assert "grad_norm_gap" in over or "change_norm_gap" in over


def test_token_altered_where_it_is_produced_is_not_correct(
        tiny, monkeypatch, capsys):
    from deeplearning4j_tpu.engine.decode_program import DecodeProgram

    sound = DecodeProgram.step

    def altered(self, *args):
        kv, nxt, ok = sound(self, *args)
        return kv, nxt.at[0].set((nxt[0] + 1) % self.model.vocab_size), ok

    monkeypatch.setattr(DecodeProgram, "step", altered)
    res = _run(capsys, "tiny-serve")
    assert res["correct"] is False, res["compared"]
    value, limit = res["compared"]["served_logit_gap"]
    assert value > 10 * limit
