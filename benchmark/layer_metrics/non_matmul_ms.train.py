"""Kernels: device time per execution of the train-step program outside
the `conv/` and `dense/` scopes: batch-norm, activations, adds, pools,
loss, updater and what carries no scope (the log gives that part
alone)."""

from benchmark import timeline


def read(facts):
    return timeline.scope_ms(
        facts, "train_step",
        lambda scope: scope.split("/")[0] not in timeline.MATMUL_KINDS)
