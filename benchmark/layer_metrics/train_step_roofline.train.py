"""Kernels (the XLA train-step program as one unit): the least time the
chips could take for one step (operations bound it) over the step
program's device time per execution in the trace."""

from benchmark.roofline import roofline_seconds


def read(facts):
    prog = facts["trace"]["programs"].get(
        facts["config"]["programs"]["train_step"])
    if not prog or not prog["n"]:
        return None
    ref, cfg, batch = facts["reference"], facts["config"], facts["batch"]
    least = roofline_seconds(ref.train_flops_per_image(cfg) * batch,
                             ref.train_step_bytes(cfg, batch),
                             facts["peaks"], facts["chips"])
    return 100.0 * least / prog["median_s"]
