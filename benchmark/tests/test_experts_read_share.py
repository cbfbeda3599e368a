"""`experts_read_share.serve`: the held experts whose weights the expert
layer read (`moe_experts_read`), over all it holds, per expert layer and
decode step: on a synthetic window, and in a traced run of the tiny
latent cell of `test_latent_cell.py`."""

import pytest

from benchmark import run
from benchmark.tests.conftest import last_line
from benchmark.tests.test_latent_cell import _facts, _reader, tiny  # noqa: F401


def test_experts_read_share_is_the_counters_ratio_and_nothing_without():
    """16 held experts in 4 expert layers: 10 steps that read 38 a step
    (the hit list) are 38 / 64; a product that read all 64 a step is 1;
    a program that does not count what it read (the parent's) gives
    nothing."""
    read = _reader("experts_read_share.serve")
    delta = {"steps": 10, "tokens_total": 320, "moe_experts_hit": 380}
    assert read(_facts({}, delta)) is None
    assert read(_facts({}, dict(delta, moe_experts_read=380))) \
        == pytest.approx(38 / 64)
    assert read(_facts({}, dict(delta, moe_experts_read=640))) == 1.0
    assert read(_facts({}, dict(delta, steps=0, moe_experts_read=0))) \
        is None


def test_traced_run_reports_the_share_of_the_held_experts_read(
        tiny, capsys):  # noqa: F811
    assert run.main(["--workload", "tiny-latent", "--seed", "13",
                     "--seconds", "1.5", "--trace", "1"]) == 0
    got = last_line(capsys)["metrics"]
    # 4 slots x 2 of 8 experts, 4 of them held: the hit list reads a
    # share of the 4 held
    assert 0 < got["experts_read_share.serve"]["value"] < 1.0
