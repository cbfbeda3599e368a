"""The harness end to end at a tiny size on the CPU."""

import pytest

from benchmark import run
from benchmark.tests.conftest import last_line

# a tiny cell reports what the first listed cell of its driver does: the
# GPT-2 chat cell, whose time per token is a per-layer metric
E2E = {"tiny-train": {"train_img_per_s", "setup_s"},
       "tiny-serve": {"decode_tok_per_s", "ttft_p95_ms", "setup_s"}}


@pytest.mark.parametrize("cell", sorted(E2E))
def test_untraced_run_prints_the_contracts_line(tiny, capsys, cell):
    assert run.main(["--workload", cell, "--seed", str(2**31 + 7),
                     "--seconds", "1.5", "--trace", "0"]) == 0
    res = last_line(capsys)
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device", "compared"}
    assert list(res)[-1] == "compared"
    assert res["correct"] is True, res["compared"]
    assert set(res["metrics"]) == E2E[cell]
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["count"] == 1


@pytest.mark.parametrize("cell", sorted(E2E))
def test_traced_run_reports_layer_metrics(tiny, capsys, cell):
    assert run.main(["--workload", cell, "--seed", "11",
                     "--seconds", "1.5", "--trace", "1"]) == 0
    res = last_line(capsys)
    plane = "." + cell.split("-")[1]
    assert res["metrics"] and all(k.endswith(plane) for k in res["metrics"])
    assert res["metrics"]["compiles_in_window" + plane]["value"] == 0
    if plane == ".serve":
        # the window's time per token, per layer in the cell it stands for
        assert res["metrics"]["tpot_p95_ms.serve"]["value"] == \
            res["end_to_end"]["tpot_p95_ms"] > 0
    # no TPU plane in a CPU trace: the trace's readers return nothing
    assert "device_idle_share" + plane not in res["metrics"]
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_accelerator_exits_nonzero_and_prints_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "resnet50-train-b256", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_window_opens_on_the_same_step_whatever_the_seed_or_the_trace(tiny):
    """The schedule is counted in engine steps: two seeds and a traced
    run reach the window with the same steps, tokens and chunks behind
    them."""
    from benchmark.drivers import serve

    ctx, _, _, _ = run.make_context("tiny-serve", 0, 0.2, False)
    ctx.start_trace = ctx.stop_trace = lambda: None
    prog = serve.build(ctx)
    opens = []
    for seed, trace in ((3, False), (2**31 + 9, False), (3, True)):
        _, eng = serve.open_engine(ctx, prog, seed)
        win = serve.drive(ctx, eng, seed, 0.2, trace)
        opens.append({k: win.s0[k] for k in serve.COUNTERS})
    assert opens[0] == opens[1] == opens[2]
    assert opens[0]["steps"] == ctx.mix["warmup_steps"]
    assert opens[0]["prefill_chunks"] > 0
