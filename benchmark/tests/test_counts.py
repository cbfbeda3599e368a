"""The shape functions against hand counts, and the generator's
steadiness from seed to seed."""

import json
import os

import numpy as np
import pytest

from benchmark import roofline
from benchmark.reference import gpt2, resnet50
from benchmark.traffic import generate

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_one_bottleneck_block_by_hand():
    # s2b1 at 56x56: 1x1 256->64, 3x3 64->64, 1x1 64->256, 2 per MAC
    macs = 56 * 56 * (256 * 64 + 9 * 64 * 64 + 64 * 256)
    specs = {s[0]: s for s in resnet50.conv_specs(config("resnet50-imagenet"))}
    got = sum(roofline.conv2d_flops(1, o, o, co, kh, kw, ci)
              for _, kh, kw, ci, co, o in
              (specs["s2b1_a"], specs["s2b1_b"], specs["s2b1_c"]))
    assert got == 2.0 * macs == 2.0 * 218_365_952


def test_resnet50_whole_model_counts():
    cfg = config("resnet50-imagenet")
    fwd = resnet50.forward_flops_per_image(cfg)
    # stride in the first 1x1 of a stage (the paper's placement): 3.86 GMAC
    assert 3.8e9 < fwd / 2 < 3.9e9
    assert 2.95 < resnet50.train_flops_per_image(cfg) / fwd < 3.0
    # 25.6M parameters with the 1000-way head; biases on every convolution
    assert 25.5e6 < resnet50.n_params(cfg) < 25.7e6


def test_one_decoder_layer_by_hand():
    cfg = dict(config("gpt2-medium"), n_layer=1, vocab_size=0)
    d, f = 1024, 4096
    assert gpt2.matmul_params(cfg) == 4 * d * d + 2 * d * f
    # one token over 100 live positions: QK^T and AV, 2 per MAC each
    assert gpt2.flops_per_token(cfg, 100) == \
        2 * (4 * d * d + 2 * d * f) + 2 * 2 * d * 100
    # weights once, 100 live cells of K and V read, one written, float32
    assert gpt2.decode_step_bytes(cfg, 100, 1) == \
        4 * (4 * d * d + 2 * d * f) + 101 * 2 * d * 4


def test_gpt2_medium_has_its_published_size():
    cfg = config("gpt2-medium")
    shapes = gpt2.param_shapes(cfg)
    n = sum(int(np.prod(s)) for k, s in shapes.items() if k != "layers")
    n += sum(int(np.prod(s)) for layer in shapes["layers"]
             for s in layer.values())
    assert 354.5e6 < n < 355.0e6


def test_peaks_are_keyed_by_device_kind():
    assert roofline.device_peaks("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError):
        roofline.device_peaks("cpu")


def test_same_seed_same_requests_and_every_seed_the_same_schedule():
    mix = generate.load("chat-closed32")
    a = generate.requests(mix, 2**31 + 5, 50257)
    b = generate.requests(mix, 2**31 + 5, 50257)
    c = generate.requests(mix, 6, 50257)
    assert a == b and a != c
    assert len(a) == mix["clients"]
    assert all(len(reqs) == mix["requests_per_client"] for reqs in a)
    # the seed draws the tokens; who sends which lengths when is the same
    sizes = lambda lists: [[(len(r["prompt"]), r["max_new"]) for r in reqs]  # noqa: E731
                           for reqs in lists]
    assert sizes(a) == sizes(c)
    lo, hi = mix["prompt_tokens"]
    assert all(lo <= p <= hi for reqs in sizes(a) for p, _ in reqs)
    # every slot has its first token before the window: the first round's
    # chunks, one a step, fit into the warm-up
    page = 16
    assert sum(-(-reqs[0][0] // page) for reqs in sizes(a)) \
        < mix["warmup_steps"]
    # no list runs out inside the window: a client is busy for at least
    # its prompts' chunks and its output tokens, a step each, whatever it
    # waits in the queue (the CPU replay has the first client silent at
    # step 5,936, a cycle of 8.8 ms; this count alone gives 9.4)
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        seconds = json.load(f)["run_seconds"]
    life = min(sum(-(-p // page) + o for p, o in reqs) for reqs in sizes(a))
    assert life - mix["warmup_steps"] > seconds / 0.0095


def test_open_loop_arrivals_are_the_mixes_too():
    mix = dict(generate.load("chat-closed32"), loop="open", rate_per_s=4.0,
               burst=2)
    due = lambda seed: [r["due_s"] for reqs in  # noqa: E731
                        generate.requests(mix, seed, 50257) for r in reqs]
    assert due(1) == due(2) and sorted(due(1)) == due(1)
    assert due(1)[0] == due(1)[1] and due(1)[1] < due(1)[2]


def test_same_seed_same_batches():
    mix = {"kind": "batch_pool", "batch": 4, "pool": 3}
    a = generate.batch_pool(mix, 2**31 + 5, 8, 10)
    b = generate.batch_pool(mix, 2**31 + 5, 8, 10)
    assert all(np.array_equal(x, u) and np.array_equal(y, v)
               for (x, y), (u, v) in zip(a, b))
    assert not np.array_equal(a[0][0], a[1][0])
    assert all(y.sum() == 4 for _, y in a)


def test_ttft_counts_every_request_sent_in_the_window():
    """A first token that comes after the close is waited for and counts
    with its wait; one that never comes counts as failed, at the time it
    has waited; what was sent before the window counts in neither."""
    from types import SimpleNamespace as Row

    from benchmark.drivers.serve import window_metrics

    def row(sub, first, last, n=5, failed=False):
        return Row(req={"prompt": [1]}, refused=False, due=None,
                   failed=failed, done=last is not None, tokens=[0] * n,
                   t_submit=sub, t_first=first, t_last=last)

    rows = [row(9.0, 9.5, 12.0),                # sent before, ends inside
            row(11.0, 11.5, 15.0),              # sent and answered inside
            row(19.0, 23.0, None),              # first token past the close
            row(19.5, None, None),              # none by the end of the wait
            Row(req={}, refused=True, due=None, failed=True, t_submit=12.0)]
    ttft, tpot, attempted, failed, finished = window_metrics(
        rows, 10.0, 20.0, 30.0)
    assert attempted == 4 and failed == 2
    assert sorted(ttft) == [500.0, 4000.0, 10500.0, 18000.0]
    assert [r.t_submit for r in finished] == [9.0, 11.0]
    assert tpot == [(12.0 - 9.5) / 4 * 1e3, (15.0 - 11.5) / 4 * 1e3]
