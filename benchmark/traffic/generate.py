"""The one traffic generator: every mix is a data file of parameters.

`kind: "batch_pool"`   a pool of `pool` distinct float32 host batches
                       (normal images, one-hot labels) that the step
                       function cycles through.
`kind: "requests"`     per-client request lists. Lengths come from a
                       fixed stratified grid over each log-uniform
                       range, dealt to the clients in one fixed order
                       that the seed does not touch: with length-ended
                       requests the schedule, counted in engine steps,
                       is then the same for every seed, and a tail
                       over a few tens of requests repeats. The seed
                       draws the token ids. `shared_prefix` tokens of
                       `tenants` system prompts may lead each prompt
                       (0: no sharing). `loop: "closed"`: a client
                       submits its next request when the last returned.
                       `loop: "open"`: requests are due at times drawn
                       with the lengths at `rate_per_s` (gaps
                       exponential, `burst` requests arriving
                       together).
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    with open(os.path.join(HERE, f"{name}.json")) as f:
        return json.load(f)


def batch_pool(mix: dict, seed: int, image_size: int, num_classes: int):
    """`pool` host batches (x float32 normal, y one-hot), each drawn on
    the device by one jitted call and fetched: NumPy takes 13 s for the
    308M normals of eight batches of 256, the chip and the copy 2 s."""
    import jax
    import jax.numpy as jnp

    b = int(mix["batch"])

    @jax.jit
    def one(key):
        kx, ky = jax.random.split(key)
        x = jax.random.normal(kx, (b, image_size, image_size, 3), jnp.float32)
        y = jax.nn.one_hot(jax.random.randint(ky, (b,), 0, num_classes),
                           num_classes, dtype=jnp.float32)
        return x, y

    seed = int(seed)
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(1), seed & 0xFFFFFFFF), seed >> 32)
    return [tuple(np.asarray(a) for a in one(jax.random.fold_in(key, i)))
            for i in range(int(mix["pool"]))]


def _grid(lo: int, hi: int, n: int, rng) -> np.ndarray:
    """n lengths at the mid-quantiles of log-uniform [lo, hi], shuffled."""
    q = (np.arange(n) + 0.5) / n
    v = np.exp(np.log(lo) + q * (np.log(hi) - np.log(lo)))
    return rng.permutation(np.clip(np.rint(v), lo, hi).astype(int))


def requests(mix: dict, seed: int, vocab: int):
    """Returns a list (one entry per client) of lists of requests
    {"prompt": [ids], "max_new": n, "due_s": t or None}."""
    rng = np.random.default_rng([int(seed), 2])
    order = np.random.default_rng([0, 4])   # the same deal for every seed
    clients, per = int(mix["clients"]), int(mix["requests_per_client"])
    n = clients * per
    p_lo, p_hi = mix["prompt_tokens"]
    o_lo, o_hi = mix["output_tokens"]
    plens, olens = _grid(p_lo, p_hi, n, order), _grid(o_lo, o_hi, n, order)
    shared = int(mix.get("shared_prefix", 0))
    tenants = [rng.integers(0, vocab, shared).tolist()
               for _ in range(int(mix.get("tenants", 1)))] if shared else []
    due = [None] * n
    if mix.get("loop") == "open":
        burst = int(mix.get("burst", 1))
        gaps = order.exponential(burst / float(mix["rate_per_s"]),
                                 -(-n // burst))
        due = np.repeat(np.cumsum(gaps), burst)[:n].tolist()
    out = []
    for c in range(clients):
        reqs = []
        for r in range(per):
            i = c * per + r
            prompt = rng.integers(0, vocab, int(plens[i])).tolist()
            if shared:
                prompt = tenants[i % len(tenants)] + prompt
            reqs.append({"prompt": prompt, "max_new": int(olens[i]),
                         "due_s": due[i]})
        out.append(reqs)
    return out
