"""The serving plane for a decoder of Mamba-2 state-space layers and
grouped-query attention (`MambaMoETransformer`: Granite-4.0-H's block,
a matrix a head and a tail a slot beside a bfloat16 K/V page pool). The
run and the study are `drivers/serve_state.py`'s over what is written
here: the model from the published `config.json` keys, the engine
opened with the Mamba-2 vectors and the embedding in their own ranges
(`mamba2_ranges`, the benchmark's own mapping), and a witness that reads
the program's Mamba-2 state back.

The served tokens see a precision only through the near-ties it turns
over, and the program's own bfloat16 products set those; a state that
drifts shows in the state. So after the window the witness serves
rows again, teacher-forced through the program's own chunk and step
programs, reads each row's state S of every Mamba-2 layer back (what
the engine's slot held when it emitted the row's last token) and
`state_gap` compares it with the reference's sequential scan over the
same tokens. The rows are the sample's first `SERVED_ROWS` and a probe:
the longest one's prompt, then its last token again up to `PROBE_LEN`
positions. A repeated input is where a head that decays slowly sums
longest: its state grows toward a sum a thousand updates wide, whose
last bfloat16 place is wider than an update, so a state kept in
bfloat16 stops where float32 goes on.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.drivers import serve_state
from benchmark.drivers.serve import mix_width
from benchmark.drivers.serve_latent import make_weights
from benchmark.reference.granite_hybrid import KINDS

SERVED_ROWS = 3     # rows of the sample the witness serves again: the
#                     longest and two drawn by the seed, side by side
PROBE_LEN = 2048    # positions of the probe row (the window's, if fewer)
# Mamba-2's own initial ranges (the reference implementation's
# `A_init_range` (1, 16), `dt_min` 1e-3, `dt_max` 0.1)
A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 1e-1)
# the study's controls: side -> the reference's `control`
CONTROLS = {"control_bfloat16": "bfloat16",
            "control_state_bfloat16": "state_bfloat16",
            "witness_fp8": "fp8"}
# the study's faults of the program's state: side -> what the witness
# does to the fault's slots after every chunk and step
FAULTS = {"fault_state_bfloat16": "bfloat16",
          "fault_state_dropped": "dropped"}


def build(ctx):
    """The model and its three programs, the weights not yet the seed's."""
    from deeplearning4j_tpu.engine.decode_program import DecodeProgram
    from deeplearning4j_tpu.zoo.mamba_moe import MambaMoETransformer

    cfg, eng_cfg = ctx.config, ctx.cell["engine"]
    if cfg["position_embedding_type"] != "nope":
        raise ValueError("only the published NoPE attention is served: "
                         f"{cfg['position_embedding_type']!r}")
    heads = int(cfg["num_attention_heads"])
    model = MambaMoETransformer(
        layer_kinds=[KINDS[k] for k in cfg["layer_types"]],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=int(cfg["hidden_size"]) // heads,
        ssm_heads=cfg["mamba_n_heads"], ssm_head_dim=cfg["mamba_d_head"],
        ssm_state=cfg["mamba_d_state"], conv_taps=cfg["mamba_d_conv"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=cfg["logits_scaling"],
        attention_multiplier=cfg["attention_multiplier"],
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        n_heads=heads, moe_ff=cfg["intermediate_size"],
        n_experts=cfg["router_experts"], top_k=cfg["num_experts_per_tok"],
        experts_held=cfg["experts_held"],
        n_shared=cfg["shared_intermediate_size"]
        // cfg["intermediate_size"],
        n_dense_layers=int(cfg["first_k_dense_replace"]),
        max_ctx=eng_cfg["max_ctx"], eps=cfg["rms_norm_eps"],
        **cfg["constructor"])
    model.params = {}           # the seed's come with `open_engine`
    return DecodeProgram(model, max_slots=eng_cfg["max_slots"],
                         page_size=eng_cfg["page_size"],
                         n_pages=eng_cfg.get("n_pages"))


def mamba2_ranges(lp: dict, conv_scale: float) -> dict:
    """A layer's seeded leaves (1 + 0.1 n where one-dimensional), those
    of a Mamba-2 mixer that are no gains mapped to Mamba-2's own ranges
    through u = Phi(n), uniform on (0, 1), n the same normal draw:
    A = exp(A_log) uniform on `A_RANGE`; the step at dt_bias alone,
    softplus(dt_bias), log-uniform on `DT_RANGE`; the convolution's
    bias 0.5 n; D stays 1 + 0.1 n; the taps times `conv_scale`."""
    import jax.numpy as jnp
    from jax.scipy.special import ndtr

    if "A_log" not in lp:
        return lp
    out = dict(lp)
    u = {k: ndtr((lp[k] - 1.0) * 10.0) for k in ("A_log", "dt_bias")}
    lo, hi = A_RANGE
    out["A_log"] = jnp.log(lo + (hi - lo) * u["A_log"])
    lo, hi = (math.log(v) for v in DT_RANGE)
    dt = jnp.exp(lo + (hi - lo) * u["dt_bias"])
    out["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1
    out["conv_b"] = (lp["conv_b"] - 1.0) * 5.0
    out["conv_w"] = (lp["conv_w"].astype(jnp.float32)
                     * conv_scale).astype(lp["conv_w"].dtype)
    return out


def open_engine(ctx, prog, seed: int):
    """(w, engine): the seed's weights in the model, the Mamba-2
    vectors and taps in their own ranges and the embedding at its own
    deviation (`assumed`), a fresh page pool and state, every program
    compiled or loaded. Program and reference hold the same arrays."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.serving.continuous import DecodeEngine

    init = ctx.config["init"]
    w = make_weights(ctx.reference.param_shapes(ctx.config), seed, init,
                     ctx.config["constructor"]["param_dtype"])
    w["layers"] = tuple(mamba2_ranges(lp, float(init["conv_scale"]))
                        for lp in w["layers"])
    emb = w["tok_emb"]
    w["tok_emb"] = (emb.astype(jnp.float32) * (
        float(init["embedding_std"]) / float(init["w_std"]))).astype(
            emb.dtype)
    prog.model.params = w
    eng = DecodeEngine(program=prog,
                       **ctx.cell["engine"].get("engine_kwargs", {}))
    eng.kv, eng.state = prog.warmup(eng.kv, state=eng.state)
    jax.block_until_ready((eng.kv, eng.state))
    return w, eng


def witness_rows(tokens, served, n: int, length: int):
    """The first `n` rows of the sample as (tokens, prompt length), and
    the probe: the first one's prompt, then its last token again up to
    `length` positions."""
    rows = []
    for seq, mask in zip(tokens[:n], served[:n]):
        at = np.flatnonzero(mask)
        rows.append((seq[:at[-1] + 2].tolist(), int(at[0]) + 1))
    prompt, n_prompt = rows[0][0][:rows[0][1]], rows[0][1]
    return rows, (prompt + [prompt[-1]] * (length - n_prompt), n_prompt)


def _spoil(kind: str, slots):
    """A state fault: `slots`' entries of `s`, every layer's, rounded to
    bfloat16 or set to zero."""
    import jax
    import jax.numpy as jnp

    at = jnp.asarray(slots, jnp.int32)

    def fn(state):
        part = state["s"][:, at]
        part = jax.lax.reduce_precision(part, exponent_bits=8,
                                        mantissa_bits=7) \
            if kind == "bfloat16" else jnp.zeros_like(part)
        return dict(state, s=state["s"].at[:, at].set(part))

    return jax.jit(fn, donate_argnums=0)


def replay(prog, kv, state, rows, spoil=()):
    """Serve `rows` (tokens, prompt length) again through the program's
    own chunk and step programs, teacher-forced: row r in slot r on
    pages of its own, its prompt by chunks, then a token a step, every
    slot at its own position, up to the row's last served position,
    the one whose step emitted the row's last token. `spoil` is
    (fault, slots) pairs: after every chunk and step the fault is
    applied to those slots' entries. `kv` and `state` are donated.
    Returns (kv, state, S, agree): S [rows, Mamba-2 layers, H, P, N]
    float32 on the host, each row's state after its last step; agree,
    a row's share of steps that emit its own next token."""
    import jax
    from deeplearning4j_tpu.engine.decode_program import SCRATCH_PAGE

    faults = [_spoil(kind, slots) for kind, slots in spoil]

    def spoiled(st):
        for fn in faults:
            st = fn(st)
        return st

    ps, pps, c = prog.page_size, prog.pages_per_slot, prog.chunk_tokens
    tables = [list(range(1 + r * pps, 1 + (r + 1) * pps))
              for r in range(len(rows))]
    for r, (seq, n_prompt) in enumerate(rows):
        for start in prog.chunk_starts(n_prompt):
            pages = prog.block_pages(n_prompt, start)
            kv, state = prog.prefill_chunk(
                kv, seq[start:min(start + c, n_prompt)], start,
                prog.window_pages(tables[r], start - 1),
                tables[r][pages.start:pages.stop], state=state, slot=r,
                n_state=prog.state_rows(n_prompt, start))
            state = spoiled(state)
    first = [n_prompt - 1 for _, n_prompt in rows]
    last = [len(seq) - 2 for seq, _ in rows]
    slots = prog.max_slots
    emitted = []
    for i in range(max(b - a + 1 for a, b in zip(first, last))):
        live = [r for r in range(len(rows)) if first[r] + i <= last[r]]
        width = prog.width_for(max(prog.live_pages(first[r] + i)
                                   for r in live))
        tokens = np.zeros(slots, np.int32)
        positions = np.zeros(slots, np.int32)
        page_ids = np.full((slots, width), SCRATCH_PAGE, np.int32)
        wp = np.full(slots, SCRATCH_PAGE, np.int32)
        wo = np.zeros(slots, np.int32)
        for r in live:
            pos = first[r] + i
            tokens[r], positions[r] = rows[r][0][pos], pos
            page_ids[r] = prog.window_pages(tables[r], pos, width)
            if i:       # the first step's cell is the prefill's
                wp[r], wo[r] = tables[r][pos // ps], pos % ps
        kv, nxt, _, state = prog.step(kv, tokens, positions, page_ids, wp,
                                      wo, state)
        state = spoiled(state)
        emitted.append((live, i, nxt))
    same = np.zeros(len(rows))
    for live, i, nxt in emitted:
        nxt = np.asarray(nxt)
        for r in live:
            same[r] += int(nxt[r]) == rows[r][0][first[r] + i + 1]
    s = np.asarray(jax.device_get(state["s"][:, :len(rows)]), np.float32)
    n_layers, n_rows, g, n, lanes = s.shape
    p = int(prog.model.ssm_head_dim)
    # `s` holds rows of heads [G, N, W]: head g r + j, channel p of
    # state n at [g, n, j P + p] (`nn/mamba2.py` `state_shapes`)
    s = s.reshape(n_layers, n_rows, g, n, lanes // p, p)
    s = s.transpose(1, 0, 2, 4, 5, 3).reshape(n_rows, n_layers, -1, p, n)
    return kv, state, s, same / (np.asarray(last) - first + 1)


def state_gap(got, ref) -> float:
    """The widest, over rows and Mamba-2 layers, of |S - S_ref| / |S_ref|
    (Frobenius norms of a layer's matrices)."""
    k, n = ref.shape[:2]
    diff = np.linalg.norm((got - ref).reshape(k, n, -1), axis=-1)
    return float(np.max(diff / np.linalg.norm(ref.reshape(k, n, -1),
                                              axis=-1)))


def _states(ctx, control=None):
    """rows -> the reference's states after each row's last served
    position, a row at a time, as numpy."""
    import jax
    import jax.numpy as jnp

    cfg, width = ctx.config, mix_width(ctx.mix)
    fn = jax.jit(lambda w, t, n: ctx.reference.final_states(w, t, n, cfg,
                                                            control))

    def states(w, rows):
        out = []
        wide = max([width] + [len(seq) for seq, _ in rows])
        for seq, _ in rows:
            padded = np.zeros((1, wide), np.int32)
            padded[0, :len(seq)] = seq
            out.append(np.asarray(fn(w, jnp.asarray(padded),
                                     jnp.asarray([len(seq) - 1]))))
        return np.concatenate(out)

    return states


def witness(ctx, w, eng, tokens, served, study=False):
    """Serve `witness_rows` again while the engine holds its programs;
    in a study the same rows once more under each of `FAULTS`, side by
    side where the slots hold them (fewer served rows where they cannot
    hold one side). Returns numbers(w): the run's `state_gap`, or in a
    study one dict a side (the program, each fault, and each of
    `CONTROLS` from the reference alone)."""
    names = ["program"] + (list(FAULTS) if study else [])
    prog = eng.program
    served_rows, probe = witness_rows(
        tokens, served, min(SERVED_ROWS, prog.max_slots - 1),
        min(PROBE_LEN, prog.window))
    rows = served_rows + [probe]
    k = len(rows)
    # as many of the sides side by side as the slots hold
    per = max(1, prog.max_slots // k)
    s, agree = [], []
    for part in (names[i:i + per] for i in range(0, len(names), per)):
        spoil = [(FAULTS[name], range(i * k, (i + 1) * k))
                 for i, name in enumerate(part) if name in FAULTS]
        eng.kv, eng.state, got, same = replay(prog, eng.kv, eng.state,
                                              rows * len(part), spoil)
        s.append(got)
        agree.append(same)
    s, agree = np.concatenate(s), np.concatenate(agree)
    ctx.log(f"state witness: {k - 1} rows of "
            f"{[len(q) for q, _ in served_rows]} positions served again, "
            f"{np.mean(agree[:k - 1]):.4f} of their steps emit the served "
            f"token; the probe of {len(probe[0])} ({probe[1]} prompted)")
    got = {name: s[i * k:(i + 1) * k] for i, name in enumerate(names)}

    def numbers(w):
        ref = _states(ctx)(w, rows)
        gaps = {name: state_gap(g, ref) for name, g in got.items()}
        ctx.log(f"state against the reference's scan, widest of rows and "
                f"layers: {gaps}; the probe's: "
                f"{ {n: state_gap(g[-1:], ref[-1:]) for n, g in got.items()} }")
        if not study:
            return {"state_gap": gaps["program"]}
        gaps.update({side: state_gap(_states(ctx, c)(w, rows), ref)
                     for side, c in CONTROLS.items()})
        return {side: {"state_gap": v} for side, v in gaps.items()}

    return numbers


def run(ctx):
    return serve_state.run(ctx, build, open_engine, witness)


def study(ctx, seeds):
    """`drivers/serve_state.py`'s study with this driver's model, the
    controls `CONTROLS` (the reference in bfloat16 throughout, its
    state included; the state alone in bfloat16; every product's
    operands and the state in float8 e4m3) and, from the witness, the
    program's state rounded to bfloat16 after every chunk and step
    (what storing it in bfloat16 does) and dropped after each (a state
    never carried). Yields one dict per seed."""
    return serve_state.study(ctx, seeds, build, open_engine, CONTROLS,
                             witness)
