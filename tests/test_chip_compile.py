"""The main path's kernels and decode programs, compiled for the chip
without the chip.

The TPU's compiler is installed beside the CPU backend and compiles for
a v5e that is described, not attached (`jax.experimental.topologies`).
Interpret mode — all `tests/test_pallas_kernels.py` can run here —
accepts kernels the chip's compiler refuses: a slice off the tiling,
too much VMEM, a program that does not fit HBM. These compiles guard
that at real widths (ResNet50 batch 128, stage 1 and stage 4, bf16; the
decode programs at the widths `chip_smoke.py` serves) for about a
second each. Nothing runs: they say nothing about results or times.

Rules this file keeps (the on-chip-measurement guide, section 2): the
topology is described inside a module-scoped fixture, never at import,
so every xdist worker collects the same tests and only the worker that
runs this file loads libtpu; everything lives in this ONE file, since a
second file could land on another worker, whose fixture would skip; the
persistent compile cache is off around these compiles, because such an
entry cannot be read back without a chip.
"""

import re

import numpy as np
import pytest

BATCH = 128
# ResNet50 bottleneck widths at batch 128: (spatial, mid channels,
# out channels) of the first and the last residual stage
STAGES = {"stage1": (56, 64, 256), "stage4": (7, 512, 2048)}
DECODER = dict(vocab_size=512, d_model=128, n_heads=4, n_layers=4,
               max_ctx=128, seed=17)


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import (
        compilation_cache as cc,
    )

    env = pytest.MonkeyPatch()
    env.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 - any failure means: skip
        env.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # jax.default_backend() is "cpu" here, so the expert layer's, the
    # Mamba-2 step's and the paged attention's kernels would pick
    # interpret mode; the chip's compiler must get Mosaic
    from deeplearning4j_tpu.nn.helpers import (
        pallas_moe,
        pallas_paged_gqa,
        pallas_ssd,
    )

    for kernel in (pallas_moe, pallas_paged_gqa, pallas_ssd):
        env.setattr(kernel, "_interpret", lambda: False)
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    cc.reset_cache()
    env.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _kernel_case(kernel, stage):
    """(fn, [(shape, dtype), ...]) for one Pallas entry point at one
    ResNet50 stage: the affine + relu prologue form the fused graph
    runs between two convolutions."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.helpers import pallas_conv as pc

    hw, c_mid, c_out = STAGES[stage]
    m = BATCH * hw * hw
    bf, f32 = jnp.bfloat16, jnp.float32
    if kernel == "fused_conv1x1":
        return (lambda x, w, b, s, t: pc.fused_conv1x1(
            x, w, b, scale=s, shift=t, relu=True, emit_u=True),
            [((m, c_mid), bf), ((c_mid, c_out), bf), ((c_out,), f32),
             ((c_mid,), f32), ((c_mid,), f32)])
    if kernel == "fused_conv3x3":
        return (lambda x, w, b, s, t: pc.fused_conv3x3(
            x, w, b, scale=s, shift=t, relu=True),
            [((BATCH, hw, hw, c_mid), bf), ((3, 3, c_mid, c_mid), bf),
             ((c_mid,), f32), ((c_mid,), f32), ((c_mid,), f32)])
    if kernel == "dgrad_conv1x1":
        return (lambda dy, y, w, x, s, t: pc.dgrad_conv1x1(
            dy, y, w, x, scale=s, shift=t, relu=True),
            [((m, c_out), bf), ((m, c_out), bf), ((c_mid, c_out), bf),
             ((m, c_mid), bf), ((c_mid,), f32), ((c_mid,), f32)])
    assert kernel == "wgrad_conv1x1"
    return (lambda dy, y, x, s, t: pc.wgrad_conv1x1(
        dy, y, x, scale=s, shift=t, relu=True),
        [((m, c_out), bf), ((m, c_out), bf), ((m, c_mid), bf),
         ((c_mid,), f32), ((c_mid,), f32)])


@pytest.mark.parametrize("stage", sorted(STAGES))
@pytest.mark.parametrize("kernel", ["fused_conv1x1", "fused_conv3x3",
                                    "dgrad_conv1x1", "wgrad_conv1x1"])
def test_pallas_kernel_compiles_for_v5e(kernel, stage, one_chip,
                                        monkeypatch):
    import jax

    from deeplearning4j_tpu.nn.helpers import pallas_conv as pc

    # jax.default_backend() is "cpu" here, so the kernels would pick
    # interpret mode; the chip's compiler must get the Mosaic lowering
    monkeypatch.setattr(pc, "_interpret", lambda: False)
    fn, specs = _kernel_case(kernel, stage)
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("program", ["decode_step_s8",
                                     "decode_prefill_c128",
                                     "decode_page_copy"])
@pytest.mark.parametrize("compute_dtype", [None, "bfloat16"],
                         ids=["f32", "bf16"])
def test_decode_program_compiles_for_v5e(compute_dtype, program,
                                         one_chip):
    """The three programs DecodeEngine dispatches, lowered from
    `DecodeProgram.lint_records()` (the cache paths the engine itself
    uses) with the example arguments turned into shapes on the
    described chip. The chunk is named by its length in tokens: eight
    pages of 16, the whole window of this toy model."""
    import jax

    from deeplearning4j_tpu.engine.decode_program import DecodeProgram
    from deeplearning4j_tpu.zoo.decoder import CausalTransformer

    model = CausalTransformer(compute_dtype=compute_dtype,
                              **DECODER).init()
    prog = DecodeProgram(model, max_slots=8, page_size=16)
    rec = {r.name: r for r in prog.lint_records()}[program]
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=one_chip),
        rec.example_args)
    compiled = rec.fn.lower(*shapes).compile()
    mem = compiled.memory_analysis()
    assert mem is not None
    # the donated page pool is updated in place: its bytes are aliased
    assert mem.alias_size_in_bytes >= np.prod(prog.kv_shape) * 4


def _cell_programs(one_chip, name, driver, ref, matrix_dtype):
    """(program, {kind: (jitted function, argument shapes)}) of a
    serving cell as its driver builds it, with shapes on the described
    chip in the place of the weights and the pool. The decode step and
    the chunk at the widest window of the program's ladder (the whole
    window) and, as `*_narrow`, at the narrowest (the step's own: a
    step that reads the pool itself has the whole window alone)."""
    import json
    import os
    from types import SimpleNamespace

    import jax
    import jax.numpy as jnp

    def load(*parts):
        with open(os.path.join(os.path.dirname(__file__), "..",
                               "benchmark", *parts)) as f:
            return json.load(f)

    cell = load("workloads", f"{name}.json")
    cfg = load("configs", f"{cell['config']}.json")
    prog = driver.build(SimpleNamespace(config=cfg, cell=cell))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def leaf(shape):
        return sds(shape, jnp.float32 if len(shape) == 1 else matrix_dtype)

    shapes = ref.param_shapes(cfg)
    params = {k: leaf(v) for k, v in shapes.items() if k != "layers"}
    params["layers"] = tuple({k: leaf(v) for k, v in layer.items()}
                             for layer in shapes["layers"])
    pool = sds(prog.kv_shape, prog.model.kv_dtype)
    s, t = prog.max_slots, prog.chunk_tokens
    i32 = jnp.int32
    zs, one = sds((s,), i32), sds((), i32)
    cases = {"copy": (prog._copy_program(), (pool, one, one))}
    # a model with per-slot state: the state beside the pool in both
    # programs, and the chunk's slot and rows absorbed after the rest
    held, tail = (pool,), ()
    if prog.has_state:
        shape = prog.model.state_shape(s)
        dt = prog.model.state_dtype
        held += ({k: sds(v, dt) for k, v in shape.items()}
                 if isinstance(shape, dict) else sds(shape, dt),)
        tail = (one, one)
    assert prog.widths[0] < prog.widths[-1] == prog.pages_per_slot
    assert prog.step_widths[-1] == prog.pages_per_slot
    for tag, i in (("", -1), ("_narrow", 0)):
        # the step's last two arguments (PR 35): the step before's
        # tokens, on the device, and the rows that take them
        p = prog.step_widths[i]
        cases["decode" + tag] = (
            prog._decode_program(p),
            (params, *held, zs, zs, sds((s, p), i32), zs, zs, zs,
             sds((s,), jnp.bool_)))
        p = prog.widths[i]
        cases["chunk" + tag] = (
            prog._chunk_program(p),
            (params, *held, sds((t,), i32), one, sds((p,), i32),
             sds((prog.chunk_pages,), i32), *tail))
    return prog, cases


@pytest.fixture(scope="module")
def latent_cell(one_chip):
    """`pangu-ultra-chat-closed32` with shapes in the place of 9.84 GB
    of bfloat16 weights."""
    import jax.numpy as jnp

    from benchmark.drivers import serve_latent
    from benchmark.reference import pangu_ultra_moe as ref

    return _cell_programs(one_chip, "pangu-ultra-chat-closed32",
                          serve_latent, ref, jnp.bfloat16)


CELL_PROGRAMS = ["decode", "chunk", "copy", "decode_narrow",
                 "chunk_narrow"]


@pytest.mark.parametrize("program", CELL_PROGRAMS)
def test_latent_cell_compiles_for_v5e_with_no_copy_of_the_pool(
        latent_cell, program):
    """The cell's three programs at the published widths (4.92B
    parameters, 32 slots of 4,096 positions), the step and the chunk
    at the widest and at the narrowest window of the ladder (32 pages
    and 4). The pool is updated in
    place and in ONE layout: a row of 576 in place of 640 lanes makes
    the compiler convert the whole pool in and out of every program
    (0.76 GB of temporaries and two copies a step; PERF.md, PR 28), and
    a program that needs more than a gigabyte beside its arguments
    would not leave the chunk's room beside 10.7 GB of weights and
    pool."""
    prog, cases = latent_cell
    fn, args = cases[program]
    compiled = getattr(fn, "__wrapped__", fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    pool_bytes = int(np.prod(prog.kv_shape)) * 2
    assert prog.kv_shape == (5, 1025, 128, 640)
    assert prog.widths == (4, 8, 16, 32)
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 1.0e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12.5e9
    text = compiled.as_text()
    layouts = set(re.findall(r"bf16\[5,1025,128,640\]\{([0-9,]+):", text))
    assert layouts == {"3,2,1,0"}
    # the step and the chunk read their experts through the hit list's
    # kernel (nn/helpers/pallas_moe.py) at the chip's VMEM
    assert ("tpu_custom_call" in text) == (program != "copy")


@pytest.fixture(scope="module")
def gpt2_cell(one_chip):
    """`gpt2m-chat-closed32` (24 x 1,024, 16 heads, 32 slots, pages of
    16, 1,025 pages) with shapes in the place of 1.42 GB of float32
    weights."""
    import jax.numpy as jnp

    from benchmark.drivers import serve
    from benchmark.reference import gpt2 as ref

    return _cell_programs(one_chip, "gpt2m-chat-closed32", serve, ref,
                          jnp.float32)


_ARRAY = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]+)\]\{([0-9,]+)")


def _materialized(text):
    """(opcode, bytes, dims, minor dimension's size) of every array an
    instruction of the compiled program's ENTRY computation produces.
    Those are the buffers that exist; what a fusion computes inside
    itself is in the computations before ENTRY and is not listed."""
    out = []
    for line in text[text.index("\nENTRY "):].splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?\S+ = (.*?) ([a-z][a-z\-]*)\(", line)
        if not m:
            continue
        for dtype, dims, layout in _ARRAY.findall(m.group(1)):
            dims = tuple(int(d) for d in dims.split(","))
            bits = re.search(r"[0-9]+$", dtype)    # `pred` has none
            out.append((m.group(2),
                        int(np.prod(dims)) * (int(bits[0]) if bits else 8)
                        // 8, dims, dims[int(layout.split(",")[0])]))
    return out


@pytest.mark.parametrize("program", CELL_PROGRAMS)
def test_gpt2_cell_compiles_for_v5e_with_the_pool_as_stored(gpt2_cell,
                                                            program):
    """The cell's three programs at the published widths, the step and
    the chunk at the widest and at the narrowest window of the ladder
    (64 pages and 32). The pool is
    token rows of 1,024 lanes, updated in place and in ONE layout, and
    no instruction of its shape is a `copy`: with head_dim 64 minor
    (the head-major page, and the same padded to 128) the compiler
    converted all 3.6 GB in and out of every program, 31 of a 77 ms
    step and 7 GB of temporaries (PERF.md, PR 29). And no buffer of
    50 MB or more is narrower than a 128-lane tile: a window split
    into (heads, 64) is materialized at half width, 48 times a step."""
    prog, cases = gpt2_cell
    fn, args = cases[program]
    compiled = getattr(fn, "__wrapped__", fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert prog.kv_shape == (24, 2, 1025, 16, 1024)
    assert prog.widths == (32, 64)
    assert mem.alias_size_in_bytes >= int(np.prod(prog.kv_shape)) * 4
    assert mem.temp_size_in_bytes < 1.0e9
    text = compiled.as_text()
    layouts = set(re.findall(
        r"f32\[24,2,1025,16,1024\]\{([0-9,]+):", text))
    assert layouts == {"4,3,2,1,0"}
    made = _materialized(text)
    assert any(dims == prog.kv_shape for _, _, dims, _ in made)
    assert not [m for m in made
                if m[0] == "copy" and m[2] == prog.kv_shape]
    assert not [m for m in made if m[1] >= 50e6 and m[3] < 128]


@pytest.fixture(scope="module")
def hybrid_cell(one_chip):
    """`kimi-linear-reason-closed64` with shapes in the place of
    7.55 GB of bfloat16 weights, 1.34 GB of latent pool and 0.86 GB of
    per-slot state."""
    import jax.numpy as jnp

    from benchmark.drivers import serve_hybrid
    from benchmark.reference import kimi_linear as ref

    return _cell_programs(one_chip, "kimi-linear-reason-closed64",
                          serve_hybrid, ref, jnp.bfloat16)


@pytest.mark.parametrize("program", CELL_PROGRAMS)
def test_hybrid_cell_compiles_for_v5e_with_pool_and_state_in_place(
        hybrid_cell, program):
    """The cell's programs at the published widths (3.77B parameters,
    64 slots of 8,192 positions, six layers' state of 32 matrices of
    128 x 128 a slot), the step and the chunk at the widest and the
    narrowest window of the ladder (64 pages and 4). Pool AND state
    are donated and updated in place, each in ONE layout (the state
    with its 128 x 128 matrices innermost, as stored), no instruction
    copies the state whole, no buffer of 50 MB or more is narrower
    than a 128-lane tile, and a program needs under a gigabyte beside
    9.75 GB of arguments."""
    prog, cases = hybrid_cell
    fn, args = cases[program]
    compiled = getattr(fn, "__wrapped__", fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert prog.kv_shape == (2, 4097, 128, 640)
    assert prog.widths == (4, 8, 16, 32, 64)
    shapes = prog.model.state_shape(prog.max_slots)
    assert shapes == {"s": (6, 64, 32, 128, 128),
                      "tail": (6, 64, 3, 12288)}
    pool_bytes = int(np.prod(prog.kv_shape)) * 2
    state_bytes = sum(int(np.prod(v)) * 4 for v in shapes.values())
    assert mem.temp_size_in_bytes < 1.0e9
    text = compiled.as_text()
    # the step and the chunk through the hit list's kernel
    assert ("tpu_custom_call" in text) == (program != "copy")
    if program == "copy":
        assert mem.alias_size_in_bytes >= pool_bytes
        return
    assert mem.alias_size_in_bytes >= pool_bytes + state_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12.5e9
    assert set(re.findall(r"bf16\[2,4097,128,640\]\{([0-9,]+):",
                          text)) == {"3,2,1,0"}
    assert set(re.findall(r"f32\[6,64,32,128,128\]\{([0-9,]+):",
                          text)) == {"4,3,2,1,0"}
    made = _materialized(text)
    assert not [m for m in made
                if m[0] == "copy" and m[2] == shapes["s"]]
    assert not [m for m in made if m[1] >= 50e6 and m[3] < 128]


@pytest.fixture(scope="module")
def conv_cell(one_chip):
    """`lfm2-moe-chat-closed128` with shapes in the place of 10.36 GB
    of bfloat16 weights, 2.15 GB of K/V pool and 15 MB of tails."""
    import jax.numpy as jnp

    from benchmark.drivers import serve_conv
    from benchmark.reference import lfm2_moe as ref

    return _cell_programs(one_chip, "lfm2-moe-chat-closed128", serve_conv,
                          ref, jnp.bfloat16)


@pytest.mark.parametrize("program", CELL_PROGRAMS)
def test_conv_cell_compiles_for_v5e_with_pool_and_tails_in_place(
        conv_cell, program):
    """The cell's programs at the published widths (5.18B parameters,
    every one of 64 experts of eight layers, 128 slots of 4,096
    positions, two attention layers' K and V rows of 512 lanes in
    bfloat16, seven layers' tails of two rows of 2,048 a slot), the step
    and the chunk at the widest and the narrowest window of the ladder
    (32 pages and 4). Pool AND tails are donated and updated in place,
    the pool in ONE layout and never copied whole or raised to
    float32, no buffer of 50 MB or more is narrower than a 128-lane
    tile, and arguments and temporaries leave the chip's 15.75 GiB a
    twelfth to spare (the cell's ceiling of 92%)."""
    prog, cases = conv_cell
    fn, args = cases[program]
    compiled = getattr(fn, "__wrapped__", fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert prog.kv_shape == (2, 2, 4097, 128, 512)
    assert prog.widths == (4, 8, 16, 32)
    tails = prog.model.state_shape(prog.max_slots)
    assert tails == (7, 128, 2, 2048)
    pool_bytes = int(np.prod(prog.kv_shape)) * 2
    text = compiled.as_text()
    # 128 rows x 4 of 64 (nearly every expert hit): the kernel too
    assert ("tpu_custom_call" in text) == (program != "copy")
    if program == "copy":
        assert mem.alias_size_in_bytes >= pool_bytes
        return
    assert mem.alias_size_in_bytes >= pool_bytes + int(np.prod(tails)) * 4
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 0.92 * 15.75 * 2**30
    assert set(re.findall(r"bf16\[2,2,4097,128,512\]\{([0-9,]+):",
                          text)) == {"4,3,2,1,0"}
    assert not re.findall(r"f32\[2,2,4097,128,512\]", text)
    made = _materialized(text)
    assert not [m for m in made
                if m[0] == "copy" and m[2] == prog.kv_shape]
    assert not [m for m in made if m[1] >= 50e6 and m[3] < 128]
    # no float32 copy of a gathered window (it would be 1.07 GB a plane
    # at 32 pages)
    assert not [m for m in made if m[1] >= 1.0e9 and m[2] != prog.kv_shape]


@pytest.fixture(scope="module")
def window_cell(one_chip):
    """`laguna-code-closed32` with shapes in the place of 3.43 GB of
    bfloat16 weights, 2.69 GB of K/V pool and 0.20 GB of rings."""
    import jax.numpy as jnp

    from benchmark.drivers import serve_window
    from benchmark.reference import laguna as ref

    return _cell_programs(one_chip, "laguna-code-closed32", serve_window,
                          ref, jnp.bfloat16)


@pytest.mark.parametrize("program", CELL_PROGRAMS)
def test_window_cell_compiles_for_v5e_with_pool_and_rings_in_place(
        window_cell, program):
    """The cell's programs at the published widths (1.72B parameters,
    32 of 256 experts of four layers, 32 slots of 10,240 positions for
    the two full layers' K and V rows of 1,024 lanes in bfloat16, three
    window layers' rings of 512 such rows a slot), the step and the
    chunk at the widest and the narrowest window of the ladder (80 pages
    and 4). Pool AND rings are donated and updated in place, each in ONE
    layout and never copied whole or raised to float32, no buffer of
    50 MB or more is narrower than a 128-lane tile, and arguments and
    temporaries stay under the cell's ceiling of 92% of the chip's
    15.75 GiB."""
    prog, cases = window_cell
    fn, args = cases[program]
    compiled = getattr(fn, "__wrapped__", fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert prog.kv_shape == (2, 2, 2561, 128, 1024)
    assert prog.widths == (4, 8, 16, 32, 64, 80)
    rings = prog.model.state_shape(prog.max_slots)
    assert rings == (3, 32, 2, 512, 1024)
    pool_bytes = int(np.prod(prog.kv_shape)) * 2
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (program != "copy")
    if program == "copy":
        assert mem.alias_size_in_bytes >= pool_bytes
        return
    assert mem.alias_size_in_bytes >= pool_bytes + int(np.prod(rings)) * 2
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 0.92 * 15.75 * 2**30
    assert set(re.findall(r"bf16\[2,2,2561,128,1024\]\{([0-9,]+):",
                          text)) == {"4,3,2,1,0"}
    assert set(re.findall(r"bf16\[3,32,2,512,1024\]\{([0-9,]+):",
                          text)) == {"4,3,2,1,0"}
    assert not re.findall(r"f32\[2,2,2561,128,1024\]", text)
    made = _materialized(text)
    assert not [m for m in made if m[0] == "copy"
                and m[2] in (prog.kv_shape, rings)]
    assert not [m for m in made if m[1] >= 50e6 and m[3] < 128]


@pytest.mark.parametrize("cell,width", [("latent", 8), ("latent", 16),
                                        ("hybrid", 8), ("hybrid", 16),
                                        ("hybrid", 32)])
def test_decode_step_keeps_its_pins_at_the_ladder_widths_between(
        cell, width, request):
    """The decode step at the ladder widths the tests above leave out
    (they take the narrowest and the widest), with the two arguments of
    PR 35: the step before's tokens (`s32[slots]`, the fifth such
    parameter, not donated: its caller may not have fetched it) and the
    rows that take them (`pred[slots]`). Pool and state are still
    donated and updated in place, in one layout, under a gigabyte of
    temporaries."""
    import jax
    import jax.numpy as jnp

    prog, cases = request.getfixturevalue(f"{cell}_cell")
    assert width in prog.widths[1:-1]
    _, args = cases["decode"]
    s = prog.max_slots
    ids = jax.ShapeDtypeStruct((s, width), jnp.int32,
                               sharding=args[-5].sharding)
    fn = prog._decode_program(width)
    compiled = getattr(fn, "__wrapped__", fn).lower(
        *args[:-5], ids, *args[-4:]).compile()
    mem = compiled.memory_analysis()
    donated = int(np.prod(prog.kv_shape)) * 2
    if prog.has_state:
        donated += sum(int(np.prod(v)) * 4 for v in
                       prog.model.state_shape(s).values())
    assert donated <= mem.alias_size_in_bytes < donated + 4 * s
    assert mem.temp_size_in_bytes < 1.0e9
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    assert len(re.findall(rf"s32\[{s}\]\S* parameter\(", entry)) == 5
    assert len(re.findall(rf"pred\[{s}\]\S* parameter\(", entry)) == 1
    pool = ",".join(str(d) for d in prog.kv_shape)
    assert set(re.findall(rf"bf16\[{pool}\]\{{([0-9,]+):", text)) == {
        ",".join(str(i) for i in reversed(range(len(prog.kv_shape))))}


@pytest.fixture(scope="module")
def granite_cell(one_chip):
    """`granite-h-chat-closed128` with shapes in the place of its
    bfloat16 weights, its K/V pool and its float32 Mamba-2 states."""
    import jax.numpy as jnp

    from benchmark.drivers import serve_granite
    from benchmark.reference import granite_hybrid as ref

    return _cell_programs(one_chip, "granite-h-chat-closed128",
                          serve_granite, ref, jnp.bfloat16)


@pytest.mark.parametrize("cell", ["conv", "window", "granite"])
def test_gqa_decode_step_reads_the_pool_in_place_and_gathers_no_window(
        cell, request):
    """The decode step of each cell whose page layers are grouped-query
    attention, at its one width (the whole window): the attention reads
    each slot's live pages straight from the pool through the paged
    kernel (nn/helpers/pallas_paged_gqa.py). The pool is donated and
    updated in place, in its ONE layout; no gathered window of
    `bf16[slots, cells, n_kv * head_dim]` (nor of pages,
    `[slots, pages, page, n_kv * head_dim]`) exists at any cell count,
    where the gather made `bf16[32,10240,1024]`, `bf16[128,2048,512]`
    and `bf16[96,2048,1024]` and their zeroed copies; and the step
    needs under a gigabyte of temporaries."""
    prog, cases = request.getfixturevalue(f"{cell}_cell")
    assert prog.from_pool
    assert prog.step_widths == (prog.pages_per_slot,)
    fn, args = cases["decode"]
    compiled = getattr(fn, "__wrapped__", fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert mem.temp_size_in_bytes < 1.0e9
    import jax.numpy as jnp

    shapes = prog.model.state_shape(prog.max_slots)
    state = sum(int(np.prod(v)) for v in (
        shapes.values() if isinstance(shapes, dict) else [shapes]))
    assert mem.alias_size_in_bytes >= int(np.prod(prog.kv_shape)) * 2 \
        + state * jnp.dtype(prog.model.state_dtype).itemsize
    pool = ",".join(str(d) for d in prog.kv_shape)
    assert set(re.findall(rf"bf16\[{pool}\]\{{([0-9,]+):", text)) == {
        ",".join(str(i) for i in reversed(range(len(prog.kv_shape))))}
    s, ps, c = prog.max_slots, prog.page_size, prog.kv_shape[-1]
    windows = [dims for op, _, dims, _ in _materialized(text)
               if op != "parameter" and dims[0] == s and dims[-1] == c
               and (len(dims) == 3 and dims[1] >= ps
                    or len(dims) == 4 and dims[2] == ps)]
    assert not windows
