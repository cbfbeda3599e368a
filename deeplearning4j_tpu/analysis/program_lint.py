"""Pass 4 — compiled-program lint: below the AST, into jaxpr/HLO.

The AST passes (jit/concurrency/conformance) see what the *source*
says; this pass sees what the *compiler* was actually handed. The gap
between delivered and peak flops hides in dtype/layout/fusion details
invisible at the Python level (Tensor Processing Primitives, arXiv
2104.05755; cuDNN primitives, arXiv 1410.0759) — so every registered
compiled program (the StepProgram single/graph/TBPTT/k-group variants,
the serving bucket programs, the decode programs, the clustering steps)
is traced/lowered here and checked against its *declared* facts:

  prog-fp32-matmul-under-policy  dot/conv operand dtypes contradict the
                                 program's declared precision_policy
  prog-unhonored-donation        donate_argnums arg absent from the
                                 executable's input-output alias map
  prog-transpose-churn           authored transpose bytes above threshold
  prog-hidden-host-transfer      outfeed/callback edges in a hot program
  prog-dead-output               computed outputs no caller consumes
  prog-excess-padding            serving pow2 bucket fill below threshold
  prog-unsharded-optimizer-state a mesh-registered (ZeRO-1) program's
                                 lowered module does not actually shard
                                 its declared optimizer-state argument
                                 (sharding annotations + alias map)

Declared facts, not guesses: the intended dtype comes from the
`precision_policy` registered on StepProgram / JitCache entries, the
intended aliasing from the jit site's own donate_argnums (read back
from `lowered.args_info`), the consumed outputs from the registration.

This module stays import-light at module scope (no jax) so the default
AST-only CLI keeps its zero-dependency contract; jax is imported only
when `run()` actually lints records (the `--programs` mode, pinned to
JAX_PLATFORMS=cpu by the CLI).

Rule ids are PINNED: `REGISTERED_PROGRAM_RULES` below is the registry
the conformance pass checks the findings.py catalog against (the same
discipline as REGISTERED_METRICS), so a rule cannot be added, renamed,
or dropped without the registry — and its tests — moving in the same
commit.
"""

from __future__ import annotations

import re
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from deeplearning4j_tpu.analysis.findings import Finding

# pinned program-rule registry (conformance pass checks catalog == this)
REGISTERED_PROGRAM_RULES = frozenset({
    "prog-fp32-matmul-under-policy",
    "prog-unhonored-donation",
    "prog-transpose-churn",
    "prog-hidden-host-transfer",
    "prog-dead-output",
    "prog-excess-padding",
    "prog-unsharded-optimizer-state",
})

# precision policies a program can declare (JitCache.policy_name)
MIXED_POLICIES = ("bf16", "f16")

MATMUL_PRIMS = ("dot_general", "conv_general_dilated")
# jaxpr primitives that move data to the host mid-program
HOST_TRANSFER_PRIMS = ("outfeed", "infeed")
HOST_TRANSFER_MARKERS = ("callback", "host_callback")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1,
                "u8": 1, "pred": 1, "i64": 8, "i32": 4, "i16": 2,
                "i8": 1, "i1": 1, "ui32": 4, "ui8": 1}


@dataclass
class Thresholds:
    """Tunable rule thresholds. Defaults are calibrated so the shipped
    program set is clean (PERF.md records the measured margins) while
    the bad fixtures fire: real backward passes legitimately transpose
    weight matrices and lax.scan bodies copy carries, so churn flags on
    the *fraction* of program traffic, not the raw count."""

    # prog-transpose-churn: flag when BOTH hold
    transpose_min_ops: int = 8
    transpose_bytes_frac: float = 0.25
    # prog-unhonored-donation: leaves smaller than this never flag
    # (a dropped scalar alias is not "silent 2x memory")
    min_donated_bytes: int = 1024
    # prog-excess-padding: minimum average bucket fill ratio
    min_bucket_fill: float = 0.5


@dataclass
class ProgramRecord:
    """One registered compiled program, with its declared facts.

    `fn` is either a `jax.jit`-wrapped callable (its own donation
    declaration is read back from `lowered.args_info`) or a plain
    callable jitted here with `donate_argnums`. `fn=None` records carry
    only registration metadata (the serving bucket fill records).
    Programs are traced and lowered, never compiled or run: every
    rule reads the jaxpr or the lowered module, which are the same
    whatever backend will compile them."""

    name: str
    fn: Optional[Callable] = None
    example_args: Tuple = ()
    example_kwargs: Dict[str, Any] = field(default_factory=dict)
    donate_argnums: Tuple[int, ...] = ()
    static_argnums: Tuple[int, ...] = ()
    precision_policy: Optional[str] = None    # "bf16" | "f16" | "f32"
    consumed_outputs: Optional[Tuple[int, ...]] = None  # None = all
    source: str = "deeplearning4j_tpu/analysis/programs.py"
    # serving bucket metadata (prog-excess-padding)
    bucket_capacity: Optional[int] = None
    bucket_rows_per_dispatch: Optional[float] = None
    # mesh-sharded registration fact (prog-unsharded-optimizer-state):
    # top-level example_args indices whose leaves the program DECLARES
    # sharded (the ZeRO-1 optimizer state). The lint verifies the
    # lowered module actually carries non-replicated sdy.sharding
    # annotations AND donation/aliasing on those arguments — a silent
    # fallback to replicated state is exactly the O(n) memory
    # regression the rule exists to catch.
    sharded_argnums: Tuple[int, ...] = ()


# ----------------------------------------------------------- jaxpr walk
def _iter_eqns(jaxpr):
    """Yield every eqn of `jaxpr` and of every sub-jaxpr reachable
    through eqn params (pjit/scan/while/cond/remat/custom_vjp...)."""
    stack = [jaxpr]
    seen = set()
    while stack:
        jx = stack.pop()
        if id(jx) in seen:
            continue
        seen.add(id(jx))
        for eqn in jx.eqns:
            yield eqn
            for v in eqn.params.values():
                vs = v if isinstance(v, (list, tuple)) else [v]
                for s in vs:
                    inner = getattr(s, "jaxpr", None)
                    if inner is not None and hasattr(inner, "eqns"):
                        stack.append(inner)      # ClosedJaxpr
                    elif hasattr(s, "eqns"):
                        stack.append(s)          # raw Jaxpr


def _matmul_ops(closed_jaxpr) -> List[Tuple[str, str, str]]:
    """(primitive, lhs_dtype, rhs_dtype) for every dot/conv eqn."""
    out = []
    for eqn in _iter_eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name in MATMUL_PRIMS and len(eqn.invars) >= 2:
            out.append((eqn.primitive.name,
                        str(eqn.invars[0].aval.dtype),
                        str(eqn.invars[1].aval.dtype)))
    return out


def _host_transfer_prims(closed_jaxpr) -> List[str]:
    out = []
    for eqn in _iter_eqns(closed_jaxpr.jaxpr):
        name = eqn.primitive.name
        if name in HOST_TRANSFER_PRIMS or any(
                m in name for m in HOST_TRANSFER_MARKERS):
            out.append(name)
    return out


# ------------------------------------------------------- HLO text maths
def _tensor_bytes(type_str: str) -> int:
    """Bytes of a StableHLO `4x8xf32`-style tensor type string."""
    parts = type_str.strip().split("x")
    if not parts:
        return 0
    dt = parts[-1]
    n = 1
    for d in parts[:-1]:
        if d.isdigit():
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


_MAIN_SIG_RE = re.compile(
    r"func\.func\s+(?:public\s+)?@main\((.*?)\)\s*->", re.S)
_ARG_RE = re.compile(r"%arg(\d+): tensor<([^>]*)>\s*(\{[^}]*\})?")
_STABLE_TRANSPOSE_RE = re.compile(
    r"stablehlo\.transpose.*?->\s*tensor<([^>]*)>")
_RESULT_RE = re.compile(r"->\s*\((.*?)\)\s*\{", re.S)


def _main_signature(lowered_text: str) -> List[Tuple[int, str, bool]]:
    """[(arg_index, tensor_type, has_alias)] of the lowered @main.
    Donation shows as `tf.aliasing_output` on single-device lowerings
    and as `jax.buffer_donor` on SPMD-partitioned ones (aliases only
    resolve at compile there) — both count as the module carrying the
    donation declaration."""
    m = _MAIN_SIG_RE.search(lowered_text)
    if m is None:
        return []
    return [(int(a), t,
             bool(attr and ("aliasing_output" in attr
                            or "buffer_donor" in attr)))
            for a, t, attr in _ARG_RE.findall(m.group(1))]


def _donated_leaf_avals(lowered) -> List[Any]:
    """ShapedArray avals of every leaf the jit site declared donated,
    read back from `lowered.args_info` — the jit site's own
    declaration, not a re-guess from the record."""
    import jax

    leaves = jax.tree_util.tree_leaves(
        lowered.args_info,
        is_leaf=lambda a: hasattr(a, "donated"))
    return [getattr(l, "aval", None) or getattr(l, "shape", None)
            for l in leaves if getattr(l, "donated", False)]


def _aval_bytes(aval) -> int:
    try:
        import numpy as np

        size = 1
        for d in aval.shape:
            size *= int(d)
        return size * np.dtype(aval.dtype).itemsize
    except Exception:   # noqa: BLE001 - unknown aval shape: assume big
        return 1 << 30


# --------------------------------------------------------------- checks
_DONATION_WARNING = "donated buffers were not usable"


def _lint_one(rec: ProgramRecord, th: Thresholds) -> List[Finding]:
    import jax

    findings: List[Finding] = []

    def finding(rule: str, message: str) -> None:
        findings.append(Finding(rule, rec.source, 1, message,
                                symbol=rec.name))

    # ---- prog-excess-padding (metadata-only records) -----------------
    if rec.bucket_capacity:
        rows = rec.bucket_rows_per_dispatch or 0.0
        fill = rows / float(rec.bucket_capacity)
        if fill < th.min_bucket_fill:
            finding(
                "prog-excess-padding",
                f"bucket capacity {rec.bucket_capacity} dispatches "
                f"{rows:g} rows on average (fill {fill:.2f} < "
                f"{th.min_bucket_fill:.2f}) — the MXU runs mostly "
                f"padding")
    if rec.fn is None:
        return findings

    jitted = rec.fn
    if not hasattr(jitted, "lower"):
        jitted = jax.jit(jitted, donate_argnums=rec.donate_argnums,
                         static_argnums=rec.static_argnums)

    # ONE trace serves every rule: jaxpr + out tree from the Traced,
    # the lowered module (donation and sharding attrs) from it
    with warnings.catch_warnings(record=True) as wrec:
        warnings.simplefilter("always")
        traced = jitted.trace(*rec.example_args, **rec.example_kwargs)
        lowered = traced.lower()
    closed = traced.jaxpr
    out_shape = traced.out_info
    lowered_text = lowered.as_text()

    # ---- prog-unhonored-donation -------------------------------------
    # jax reports unmatched donations at lowering; the lowered module's
    # aliasing attributes are the accepted set. Both are checked: a
    # warning names the dropped buffers, a donation declaration whose
    # accepted set is empty is the catastrophic (platform/backend) case.
    donated = [a for a in _donated_leaf_avals(lowered)
               if a is not None and _aval_bytes(a) >= th.min_donated_bytes]
    dropped = [str(w.message) for w in wrec
               if _DONATION_WARNING in str(w.message)]
    sig = _main_signature(lowered_text)
    aliased = sum(1 for _, _, has in sig if has)
    if dropped:
        detail = dropped[0].splitlines()[0]
        finding(
            "prog-unhonored-donation",
            f"donated argument(s) absent from the executable's "
            f"input-output alias map ({detail}) — the caller loses the "
            f"buffer AND pays the copy")
    elif donated and aliased == 0:
        finding(
            "prog-unhonored-donation",
            f"{len(donated)} donated buffer(s) declared but the "
            f"lowered module carries no aliasing attribute at all — "
            f"donation is silently ignored on this path")

    # ---- prog-unsharded-optimizer-state ------------------------------
    if rec.sharded_argnums:
        _check_sharded_args(rec, lowered_text, finding)

    # ---- prog-fp32-matmul-under-policy -------------------------------
    if rec.precision_policy in MIXED_POLICIES:
        ops = _matmul_ops(closed)
        bad = [o for o in ops if "float32" in (o[1], o[2])
               or "float64" in (o[1], o[2])]
        if bad:
            prim, lhs, rhs = bad[0]
            finding(
                "prog-fp32-matmul-under-policy",
                f"{len(bad)} of {len(ops)} dot/conv op(s) compute in "
                f"f32 under the declared {rec.precision_policy} "
                f"policy (first: {prim} {lhs} x {rhs})")

    # ---- prog-hidden-host-transfer -----------------------------------
    host = _host_transfer_prims(closed)
    if not host and "custom_call" in lowered_text:
        host = [m.group(0).split("@")[-1] for m in re.finditer(
            r"stablehlo\.custom_call\s*@\S*callback\S*", lowered_text)]
    if host:
        finding(
            "prog-hidden-host-transfer",
            f"host-transfer edge(s) inside the program: "
            f"{', '.join(sorted(set(host))[:4])} — every call blocks "
            f"the device on the host")

    # ---- prog-dead-output --------------------------------------------
    if rec.consumed_outputs is not None:
        _dead_outputs(rec, closed, out_shape, finding)

    # ---- prog-transpose-churn ----------------------------------------
    # Authored stablehlo.transpose bytes against the program signature.
    # The lowered module is what the model's code asked for and is the
    # same for every backend; the transposes and copies a backend's
    # compiler adds are its own layout decisions (the CPU compiler's
    # say nothing about the chip's), so they are read from a profile
    # of the chip, not here.
    trs = _STABLE_TRANSPOSE_RE.findall(lowered_text)
    churn = sum(_tensor_bytes(t) for t in trs)
    total = _signature_bytes(lowered_text)
    if (len(trs) >= th.transpose_min_ops and total
            and churn / total >= th.transpose_bytes_frac):
        finding(
            "prog-transpose-churn",
            f"{len(trs)} authored transpose(s) move {churn} bytes "
            f"= {churn / total:.0%} of program I/O (threshold "
            f"{th.transpose_bytes_frac:.0%}) — layout thrash")
    return findings


def _arg_segments(lowered_text: str) -> Dict[int, str]:
    """{arg_index: raw attribute text} of the lowered @main signature.
    Attribute dicts nest braces inside sdy.sharding values
    (`#sdy.sharding<@mesh, [{"dp"}, {}]>`), so the signature is split
    on `%arg` boundaries instead of brace-matched."""
    m = _MAIN_SIG_RE.search(lowered_text)
    if m is None:
        return {}
    out: Dict[int, str] = {}
    parts = m.group(1).split("%arg")
    for part in parts[1:]:
        idx_end = 0
        while idx_end < len(part) and part[idx_end].isdigit():
            idx_end += 1
        if idx_end == 0:
            continue
        out[int(part[:idx_end])] = part
    return out


# Shardy's argument annotation: `sdy.sharding = #sdy.sharding<@mesh,
# [{"dp"}, {}]>` — one `{...}` per dimension, naming the mesh axes that
# dimension is split over; `[{}, {}]` is replicated
_SDY_DIMS_RE = re.compile(r"sdy\.sharding\s*=\s*#sdy\.sharding<@\w+,"
                          r"\s*\[([^\]]*)\]")


def _axis_sharded(seg: str) -> bool:
    """True when an @main argument's attribute text shards at least
    one dimension over a named mesh axis."""
    m = _SDY_DIMS_RE.search(seg)
    return m is not None and '"' in m.group(1)


def _check_sharded_args(rec: ProgramRecord, lowered_text: str,
                        finding) -> None:
    """prog-unsharded-optimizer-state: every example leaf of a
    declared `sharded_argnums` argument that IS sharded at the call
    site must appear in the lowered @main with a non-replicated
    sdy.sharding annotation AND donation/aliasing; a declaration with
    no sharded leaf at all is the catastrophic silent-replication
    case."""
    import jax

    segs = _arg_segments(lowered_text)
    offsets = []
    pos = 0
    for a in rec.example_args:
        n = len(jax.tree_util.tree_leaves(a))
        offsets.append((pos, pos + n))
        pos += n

    def leaf_sharded(leaf) -> bool:
        sh = getattr(leaf, "sharding", None)
        return sh is not None and not sh.is_fully_replicated

    for argnum in rec.sharded_argnums:
        if argnum >= len(offsets):
            continue
        lo, hi = offsets[argnum]
        leaves = jax.tree_util.tree_leaves(rec.example_args[argnum])
        expected = [lo + i for i, leaf in enumerate(leaves)
                    if leaf_sharded(leaf)]
        if not expected:
            finding(
                "prog-unsharded-optimizer-state",
                f"argument {argnum} is declared mesh-sharded "
                f"optimizer state but NO leaf of it is sharded at the "
                f"call site — the state is silently replicated (n x "
                f"the memory the registration promises to shard)")
            continue
        unannotated = []
        unaliased = []
        for i in expected:
            seg = segs.get(i, "")
            if not _axis_sharded(seg):
                unannotated.append(i)
            elif "buffer_donor" not in seg \
                    and "aliasing_output" not in seg:
                unaliased.append(i)
        if unannotated:
            finding(
                "prog-unsharded-optimizer-state",
                f"{len(unannotated)} of {len(expected)} sharded "
                f"optimizer-state leaf/leaves of argument {argnum} "
                f"carry no device sharding annotation in the lowered "
                f"module — XLA receives them replicated")
        elif unaliased:
            finding(
                "prog-unsharded-optimizer-state",
                f"{len(unaliased)} of {len(expected)} sharded "
                f"optimizer-state leaf/leaves of argument {argnum} "
                f"are sharded but not donated/aliased — the sharded "
                f"update still pays a full state copy per step")


def _dead_outputs(rec: ProgramRecord, closed, out_shape,
                  finding) -> None:
    """Outputs the registration declares unconsumed, when their leaves
    are genuinely computed (not input pass-throughs or literals)."""
    import jax

    if not isinstance(out_shape, (tuple, list)):
        return
    invars = set(map(id, closed.jaxpr.invars))
    offsets = []
    pos = 0
    for child in out_shape:
        n = len(jax.tree_util.tree_leaves(child))
        offsets.append((pos, pos + n))
        pos += n
    consumed = set(rec.consumed_outputs)
    for i, (lo, hi) in enumerate(offsets):
        if i in consumed:
            continue
        leaves = closed.jaxpr.outvars[lo:hi]
        computed = [v for v in leaves
                    if type(v).__name__ != "Literal"
                    and id(v) not in invars]
        if computed:
            finding(
                "prog-dead-output",
                f"output {i} ({hi - lo} leaf/leaves) is computed but "
                f"no caller consumes it — wasted flops and transfer")


def _signature_bytes(lowered_text: str) -> int:
    """Sum of @main argument + result tensor bytes — the denominator
    of the churn fraction."""
    total = sum(_tensor_bytes(t) for _, t, _ in
                _main_signature(lowered_text))
    m = _RESULT_RE.search(lowered_text)
    if m:
        total += sum(_tensor_bytes(t) for t in
                     re.findall(r"tensor<([^>]*)>", m.group(1)))
    return total


# ------------------------------------------------------------------ run
def run(records: Sequence[ProgramRecord],
        thresholds: Optional[Thresholds] = None) -> List[Finding]:
    """Lint every record; findings are fingerprintable (file = the
    program's owning source, symbol = the program name, line-free
    message) so the baseline/pragma machinery applies unchanged."""
    th = thresholds or Thresholds()
    findings: List[Finding] = []
    for rec in records:
        findings.extend(_lint_one(rec, th))
    findings.sort(key=lambda f: (f.file, f.symbol, f.rule))
    return findings
