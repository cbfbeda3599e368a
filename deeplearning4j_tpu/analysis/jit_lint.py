"""Pass 1 — JIT / recompile hygiene.

Walks every function reachable from the step/serving hot paths (the
`fit`/`output`/`predict` entry points, HTTP handlers, and every
`threading.Thread` target — the batcher/completion/watchdog/flush
thread bodies) and flags the hazards that erase compiled-path wins:

  jit-host-sync            blocking device→host sync on a hot path
  jit-missing-donate       step-shaped jax.jit without buffer donation
  jit-traced-python-scalar shape-derived value fed to a traced arg
  jit-use-after-donation   donated buffer read after the donating call

Reachability is a real call graph where the AST can prove one and a
name-based over-approximation where it cannot (ROADMAP carried-forward
gap, closed by the engine's stable entry points):

  - roots: the `fit`/`output`/`predict`/HTTP-handler names, every
    `threading.Thread` target, and the engine's StepProgram/StepHarness
    entry points by exact qualname (`ROOT_QUALNAMES`) — the compiled
    step path hangs off those whatever the surrounding loop is named;
  - jit sites include every spelling in the tree: `jax.jit(f, ...)`,
    `@jax.jit`, `@partial(jax.jit, ...)` (plain or
    functools-qualified), the chained `functools.partial(jax.jit,
    ...)(f)` call, and module-level aliases
    `jit = functools.partial(jax.jit, ...)` whose call/decorator
    sites inherit the partial's donate/static kwargs;
  - `self.m()` edges resolve through a class-hierarchy map (the class,
    its ancestors, and its descendants by base-name linking — virtual
    dispatch included) to the actual method bodies;
  - everything else falls back to the old rule: an edge `f -> g`
    exists when `f`'s body calls *any* function named `g`. False
    reachability costs a pragma; a missed hot function costs a
    recompile nobody traced — so unresolvable calls stay
    over-approximate, never dropped.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from deeplearning4j_tpu.analysis.findings import (
    Finding,
    pragma_allows,
)
from deeplearning4j_tpu.analysis.source import (
    SourceFile,
    call_name,
    dotted,
)

# entry points of the step/serving hot paths (thread targets are added
# dynamically — every Thread body is a hot path in this codebase)
ROOT_NAMES = {"fit", "output", "predict", "do_POST", "do_GET"}

# the engine's stable compiled-step entry points, rooted by exact
# qualname: every fit loop now funnels through these, so the walk no
# longer depends on what the surrounding loop method happens to be
# called (ROADMAP: "real call-graph edges once a StepProgram
# abstraction gives it stable entry points")
ROOT_QUALNAMES = {
    "deeplearning4j_tpu/engine/step_program.py::StepProgram.run",
    "deeplearning4j_tpu/engine/step_program.py::StepProgram.run_batch",
    "deeplearning4j_tpu/engine/step_program.py::StepProgram.run_group",
    "deeplearning4j_tpu/engine/harness.py::StepHarness.guarded",
    "deeplearning4j_tpu/engine/harness.py::StepHarness.step_scope",
    "deeplearning4j_tpu/engine/harness.py::StepHarness.session",
    "deeplearning4j_tpu/engine/harness.py::StepHarness.check_preemption",
}

STEP_SHAPED = re.compile(r"step|update|slab")

# files whose host syncs are the *instrument* (the sanctioned sites the
# tentpole names: the StepPhaseProfiler's deliberate sampled sync)
SANCTIONED_SYNC_FILES = ("observability/perf.py",)


@dataclass
class JitSite:
    file: SourceFile
    line: int
    wrapped_name: str
    bound_to: Optional[str]
    donate: bool
    static: bool
    donate_argnums: Optional[Tuple[int, ...]] = None


@dataclass
class _FuncInfo:
    sf: SourceFile
    node: ast.FunctionDef
    qualname: str
    calls: Set[str] = field(default_factory=set)
    self_calls: Set[str] = field(default_factory=set)
    owner_class: Optional[str] = None
    thread_targets: Set[str] = field(default_factory=set)


def _jit_kwargs(call: ast.Call) -> Tuple[bool, bool, Optional[Tuple[int, ...]]]:
    donate = static = False
    nums: Optional[Tuple[int, ...]] = None
    for kw in call.keywords:
        if kw.arg in ("donate_argnums", "donate_argnames"):
            donate = True
            try:
                v = ast.literal_eval(kw.value)
                if isinstance(v, int):
                    nums = (v,)
                elif isinstance(v, (tuple, list)) and all(
                        isinstance(x, int) for x in v):
                    nums = tuple(v)
            except (ValueError, SyntaxError):
                nums = None
        if kw.arg in ("static_argnums", "static_argnames"):
            static = True
    return donate, static, nums


def _wrapped_name(expr) -> str:
    """Name of the function a jax.jit call wraps, through one level of
    combinator (jax.shard_map(worker, ...), value_and_grad(f))."""
    if isinstance(expr, ast.Name):
        return expr.id
    if isinstance(expr, ast.Attribute):
        return expr.attr
    if isinstance(expr, ast.Lambda):
        return "<lambda>"
    if isinstance(expr, ast.Call) and expr.args:
        return _wrapped_name(expr.args[0])
    return ""


def _is_jax_jit(func) -> bool:
    d = dotted(func)
    return d == "jax.jit" or d == "jit" or d.endswith(".jit")


def _partial_jit_aliases(sf: SourceFile) -> Dict[str, ast.Call]:
    """Module-level `jit = functools.partial(jax.jit, ...)` aliases:
    name -> the partial() Call carrying the jit kwargs. Call sites of
    the alias are jit sites with those kwargs."""
    aliases: Dict[str, ast.Call] = {}
    for node in sf.tree.body:
        if not (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)):
            continue
        c = node.value
        if call_name(c) == "partial" and c.args \
                and _is_jax_jit(c.args[0]):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    aliases[t.id] = c
    return aliases


def collect_jit_sites(sources: List[SourceFile]) -> List[JitSite]:
    sites: List[JitSite] = []
    for sf in sources:
        parents: Dict[int, ast.AST] = {}
        for node in ast.walk(sf.tree):
            for child in ast.iter_child_nodes(node):
                parents[id(child)] = node
        aliases = _partial_jit_aliases(sf)
        for node in ast.walk(sf.tree):
            # call form: jax.jit(X, ...) — possibly partial(jax.jit, ...)
            # (plain or functools-qualified), or a module-level
            # partial-alias call site `step = jit(step_fn)`
            if isinstance(node, ast.Call):
                jit_call = None
                alias_call = None
                wrapped = ""
                if _is_jax_jit(node.func):
                    jit_call = node
                    wrapped = _wrapped_name(node.args[0]) \
                        if node.args else ""
                elif (call_name(node) == "partial" and node.args
                      and _is_jax_jit(node.args[0])):
                    jit_call = node
                    wrapped = ""          # decorator form fills it in
                elif (isinstance(node.func, ast.Name)
                      and node.func.id in aliases):
                    jit_call = node
                    alias_call = aliases[node.func.id]
                    wrapped = _wrapped_name(node.args[0]) \
                        if node.args else ""
                elif (isinstance(node.func, ast.Call)
                      and call_name(node.func) == "partial"
                      and node.func.args
                      and _is_jax_jit(node.func.args[0])):
                    # chained form: functools.partial(jax.jit, ...)(f)
                    jit_call = node
                    alias_call = node.func
                    wrapped = _wrapped_name(node.args[0]) \
                        if node.args else ""
                if jit_call is None:
                    continue
                donate, static, nums = _jit_kwargs(jit_call)
                if alias_call is not None:
                    # kwargs split between the partial and the call site
                    a_donate, a_static, a_nums = _jit_kwargs(alias_call)
                    donate = donate or a_donate
                    static = static or a_static
                    nums = nums if nums is not None else a_nums
                # decorator? the parent chain reaches a FunctionDef
                # whose decorator_list contains us
                parent = parents.get(id(node))
                bound_to: Optional[str] = None
                if isinstance(parent, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)) \
                        and node in parent.decorator_list:
                    wrapped = parent.name
                    bound_to = parent.name
                elif isinstance(parent, ast.Assign) and wrapped:
                    t = parent.targets[0]
                    if isinstance(t, ast.Name):
                        bound_to = t.id
                    elif isinstance(t, ast.Attribute):
                        bound_to = t.attr
                if not wrapped:
                    continue
                sites.append(JitSite(sf, node.lineno, wrapped, bound_to,
                                     donate, static, nums))
            # bare @jax.jit decorator (an Attribute, not a Call) — or a
            # bare @<alias> decorator carrying the partial's kwargs
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if isinstance(dec, ast.Name) and dec.id in aliases:
                        donate, static, nums = _jit_kwargs(
                            aliases[dec.id])
                        sites.append(JitSite(sf, node.lineno, node.name,
                                             node.name, donate, static,
                                             nums))
                    elif not isinstance(dec, ast.Call) \
                            and _is_jax_jit(dec):
                        sites.append(JitSite(sf, node.lineno, node.name,
                                             node.name, False, False))
    return sites


# ------------------------------------------------------- reachability
def _is_self_call(node: ast.Call) -> bool:
    return (isinstance(node.func, ast.Attribute)
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id == "self")


class _ClassGraph:
    """Class-hierarchy map for real `self.m()` edge resolution.

    Classes link by base NAME across the whole package (no imports are
    executed), so `self.m()` resolves to the method bodies of the
    class, its ancestors, and its descendants — virtual dispatch over
    overrides included. Name collisions merge conservatively (both
    hierarchies are related)."""

    def __init__(self, sources: List[SourceFile]):
        # class name -> [{bases, methods{name: node-qualname}}]
        self.entries: Dict[str, List[dict]] = {}
        self.derived: Dict[str, Set[str]] = {}
        for sf in sources:
            for node in ast.walk(sf.tree):
                if not isinstance(node, ast.ClassDef):
                    continue
                bases = [dotted(b).split(".")[-1] for b in node.bases]
                methods = {
                    ch.name: f"{sf.rel}::{sf.qualname_of(ch)}"
                    for ch in node.body
                    if isinstance(ch, (ast.FunctionDef,
                                       ast.AsyncFunctionDef))}
                self.entries.setdefault(node.name, []).append(
                    {"bases": [b for b in bases if b],
                     "methods": methods})
                for b in bases:
                    if b:
                        self.derived.setdefault(b, set()).add(node.name)

    def related(self, cls: str) -> Set[str]:
        """The class plus ancestors and descendants by name-linking."""
        out: Set[str] = set()
        frontier = [cls]
        while frontier:      # ancestors
            c = frontier.pop()
            if c in out:
                continue
            out.add(c)
            for entry in self.entries.get(c, ()):
                frontier.extend(entry["bases"])
        frontier = [cls]
        down: Set[str] = set()
        while frontier:      # descendants
            c = frontier.pop()
            if c in down:
                continue
            down.add(c)
            frontier.extend(self.derived.get(c, ()))
        return out | down

    def resolve(self, cls: str, method: str) -> List[str]:
        """Qualnames of every `method` body `self.method()` can reach
        from `cls` (empty when the hierarchy defines none — the caller
        falls back to name matching)."""
        return [entry["methods"][method]
                for c in self.related(cls)
                for entry in self.entries.get(c, ())
                if method in entry["methods"]]


def build_reachable(sources: List[SourceFile]) -> Set[str]:
    """Set of function qualnames reachable from the hot-path roots."""
    funcs: List[_FuncInfo] = []
    by_name: Dict[str, List[_FuncInfo]] = {}
    by_qual: Dict[str, _FuncInfo] = {}
    classes = _ClassGraph(sources)
    for sf in sources:
        # AST parents of each function: methods are direct ClassDef
        # children (nested `outer.inner` functions are NOT methods)
        method_owner: Dict[int, str] = {}
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.ClassDef):
                for ch in node.body:
                    if isinstance(ch, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                        method_owner[id(ch)] = node.name
        for node in sf.functions():
            fi = _FuncInfo(sf, node, f"{sf.rel}::{sf.qualname_of(node)}",
                           owner_class=method_owner.get(id(node)))
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call):
                    n = call_name(sub)
                    if n:
                        if _is_self_call(sub):
                            fi.self_calls.add(n)
                        else:
                            fi.calls.add(n)
                    if n == "Thread":
                        for kw in sub.keywords:
                            if kw.arg == "target":
                                tn = dotted(kw.value).split(".")[-1]
                                if tn:
                                    fi.thread_targets.add(tn)
            funcs.append(fi)
            by_name.setdefault(node.name, []).append(fi)
            by_qual[fi.qualname] = fi

    thread_roots: Set[str] = set()
    for fi in funcs:
        thread_roots |= fi.thread_targets
    roots = [fi for fi in funcs
             if fi.node.name in ROOT_NAMES
             or fi.node.name in thread_roots
             or fi.qualname in ROOT_QUALNAMES]

    seen: Set[str] = set()
    frontier = list(roots)
    while frontier:
        fi = frontier.pop()
        if fi.qualname in seen:
            continue
        seen.add(fi.qualname)
        # real edges: self.m() through the class hierarchy when it
        # resolves; name-based fallback when it does not
        for called in fi.self_calls:
            targets = (classes.resolve(fi.owner_class, called)
                       if fi.owner_class else [])
            if targets:
                for q in targets:
                    callee = by_qual.get(q)
                    if callee is not None and callee.qualname not in seen:
                        frontier.append(callee)
                continue
            for callee in by_name.get(called, ()):
                if callee.qualname not in seen:
                    frontier.append(callee)
        for called in fi.calls | fi.thread_targets:
            for callee in by_name.get(called, ()):
                if callee.qualname not in seen:
                    frontier.append(callee)
    return seen


# ------------------------------------------------------------- checks
def _host_sync_marker(node: ast.Call) -> Optional[str]:
    f = node.func
    if isinstance(f, ast.Attribute):
        if f.attr == "item" and not node.args:
            return ".item()"
        if f.attr == "tolist" and not node.args:
            return ".tolist()"
        if f.attr == "block_until_ready":
            return "block_until_ready"
        if f.attr == "device_get":
            return "jax.device_get"
    if isinstance(f, ast.Name) and f.id == "float" and len(node.args) == 1:
        a = node.args[0]
        if isinstance(a, ast.Call) and isinstance(a.func, ast.Attribute) \
                and a.func.attr == "score":
            return "float(x.score())"
    if isinstance(f, ast.Name) and f.id == "block_until_ready":
        return "block_until_ready"
    return None


def run(sources: List[SourceFile]) -> List[Finding]:
    findings: List[Finding] = []
    reachable = build_reachable(sources)
    sites = collect_jit_sites(sources)

    # --- jit-missing-donate -------------------------------------------
    for s in sites:
        if not s.donate and STEP_SHAPED.search(s.wrapped_name or ""):
            line = s.line
            if pragma_allows(s.file.allow, line, "jit-missing-donate"):
                continue
            findings.append(Finding(
                "jit-missing-donate", s.file.rel, line,
                f"jax.jit of step-shaped '{s.wrapped_name}' without "
                f"donate_argnums — updated buffers copy instead of "
                f"aliasing",
                symbol=s.wrapped_name))

    # per-module jitted identifiers
    jitted_by_file: Dict[str, Dict[str, JitSite]] = {}
    for s in sites:
        if s.bound_to:
            jitted_by_file.setdefault(s.file.rel, {})[s.bound_to] = s

    for sf in sources:
        jitted = jitted_by_file.get(sf.rel, {})
        in_sanctioned = any(sf.rel.endswith(x)
                            for x in SANCTIONED_SYNC_FILES)
        for fnode in sf.functions():
            qual = f"{sf.rel}::{sf.qualname_of(fnode)}"
            hot = qual in reachable

            # --- jit-host-sync ----------------------------------------
            if hot and not in_sanctioned:
                for sub in ast.walk(fnode):
                    if not isinstance(sub, ast.Call):
                        continue
                    marker = _host_sync_marker(sub)
                    if marker is None:
                        continue
                    if pragma_allows(sf.allow, sub.lineno,
                                     "jit-host-sync"):
                        continue
                    findings.append(Finding(
                        "jit-host-sync", sf.rel, sub.lineno,
                        f"{marker} forces a device->host sync on a "
                        f"hot path (reachable from "
                        f"{'/'.join(sorted(ROOT_NAMES))} or a thread "
                        f"body)",
                        symbol=sf.qualname_of(fnode)))

            # --- jit-traced-python-scalar -----------------------------
            for sub in ast.walk(fnode):
                if not isinstance(sub, ast.Call):
                    continue
                cn = call_name(sub)
                site = jitted.get(cn)
                if site is None or site.static:
                    continue
                for arg in sub.args:
                    label = _scalar_shaped(arg)
                    if label is None:
                        continue
                    if pragma_allows(sf.allow, sub.lineno,
                                     "jit-traced-python-scalar"):
                        continue
                    findings.append(Finding(
                        "jit-traced-python-scalar", sf.rel, sub.lineno,
                        f"{label} passed as a traced argument to "
                        f"jitted '{cn}' — each new value retraces "
                        f"and recompiles",
                        symbol=sf.qualname_of(fnode)))

            # --- jit-use-after-donation -------------------------------
            findings.extend(_use_after_donation(sf, fnode, jitted))
    return findings


def _scalar_shaped(arg) -> Optional[str]:
    if isinstance(arg, ast.Subscript) \
            and isinstance(arg.value, ast.Attribute) \
            and arg.value.attr == "shape":
        return f"{dotted(arg.value)}[...]"
    if isinstance(arg, ast.Attribute) and arg.attr in ("ndim", "size"):
        return dotted(arg)
    if isinstance(arg, ast.Call) and isinstance(arg.func, ast.Name) \
            and arg.func.id == "len":
        return "len(...)"
    return None


def _use_after_donation(sf: SourceFile, fnode,
                        jitted: Dict[str, "JitSite"]) -> List[Finding]:
    donating = {k: s for k, s in jitted.items() if s.donate}
    if not donating:
        return []
    loads: List[Tuple[int, str]] = []
    stores: List[Tuple[int, str]] = []
    calls: List[Tuple[int, str, ast.Call, Set[str]]] = []
    for sub in ast.walk(fnode):
        if isinstance(sub, ast.Name):
            if isinstance(sub.ctx, ast.Load):
                loads.append((sub.lineno, sub.id))
            else:
                stores.append((sub.lineno, sub.id))
        if isinstance(sub, ast.Assign) and isinstance(sub.value, ast.Call):
            cn = call_name(sub.value)
            if cn in donating:
                targets: Set[str] = set()
                for t in sub.targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            targets.add(n.id)
                calls.append((sub.lineno, cn, sub.value, targets))

    findings: List[Finding] = []
    for call_line, cn, call, rebound in calls:
        site = donating[cn]
        positions = site.donate_argnums
        args = call.args
        donated_names = []
        for i, a in enumerate(args):
            if positions is not None and i not in positions:
                continue
            if isinstance(a, ast.Name):
                donated_names.append(a.id)
        for name in donated_names:
            if name in rebound:
                continue
            later_loads = [ln for ln, nm in loads
                           if nm == name and ln > call_line]
            for ln in sorted(later_loads):
                restored = any(sl for sl, nm in stores
                               if nm == name and call_line < sl <= ln)
                if restored:
                    break
                if pragma_allows(sf.allow, ln, "jit-use-after-donation"):
                    break
                findings.append(Finding(
                    "jit-use-after-donation", sf.rel, ln,
                    f"'{name}' was donated to jitted '{cn}' and read "
                    f"again without being rebound — the buffer is "
                    f"invalid after donation",
                    symbol=sf.qualname_of(fnode)))
                break
    return findings
