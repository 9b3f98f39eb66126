"""graftlint rule engine: trace-safety + distributed-correctness rules.

| rule              | set it runs on        | hazard                               |
|-------------------|-----------------------|--------------------------------------|
| host-sync         | hot (dispatch path)   | device→host pull stalls the pipeline |
| retrace-hazard    | everything            | per-call compiles / cache misses     |
| jit-purity        | traced                | value baked at trace time / silent   |
| numpy-on-tracer   | traced                | TracerArrayConversionError / consts  |
| lock-discipline   | threaded modules      | unguarded shared mutable state       |
| monotonic-clock   | everything            | wall clock in duration arithmetic    |
| cost-analysis-off-hot-path | traced + hot | HLO cost walk / trace export per batch |
| step-wiring       | nn/ + parallel/       | donated-carry jit built outside nn/step_program.py |
| use-after-donate  | dataflow (donations)  | read of a buffer donated into a step |
| collective-consistency | shard_map bodies | rank-divergent / axis-mismatched collectives |
| durable-store-protocol | dataflow (paths) | raw (non-atomic) writes on durable store paths |

The last three run on the interprocedural field-sensitive dataflow layer
(``Index.dataflow``) and live in :mod:`analysis.rules_distributed`; this
module re-exports them through :data:`ALL_RULES` / :func:`run` so the CLI
and baseline treat every rule uniformly.

Each checker yields ``engine.Finding`` objects; inline
``# graftlint: disable=<rule>`` suppressions are honored by
``Index.make_finding`` (same line or the line above).
"""

from __future__ import annotations

import ast
from typing import Callable, List, Optional, Sequence, Set, Tuple

from deeplearning4j_tpu.analysis.engine import (
    MUTATOR_METHODS,
    Finding,
    FunctionInfo,
    Index,
    dotted_name,
    is_jit_call,
    own_nodes,
)
from deeplearning4j_tpu.analysis.rules_distributed import (
    DISTRIBUTED_RULES,
    run_distributed,
)

__all__ = ["ALL_RULES", "run"]

ALL_RULES = (
    "host-sync",
    "retrace-hazard",
    "jit-purity",
    "numpy-on-tracer",
    "lock-discipline",
    "monotonic-clock",
    "cost-analysis-off-hot-path",
    "step-wiring",
) + DISTRIBUTED_RULES

# numpy calls that only touch metadata — safe on tracers and device arrays
NP_METADATA_OK = {
    "shape", "ndim", "size", "dtype", "result_type", "issubdtype",
    "broadcast_shapes", "iterable", "isscalar",
}

IMPURE_CALLS = {
    "time.time": "time.time() is baked in at trace time (every later call "
                 "reuses the traced value); use a traced input instead",
    "time.time_ns": "time.time_ns() is baked in at trace time",
    "time.monotonic": "time.monotonic() is baked in at trace time",
    "datetime.datetime.now": "datetime.now() is baked in at trace time",
    "datetime.datetime.utcnow": "datetime.utcnow() is baked in at trace time",
}


def run(index: Index, rules: Optional[Sequence[str]] = None) -> List[Finding]:
    active = set(rules) if rules else set(ALL_RULES)
    out: List[Finding] = []
    if "host-sync" in active:
        out += _rule_host_sync(index)
    if "retrace-hazard" in active:
        out += _rule_retrace_hazard(index)
    if "jit-purity" in active:
        out += _rule_jit_purity(index)
    if "numpy-on-tracer" in active:
        out += _rule_numpy_on_tracer(index)
    if "lock-discipline" in active:
        out += _rule_lock_discipline(index)
    if "monotonic-clock" in active:
        out += _rule_monotonic_clock(index)
    if "cost-analysis-off-hot-path" in active:
        out += _rule_cost_analysis_off_hot_path(index)
    if "step-wiring" in active:
        out += _rule_step_wiring(index)
    if active & set(DISTRIBUTED_RULES):
        out += run_distributed(index, sorted(active & set(DISTRIBUTED_RULES)))
    # drop duplicates (one line can trip a rule through several sub-checks)
    seen: Set[tuple] = set()
    uniq = []
    for f in out:
        key = (f.rule, f.path, f.line, f.func)
        if key not in seen:
            seen.add(key)
            uniq.append(f)
    return uniq


# ---------------------------------------------------------------------------
# taint: which local names hold device values / tracer values
# ---------------------------------------------------------------------------


def _device_taint(
    fi: FunctionInfo, index: Index, seed_params: bool,
) -> Tuple[Set[str], Callable[[ast.AST], bool]]:
    """Names in ``fi`` that plausibly hold device/tracer values — parameters
    (for traced functions), plus anything assigned (or loop-iterated) from a
    jax/jnp call, a jitted-callable dispatch, or a call into the hot /
    device-source sets — and a predicate testing whether an expression
    involves such a value. Two linear passes over the body reach a fixpoint
    for ordinary straight-line reassignment chains."""
    tainted: Set[str] = set(fi.params) if seed_params else set()

    def call_is_source(call: ast.Call) -> bool:
        f = call.func
        if isinstance(f, ast.Attribute) and f.attr in index.jit_names:
            return True
        if (isinstance(f, ast.Name) and f.id in index.jit_names
                and f.id in fi.module.global_names):
            return True
        d = dotted_name(f, fi.module)
        if d and d.startswith("jax."):
            return True
        return any(c in index.hot or c in index.device_sources
                   for c in index.resolve_call(fi, f))

    def expr_tainted(expr: ast.AST) -> bool:
        for n in ast.walk(expr):
            if (isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)
                    and n.id in tainted):
                return True
            if isinstance(n, ast.Call) and call_is_source(n):
                return True
        return False

    def taint_target(t: ast.AST):
        for n in ast.walk(t):
            if isinstance(n, ast.Name):
                tainted.add(n.id)

    nodes = own_nodes(fi.node)
    for _ in range(2):
        before = len(tainted)
        for node in nodes:
            if isinstance(node, ast.Assign) and expr_tainted(node.value):
                for t in node.targets:
                    taint_target(t)
            elif isinstance(node, ast.AnnAssign) and node.value is not None \
                    and expr_tainted(node.value):
                taint_target(node.target)
            elif isinstance(node, ast.AugAssign) and expr_tainted(node.value):
                taint_target(node.target)
            elif isinstance(node, (ast.For, ast.AsyncFor)) and expr_tainted(node.iter):
                taint_target(node.target)
        if len(tainted) == before:
            break
    return tainted, expr_tainted


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------


def _rule_host_sync(index: Index) -> List[Finding]:
    out = []
    for q in sorted(index.hot):
        fi = index.functions[q]
        _, tainted = _device_taint(fi, index, seed_params=False)
        for node in own_nodes(fi.node):
            if not isinstance(node, ast.Call):
                continue
            d = dotted_name(node.func, fi.module)
            f = None
            if d == "jax.device_get":
                f = index.make_finding(
                    "host-sync", fi, node.lineno,
                    "jax.device_get in jit dispatch path: blocking "
                    "device→host transfer")
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr == "item" and not node.args
                  and tainted(node.func.value)):
                f = index.make_finding(
                    "host-sync", fi, node.lineno,
                    ".item() on a device value in the jit dispatch path: "
                    "synchronous host round-trip per call")
            elif d in ("numpy.asarray", "numpy.array", "numpy.copy") \
                    and node.args and any(tainted(a) for a in node.args):
                f = index.make_finding(
                    "host-sync", fi, node.lineno,
                    f"{d.replace('numpy', 'np')} on a device value in the "
                    "jit dispatch path: pulls the array back to host")
            elif (isinstance(node.func, ast.Name)
                  and node.func.id in ("float", "int", "bool")
                  and node.args and tainted(node.args[0])):
                f = index.make_finding(
                    "host-sync", fi, node.lineno,
                    f"{node.func.id}() on a device value in the jit dispatch "
                    "path: blocks until the executable finishes")
            if f:
                out.append(f)
    return out


# ---------------------------------------------------------------------------
# retrace-hazard
# ---------------------------------------------------------------------------


def _static_spec_is_literal(v: ast.AST) -> bool:
    if isinstance(v, ast.Constant):
        return isinstance(v.value, (int, str))
    if isinstance(v, (ast.Tuple, ast.List)):
        return all(isinstance(e, ast.Constant) and isinstance(e.value, (int, str))
                   for e in v.elts)
    return False


def _rule_retrace_hazard(index: Index) -> List[Finding]:
    out = []

    def check_jit_call(fi: FunctionInfo, call: ast.Call, loop_depth: int):
        if loop_depth > 0:
            f = index.make_finding(
                "retrace-hazard", fi, call.lineno,
                "jax.jit constructed inside a loop: a fresh jit wrapper per "
                "iteration compiles (and caches) separately every time")
            if f:
                out.append(f)
        for kw in call.keywords:
            if kw.arg in ("static_argnums", "static_argnames") \
                    and not _static_spec_is_literal(kw.value):
                f = index.make_finding(
                    "retrace-hazard", fi, call.lineno,
                    f"{kw.arg} is not a literal int/str (tuple): non-hashable "
                    "or array-valued static specs retrace per call or fail "
                    "to cache")
                if f:
                    out.append(f)

    def scan(fi: FunctionInfo, node: ast.AST, loop_depth: int):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                continue
            if isinstance(child, ast.Call):
                if is_jit_call(child, fi.module):
                    check_jit_call(fi, child, loop_depth)
                if isinstance(child.func, ast.Call) \
                        and is_jit_call(child.func, fi.module):
                    f = index.make_finding(
                        "retrace-hazard", fi, child.lineno,
                        "jax.jit(f)(...) constructs and discards the jitted "
                        "wrapper per call: the compile cache is keyed on the "
                        "wrapper, so this can retrace every invocation")
                    if f:
                        out.append(f)
            d = loop_depth + (1 if isinstance(child, (ast.For, ast.AsyncFor,
                                                      ast.While)) else 0)
            scan(fi, child, d)

    for q in sorted(index.functions):
        fi = index.functions[q]
        scan(fi, fi.node, 0)

    # traced closures over mutable module state: the captured value is baked
    # into the executable at trace time — later mutations are silently stale
    for q in sorted(index.traced):
        fi = index.functions.get(q)
        if fi is None or isinstance(fi.node, ast.Module):
            continue
        local_binds = set(fi.params)
        for node in own_nodes(fi.node):
            if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for t in targets:
                    if isinstance(t, ast.Name):
                        local_binds.add(t.id)
        for node in own_nodes(fi.node):
            if (isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load)
                    and node.id in fi.module.mutable_globals
                    and node.id not in local_binds):
                f = index.make_finding(
                    "retrace-hazard", fi, node.lineno,
                    f"traced function reads mutable module state '{node.id}': "
                    "the value is baked in at trace time; later mutations are "
                    "silently ignored by the compiled executable")
                if f:
                    out.append(f)
    return out


# ---------------------------------------------------------------------------
# jit-purity
# ---------------------------------------------------------------------------


def _rule_jit_purity(index: Index) -> List[Finding]:
    out = []
    for q in sorted(index.traced):
        fi = index.functions.get(q)
        if fi is None or isinstance(fi.node, ast.Module):
            continue
        sm = fi.module
        for node in own_nodes(fi.node):
            f = None
            if isinstance(node, ast.Call):
                d = dotted_name(node.func, sm)
                if d in IMPURE_CALLS:
                    f = index.make_finding(
                        "jit-purity", fi, node.lineno,
                        f"{d}() inside a traced function: {IMPURE_CALLS[d]}")
                elif d and (d.startswith("numpy.random.")
                            or (d.startswith("random.")
                                and "random" in sm.imports)):
                    f = index.make_finding(
                        "jit-purity", fi, node.lineno,
                        f"{d}() inside a traced function: host RNG draws once "
                        "at trace time — every compiled call replays the same "
                        "'random' constant; thread jax.random keys instead")
                elif (isinstance(node.func, ast.Attribute)
                      and isinstance(node.func.value, ast.Name)
                      and node.func.value.id in sm.mutable_globals
                      and node.func.attr in MUTATOR_METHODS):
                    f = index.make_finding(
                        "jit-purity", fi, node.lineno,
                        f"mutation of module state '{node.func.value.id}' "
                        "inside a traced function: runs once per TRACE, not "
                        "per call — a silent side-effect bug")
            elif isinstance(node, ast.Global):
                f = index.make_finding(
                    "jit-purity", fi, node.lineno,
                    f"global {', '.join(node.names)} inside a traced "
                    "function: rebinding runs once per trace, not per call")
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for t in targets:
                    if (isinstance(t, ast.Subscript)
                            and isinstance(t.value, ast.Name)
                            and t.value.id in sm.mutable_globals):
                        f = index.make_finding(
                            "jit-purity", fi, node.lineno,
                            f"item assignment into module state "
                            f"'{t.value.id}' inside a traced function: runs "
                            "once per trace, not per call")
            if f:
                out.append(f)
    return out


# ---------------------------------------------------------------------------
# numpy-on-tracer
# ---------------------------------------------------------------------------


def _rule_numpy_on_tracer(index: Index) -> List[Finding]:
    out = []
    for q in sorted(index.traced):
        fi = index.functions.get(q)
        if fi is None or isinstance(fi.node, ast.Module):
            continue
        _, tainted = _device_taint(fi, index, seed_params=True)
        for node in own_nodes(fi.node):
            if not isinstance(node, ast.Call):
                continue
            d = dotted_name(node.func, fi.module)
            if not d or not d.startswith("numpy."):
                continue
            tail = d.split(".", 1)[1]
            if tail.split(".")[0] in NP_METADATA_OK or tail.startswith("random."):
                continue
            if node.args and any(tainted(a) for a in node.args):
                f = index.make_finding(
                    "numpy-on-tracer", fi, node.lineno,
                    f"np.{tail} applied to a traced value: numpy either "
                    "raises TracerArrayConversionError or silently constant-"
                    "folds at trace time; use jnp instead")
                if f:
                    out.append(f)
    return out


# ---------------------------------------------------------------------------
# monotonic-clock
# ---------------------------------------------------------------------------


_WALL_CLOCKS = {"time.time", "time.time_ns"}


def _rule_monotonic_clock(index: Index) -> List[Finding]:
    """Wall clock in duration/deadline arithmetic: ``time.time()`` (or a name
    assigned from it) fed into +/- or an ordering comparison. The wall clock
    steps under NTP slew/adjustment — elapsed-time math wants
    ``time.monotonic()`` or ``time.perf_counter()``. Value-only uses
    (timestamps recorded into logs/indices) are not flagged."""
    out = []
    for q in sorted(index.functions):
        fi = index.functions[q]
        if isinstance(fi.node, ast.Module):
            continue
        sm = fi.module
        nodes = own_nodes(fi.node)

        wall_names: Set[str] = set()
        for node in nodes:
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call) \
                    and dotted_name(node.value.func, sm) in _WALL_CLOCKS:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        wall_names.add(t.id)

        def is_wall(expr: ast.AST) -> bool:
            if isinstance(expr, ast.Call) \
                    and dotted_name(expr.func, sm) in _WALL_CLOCKS:
                return True
            return isinstance(expr, ast.Name) and expr.id in wall_names

        for node in nodes:
            hit = False
            if isinstance(node, ast.BinOp) \
                    and isinstance(node.op, (ast.Add, ast.Sub)) \
                    and (is_wall(node.left) or is_wall(node.right)):
                hit = True
            elif isinstance(node, ast.Compare) and any(
                    isinstance(op, (ast.Lt, ast.LtE, ast.Gt, ast.GtE))
                    for op in node.ops) \
                    and (is_wall(node.left)
                         or any(is_wall(c) for c in node.comparators)):
                hit = True
            if hit:
                f = index.make_finding(
                    "monotonic-clock", fi, node.lineno,
                    "time.time() in duration/deadline arithmetic: the wall "
                    "clock steps under NTP adjustment — use time.monotonic() "
                    "or time.perf_counter() for elapsed time")
                if f:
                    out.append(f)
    return out


# ---------------------------------------------------------------------------
# lock-discipline
# ---------------------------------------------------------------------------


def _lockish(expr: ast.AST) -> bool:
    for n in ast.walk(expr):
        if isinstance(n, ast.Attribute) and "lock" in n.attr.lower():
            return True
        if isinstance(n, ast.Name) and "lock" in n.id.lower():
            return True
    return False


def _rule_lock_discipline(index: Index) -> List[Finding]:
    out = []
    for dotted in sorted(index.modules):
        sm = index.modules[dotted]
        if not sm.imports_threading or not sm.mutable_globals:
            continue
        for q in sorted(sm.functions):
            fi = sm.functions[q]
            if isinstance(fi.node, ast.Module):
                continue  # import-time mutation is single-threaded

            globals_decl: Set[str] = set()
            for node in own_nodes(fi.node):
                if isinstance(node, ast.Global):
                    globals_decl.update(node.names)

            def mutation_of(node: ast.AST) -> Optional[str]:
                if isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute) \
                        and isinstance(node.func.value, ast.Name) \
                        and node.func.value.id in sm.mutable_globals \
                        and node.func.attr in MUTATOR_METHODS:
                    return node.func.value.id
                if isinstance(node, (ast.Assign, ast.AugAssign)):
                    targets = node.targets if isinstance(node, ast.Assign) \
                        else [node.target]
                    for t in targets:
                        if isinstance(t, ast.Subscript) \
                                and isinstance(t.value, ast.Name) \
                                and t.value.id in sm.mutable_globals:
                            return t.value.id
                        if isinstance(t, ast.Name) and t.id in globals_decl \
                                and t.id in sm.mutable_globals:
                            return t.id
                if isinstance(node, ast.Delete):
                    for t in node.targets:
                        if isinstance(t, ast.Subscript) \
                                and isinstance(t.value, ast.Name) \
                                and t.value.id in sm.mutable_globals:
                            return t.value.id
                return None

            def scan(node: ast.AST, lock_depth: int):
                for child in ast.iter_child_nodes(node):
                    if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                          ast.ClassDef)):
                        continue
                    d = lock_depth
                    if isinstance(child, (ast.With, ast.AsyncWith)) and any(
                            _lockish(item.context_expr) for item in child.items):
                        d += 1
                    name = mutation_of(child)
                    if name is not None and lock_depth == 0:
                        f = index.make_finding(
                            "lock-discipline", fi, child.lineno,
                            f"module-level mutable '{name}' mutated without a "
                            "held lock in a threaded module: concurrent "
                            "callers race")
                        if f:
                            out.append(f)
                    scan(child, d)

            scan(fi.node, 0)
    out += _lock_hot_sync_findings(index)
    return out


def _lock_hot_sync_findings(index: Index) -> List[Finding]:
    """Second lock-discipline sub-check, for the serving scheduler's hot
    path: NO HOST SYNC (and no jitted dispatch) while holding a lock. A
    ``with <lock>:`` body that pulls a device value to host — device_get,
    ``.item()``, float/int/bool coercion, np.asarray, block_until_ready —
    or dispatches a jitted callable serializes every other thread behind
    XLA: producers can't even enqueue while the device runs. Admission
    math on host floats under the lock is fine; the device work must
    happen with the lock released (serve/scheduler.py's dispatch shape)."""
    out = []
    for dotted in sorted(index.modules):
        sm = index.modules[dotted]
        if not sm.imports_threading:
            continue
        for q in sorted(sm.functions):
            fi = sm.functions[q]
            if isinstance(fi.node, ast.Module):
                continue
            _, tainted = _device_taint(fi, index, seed_params=False)

            def sync_message(node: ast.AST) -> Optional[str]:
                if not isinstance(node, ast.Call):
                    return None
                d = dotted_name(node.func, sm)
                if d == "jax.device_get":
                    return ("jax.device_get under a held lock: every thread "
                            "queues behind the device→host transfer")
                if d in ("numpy.asarray", "numpy.array", "numpy.copy") \
                        and node.args and any(tainted(a) for a in node.args):
                    return (f"{d.replace('numpy', 'np')} on a device value "
                            "under a held lock: materialization blocks all "
                            "other lock holders")
                f = node.func
                if isinstance(f, ast.Attribute):
                    if f.attr == "block_until_ready":
                        return (".block_until_ready() under a held lock: "
                                "the lock is held for the whole device "
                                "execution")
                    if f.attr == "item" and not node.args \
                            and tainted(f.value):
                        return (".item() on a device value under a held "
                                "lock: synchronous host round-trip while "
                                "others wait")
                    if f.attr in index.jit_names:
                        return ("jitted dispatch under a held lock: XLA "
                                "execution serializes every other thread "
                                "on this lock")
                if isinstance(f, ast.Name):
                    if f.id in ("float", "int", "bool") and node.args \
                            and tainted(node.args[0]):
                        return (f"{f.id}() on a device value under a held "
                                "lock: blocks until the executable "
                                "finishes while others wait")
                    if f.id in index.jit_names \
                            and f.id in sm.global_names:
                        return ("jitted dispatch under a held lock: XLA "
                                "execution serializes every other thread "
                                "on this lock")
                return None

            def scan(node: ast.AST, lock_depth: int):
                for child in ast.iter_child_nodes(node):
                    if isinstance(child, (ast.FunctionDef,
                                          ast.AsyncFunctionDef,
                                          ast.ClassDef)):
                        continue
                    d = lock_depth
                    if isinstance(child, (ast.With, ast.AsyncWith)) and any(
                            _lockish(item.context_expr)
                            for item in child.items):
                        d += 1
                    if lock_depth > 0:
                        msg = sync_message(child)
                        if msg:
                            f = index.make_finding("lock-discipline", fi,
                                                   child.lineno, msg)
                            if f:
                                out.append(f)
                    scan(child, d)

            scan(fi.node, 0)
    return out


# ---------------------------------------------------------------------------
# cost-analysis-off-hot-path
# ---------------------------------------------------------------------------

# trace-export entry points (obs/trace_export.py): serializing the whole span
# ring per call — report-time surfaces only
_TRACE_EXPORT_CALLS = {"live_trace", "trace_events"}

# fleet federation entry points (obs/fleet.py): each serializes the whole
# metrics registry + span summary and does store I/O (or scans every
# worker's snapshot) — report-time/boundary surfaces only, never per batch
_FLEET_CALLS = {"publish_snapshot", "collect_snapshots", "serve_collector"}


def _rule_cost_analysis_off_hot_path(index: Index) -> List[Finding]:
    """``cost_analysis()``/``memory_analysis()`` walk the lowered/compiled
    HLO modules host-side — milliseconds per call — the trace-export
    helpers serialize the whole span ring, and the fleet federation
    helpers (obs/fleet.py) additionally do store I/O. None belongs in
    traced bodies (baked in at trace time, re-run per compile) or
    per-batch dispatch code (latency per step). Harvest at compile time
    and render at report time instead (obs/profile.py, obs/trace_export.py,
    obs/fleet.py)."""
    out = []
    for q in sorted(index.traced | index.hot):
        fi = index.functions[q]
        where = "traced" if q in index.traced else "hot-path"
        for node in own_nodes(fi.node):
            if not isinstance(node, ast.Call):
                continue
            f = None
            if isinstance(node.func, ast.Attribute) and node.func.attr in (
                    "cost_analysis", "memory_analysis"):
                f = index.make_finding(
                    "cost-analysis-off-hot-path", fi, node.lineno,
                    f".{node.func.attr}() reachable from {where} code: walks "
                    "the executable's HLO host-side (milliseconds per call); "
                    "harvest once at compile/report time via obs.profile "
                    "instead")
            else:
                d = dotted_name(node.func, fi.module) or ""
                leaf = d.rsplit(".", 1)[-1] if d else (
                    node.func.attr if isinstance(node.func, ast.Attribute)
                    else node.func.id if isinstance(node.func, ast.Name)
                    else "")
                if leaf in _TRACE_EXPORT_CALLS or "trace_export." in d:
                    f = index.make_finding(
                        "cost-analysis-off-hot-path", fi, node.lineno,
                        f"trace export ({leaf or d}) reachable from {where} "
                        "code: serializes the span ring per call; export at "
                        "report time (/debug/trace, DL4J_TPU_SPAN_DUMP) "
                        "instead")
                elif leaf in _FLEET_CALLS:
                    f = index.make_finding(
                        "cost-analysis-off-hot-path", fi, node.lineno,
                        f"fleet federation ({leaf}) reachable from {where} "
                        "code: serializes the metrics registry and does "
                        "store I/O per call; publish at step boundaries / "
                        "collect at report time (obs/fleet.py) instead")
            if f:
                out.append(f)
    return out


# ---------------------------------------------------------------------------
# step-wiring: compiled-step construction outside the step-program module
# ---------------------------------------------------------------------------


def _rule_step_wiring(index: Index) -> List[Finding]:
    """Direct ``jax.jit(..., donate_argnums=...)`` in ``nn/`` or
    ``parallel/`` outside ``nn/step_program.py``. A donated-carry jit IS a
    training/serving step executable, and the framework's step wiring
    (trace sites, AOT warm registration, retrace-guard hookup, the
    grad-accumulation scan) lives in exactly one place — ``StepProgram``.
    Hand-rolled step jits fork that policy a sixth time: they silently miss
    AOT warmup, guard budgets, and the cost-exemplar harvest (ISSUE 13;
    docs/PARALLELISM.md)."""
    out = []
    for q in sorted(index.functions):
        fi = index.functions[q]
        p = "/" + fi.module.relpath.replace("\\", "/")
        if "/nn/" not in p and "/parallel/" not in p:
            continue
        if p.endswith("/step_program.py"):
            continue
        for node in own_nodes(fi.node):
            if not (isinstance(node, ast.Call) and is_jit_call(node, fi.module)):
                continue
            if not any(kw.arg == "donate_argnums" for kw in node.keywords):
                continue
            f = index.make_finding(
                "step-wiring", fi, node.lineno,
                "donated-carry jit built outside nn/step_program.py: step "
                "executables must go through StepProgram so trace/donate/"
                "AOT-warm/retrace-guard policy stays in one place")
            if f:
                out.append(f)
    return out
