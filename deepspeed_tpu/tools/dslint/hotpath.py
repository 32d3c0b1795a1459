"""Hot-path rules: host-sync / device-transfer anti-patterns.

Two sub-families with very different badness models:

- **DSH1xx (in-jit, error)** — code reachable from a ``jax.jit`` /
  ``shard_map`` trace.  A host sync here either fails to trace outright
  or (worse) silently executes at *trace time* and bakes a stale value
  into the compiled program.  On remote-attached TPUs a single stray
  sync costs a full wire round-trip (~70-100 ms) per dispatch — 10×+ a
  tuned step time.

- **DSH2xx (step-cadence driver, warning)** — the host-side engine loop
  (``train_batch`` / ``step`` / ``forward`` / ``backward`` and the
  ``self.*`` helpers they call).  Host syncs here are *legal* but each
  separate ``device_get``/`.item()` is its own blocking round-trip; N
  scalars fetched one-by-one cost N latencies when one batched
  ``jax.device_get(pytree)`` costs one.
"""

import ast
from typing import List

from .analysis import ModuleIndex, body_nodes
from .core import (ParsedFile, Rule, call_name, diag, dotted_name,
                   register_file_checker, register_rule)

# -- rule catalog -----------------------------------------------------------

register_rule(Rule(
    id="DSH101", name="hot-item-sync", severity="error",
    summary=".item()/.tolist() on a value inside jit-traced code",
    rationale="Forces a device→host transfer inside a traced function: "
              "fails under jit, or executes at trace time and bakes a "
              "stale constant into the compiled program.",
    autofix_hint="Keep the value on device (jnp ops), or return it from "
                 "the jitted function and fetch it host-side."))

register_rule(Rule(
    id="DSH102", name="hot-scalar-cast", severity="error",
    summary="float()/int()/bool() of a traced value inside jit-traced code",
    rationale="Python scalar conversion of a tracer raises "
              "ConcretizationTypeError — or silently freezes a trace-time "
              "constant if the value happens to be concrete. Shape/dtype "
              "arithmetic (x.shape, len(...)) is static and exempt.",
    autofix_hint="Use jnp casts (x.astype(...)) on device; fetch scalars "
                 "outside the jitted function."))

register_rule(Rule(
    id="DSH103", name="hot-host-materialize", severity="error",
    summary="np.asarray/np.array/jax.device_get inside jit-traced code",
    rationale="Materializes a traced array on the host: a hidden sync "
              "per call, and numpy results are trace-time constants that "
              "do not update step to step.",
    autofix_hint="Use jnp.asarray (traced) inside jit; reserve numpy for "
                 "host-side code or jax.pure_callback."))

register_rule(Rule(
    id="DSH104", name="hot-print", severity="error",
    summary="print() inside jit-traced code",
    rationale="Executes once at trace time, printing tracer reprs — not "
              "per step, not values. Silently misleading.",
    autofix_hint="Use jax.debug.print(...) for traced values."))

register_rule(Rule(
    id="DSH105", name="hot-wall-clock", severity="error",
    summary="time.time()/perf_counter() inside jit-traced code",
    rationale="Evaluates once at trace time; every execution of the "
              "compiled program sees the same frozen timestamp.",
    autofix_hint="Time around the dispatch on the host, fencing with a "
                 "device_get of an output (see utils/timer.device_fence)."))

register_rule(Rule(
    id="DSH106", name="hot-device-loop", severity="error",
    summary="Python loop over jax.devices() inside jit-traced code",
    rationale="Per-device Python loops in traced code unroll at trace "
              "time into device_count copies of the body — and retrace "
              "when topology changes. SPMD collectives express this "
              "without unrolling.",
    autofix_hint="Use mesh axes + collectives (psum/all_gather) or "
                 "shard_map instead of enumerating devices."))

register_rule(Rule(
    id="DSH201", name="driver-item-sync", severity="warning",
    summary=".item() in step-cadence engine driver code",
    rationale=".item() blocks on one scalar: a full host round-trip on "
              "the step critical path, serializing host prep against "
              "device compute.",
    autofix_hint="Batch with other fetches via one jax.device_get(pytree) "
                 "at a coarser cadence (e.g. steps_per_print)."))

register_rule(Rule(
    id="DSH202", name="driver-sync-in-loop", severity="warning",
    summary="device transfer inside a Python loop in driver code",
    rationale="One blocking round-trip per iteration; a loop over N "
              "leaves costs N wire latencies where a single "
              "jax.device_get of the whole list costs one.",
    autofix_hint="Hoist: fetch the entire container with one "
                 "jax.device_get(...) before the loop."))

register_rule(Rule(
    id="DSH204", name="driver-memory-introspection", severity="warning",
    summary="memory_stats()/memory_analysis() on the per-step hot path",
    rationale="Device memory introspection is a host-side runtime query "
              "per device per call; on the step path it serializes host "
              "prep against the runtime and breaks the telemetry "
              "zero-new-syncs ledger contract (memory watermarks are "
              "sampled only at the steps_per_print cadence, and "
              "memory_analysis belongs at compile time).",
    autofix_hint="Route through profiling.memory: device_memory_summary "
                 "at the existing steps_per_print batched fetch, "
                 "MemoryLedger.record at program-build time."))

register_rule(Rule(
    id="DSH205", name="driver-skew-export", severity="warning",
    summary="latency/skew/fingerprint telemetry export outside the "
            "steps_per_print cadence in driver code",
    rationale="Per-rank run-dir exchange (latency-ring snapshots, the "
              "latency-rank*.json publish/read pair, and the integrity "
              "plane's integrity-rank*.json fingerprint publish/read/"
              "vote) does host arithmetic plus run-dir file I/O: cheap "
              "at print cadence, a per-step cost multiplier on the hot "
              "path.  The contract for both families is that they ride "
              "the existing batched steps_per_print fetch, adding zero "
              "per-step work.",
    autofix_hint="Call latency_snapshot/publish_rank_latency/"
                 "read_fleet_latencies (and publish_rank_fingerprint/"
                 "read_fleet_fingerprints/note_fingerprint) only from "
                 "code reached through an `if ... steps_per_print ...:` "
                 "guard (e.g. the engine's _sample_comm_skew / "
                 "_sample_integrity)."))

register_rule(Rule(
    id="DSH203", name="driver-unbatched-sync", severity="warning",
    summary="multiple separate host-sync sites in one driver function",
    rationale="Each device_get/.item()/sync-property read is an "
              "independent blocking round-trip; unrelated scalars fetched "
              "separately multiply per-step wire latency.",
    autofix_hint="Fetch together: jax.device_get((a, b, c)) is one "
                 "transfer. Suppress when sites run at different cadences."))

# -- matchers ---------------------------------------------------------------

_NUMPY_NAMES = {"np", "numpy"}
_SHAPEISH_ATTRS = {"shape", "ndim", "size", "dtype", "itemsize"}
_STATIC_CALLS = {"len", "getattr", "prod", "np.prod", "numpy.prod", "ord",
                 "range", "enumerate", "zip", "isinstance", "hash", "repr",
                 # round() of a tracer fails loudly on its own; in practice
                 # int(round(x)) sites are host-float kernel-parameter math
                 "round"}
_CLOCK_CALLS = {"time.time", "time.perf_counter", "time.monotonic",
                "time.process_time", "datetime.now", "datetime.utcnow",
                "datetime.datetime.now", "datetime.datetime.utcnow"}


def _is_item_call(node: ast.Call) -> bool:
    return (isinstance(node.func, ast.Attribute)
            and node.func.attr in ("item", "tolist") and not node.args
            and not node.keywords)


def _is_device_get(node: ast.Call) -> bool:
    name = call_name(node)
    return name.rsplit(".", 1)[-1] == "device_get"


def _is_np_materialize(node: ast.Call) -> bool:
    if not isinstance(node.func, ast.Attribute):
        return False
    if node.func.attr not in ("asarray", "array"):
        return False
    base = node.func.value
    return isinstance(base, ast.Name) and base.id in _NUMPY_NAMES


def _is_static_expr(node) -> bool:
    """Shape/dtype/len arithmetic is static under tracing — exempt from
    DSH102 even though it syntactically casts to a Python scalar."""
    if isinstance(node, ast.Constant):
        return True
    has_ref = False
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr in _SHAPEISH_ATTRS:
            return True
        if isinstance(sub, ast.Call) and call_name(sub) in _STATIC_CALLS:
            return True
        if isinstance(sub, (ast.Name, ast.Attribute)):
            has_ref = True
    # pure literal arithmetic (e.g. float(1 << 32)) references no values
    return not has_ref


def _is_scalar_cast(node: ast.Call) -> bool:
    if not (isinstance(node.func, ast.Name)
            and node.func.id in ("float", "int", "bool")):
        return False
    if len(node.args) != 1 or node.keywords:
        return False
    return not _is_static_expr(node.args[0])


_MEMORY_INTROSPECTION_ATTRS = ("memory_stats", "memory_analysis")


def _is_memory_introspection(node: ast.Call) -> bool:
    return (isinstance(node.func, ast.Attribute)
            and node.func.attr in _MEMORY_INTROSPECTION_ATTRS)


def _is_device_enum(expr) -> bool:
    return (isinstance(expr, ast.Call)
            and call_name(expr).rsplit(".", 1)[-1] in ("devices",
                                                       "local_devices")
            and dotted_name(getattr(expr.func, "value", None)) == "jax")


# -- in-jit checks ----------------------------------------------------------

def _check_hot_function(pf: ParsedFile, index: ModuleIndex, fn) -> List:
    out = []
    where = f"in jit-traced '{fn.qualname}'"
    for node, _ in body_nodes(fn, index.node_map):
        if isinstance(node, ast.Call):
            if _is_item_call(node):
                out.append(diag(pf, node, "DSH101",
                                f".{node.func.attr}() {where}: host sync "
                                "inside the compiled program"))
            elif _is_device_get(node) or _is_np_materialize(node):
                out.append(diag(pf, node, "DSH103",
                                f"{call_name(node)}(...) {where}: "
                                "materializes a traced value on host"))
            elif _is_scalar_cast(node):
                out.append(diag(pf, node, "DSH102",
                                f"{node.func.id}(...) {where}: Python "
                                "scalar conversion of a traced value"))
            elif isinstance(node.func, ast.Name) and node.func.id == "print":
                out.append(diag(pf, node, "DSH104",
                                f"print() {where}: runs once at trace "
                                "time; use jax.debug.print"))
            elif _is_memory_introspection(node):
                out.append(diag(pf, node, "DSH204",
                                f".{node.func.attr}() {where}: memory "
                                "introspection evaluates once at trace "
                                "time and is a per-device host query"))
            elif call_name(node) in _CLOCK_CALLS:
                out.append(diag(pf, node, "DSH105",
                                f"{call_name(node)}() {where}: wall clock "
                                "freezes at trace time"))
        elif isinstance(node, (ast.For, ast.AsyncFor)):
            if _is_device_enum(node.iter):
                out.append(diag(pf, node, "DSH106",
                                f"loop over {call_name(node.iter)}() "
                                f"{where}: unrolls per device at trace "
                                "time"))
    return out


# -- step-cadence driver checks --------------------------------------------

DRIVER_CLASS_MARKERS = ("Engine", "Scaler", "Frontend")
DRIVER_METHODS = {
    "train_batch", "step", "forward", "backward", "eval_batch", "__call__",
    "_train_batch_stepwise", "_eval_one", "train_step",
    "has_overflow", "has_overflow_serial", "update_scale",
}


def _driver_roots(index: ModuleIndex):
    roots = set()
    for cls in index.classes:
        if not any(m in cls.name for m in DRIVER_CLASS_MARKERS):
            continue
        for name, fn in index.methods.get(cls.name, {}).items():
            if name in DRIVER_METHODS:
                roots.add(fn)
    return roots


def _mentions_steps_per_print(node) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr == "steps_per_print":
            return True
        if isinstance(sub, ast.Name) and sub.id == "steps_per_print":
            return True
    return False


def _guarded_call_ids(fn, node_map):
    """ids of Call nodes in ``fn``'s own body that are lexically inside
    an ``if`` whose test mentions ``steps_per_print`` — the print-cadence
    guard the DSH205 skew-export contract keys on."""
    guarded = set()

    def walk(node, in_guard):
        if id(node) in node_map:
            return  # nested def: its body is its own FuncNode
        if isinstance(node, ast.If):
            walk_children(node.test, in_guard)
            body_guard = in_guard or _mentions_steps_per_print(node.test)
            for child in node.body:
                walk_children(child, body_guard, top=True)
            for child in node.orelse:
                walk_children(child, in_guard, top=True)
            return
        if isinstance(node, ast.Call) and in_guard:
            guarded.add(id(node))
        walk_children(node, in_guard)

    def walk_children(node, in_guard, top=False):
        if top:
            walk(node, in_guard)
            return
        for child in ast.iter_child_nodes(node):
            walk(child, in_guard)

    root = fn.node
    if isinstance(root, ast.Lambda):
        walk(root.body, False)
    else:
        for stmt in root.body:
            walk(stmt, False)
    return guarded


def _driver_closure(index: ModuleIndex, roots):
    """(closure, unguarded) — roots + same-class methods reached through
    self-calls (jit-hot functions are covered by the DSH1xx walk
    instead).  ``unguarded`` is the subset reachable from a root through
    a call chain with NO ``steps_per_print`` guard on any edge: per-step
    code.  Members of the closure absent from ``unguarded`` run only at
    the print cadence (the DSH205 skew-export contract)."""
    seen = set(roots)
    unguarded = set(roots)
    frontier = list(roots)
    while frontier:
        fn = frontier.pop()
        guarded_ids = _guarded_call_ids(fn, index.node_map)
        for node, _ in body_nodes(fn, index.node_map):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "self"):
                target = index.resolve_self_attr(node.func.attr, fn)
                if target is None or target in index.hot:
                    continue
                edge_unguarded = (fn in unguarded
                                  and id(node) not in guarded_ids)
                if target not in seen:
                    seen.add(target)
                    frontier.append(target)
                if edge_unguarded and target not in unguarded:
                    # re-walk: its own edges now propagate unguarded
                    unguarded.add(target)
                    frontier.append(target)
    return seen - index.hot, unguarded - index.hot


def _sync_properties(index: ModuleIndex, cls_name: str):
    """Names of @property methods on the class whose body performs a host
    sync — reading them from driver code is a hidden round-trip."""
    out = set()
    for name, fn in index.methods.get(cls_name, {}).items():
        if not fn.is_property:
            continue
        for node, _ in body_nodes(fn, index.node_map):
            if isinstance(node, ast.Call) and (_is_device_get(node)
                                               or _is_item_call(node)):
                out.add(name)
                break
    return out


# latency/skew export surface (profiling/step_profiler.StepLatencyRing
# + profiling/comm's per-rank exchange) plus the integrity plane's
# fingerprint exchange (resilience/integrity.py: the publish/read/vote
# APIs — NOT the fleet heartbeat's beat(), which is per-step by design
# at O(1) throttled host work): print-cadence-only by contract
_SKEW_EXPORT_CALLS = {"latency_snapshot", "publish_rank_latency",
                      "read_fleet_latencies", "publish_rank_fingerprint",
                      "read_fleet_fingerprints", "note_fingerprint",
                      # serving twin (inference/resilience.py): the
                      # weight-fingerprint publish/read/vote surface —
                      # file I/O per call, print-cadence-only by the
                      # same contract
                      "publish_weight_fingerprint",
                      "read_fleet_weight_fingerprints",
                      "note_weight_fingerprint",
                      # serving observability (inference/observability):
                      # the window close + fleet-gauge exporters — event
                      # emission and window resets, print-cadence-only
                      # by the same contract
                      "export_serving_window",
                      "export_serving_gauges"}


def _is_skew_export(node: ast.Call) -> bool:
    return call_name(node).rsplit(".", 1)[-1] in _SKEW_EXPORT_CALLS


def _check_driver_function(pf: ParsedFile, index: ModuleIndex, fn,
                           cadence_only=False) -> List:
    out = []
    sync_props = (_sync_properties(index, fn.class_name)
                  if fn.class_name else set())
    guarded_ids = (_guarded_call_ids(fn, index.node_map)
                   if not cadence_only else None)
    sites = []  # (node, kind, in_loop)
    for node, in_loop in body_nodes(fn, index.node_map):
        if isinstance(node, ast.Call):
            if (not cadence_only and _is_skew_export(node)
                    and id(node) not in guarded_ids):
                # reachable per step AND not under a local
                # steps_per_print guard: the skew export would run on
                # the hot path
                out.append(diag(
                    pf, node, "DSH205",
                    f"{call_name(node)}(...) in driver '{fn.qualname}': "
                    "latency/skew export on the per-step path; move it "
                    "under the steps_per_print cadence guard"))
            if _is_item_call(node):
                sites.append((node, f".{node.func.attr}()", in_loop))
                out.append(diag(pf, node, "DSH201",
                                f".{node.func.attr}() in driver "
                                f"'{fn.qualname}': blocking per-scalar "
                                "host sync on the step path"))
            elif _is_memory_introspection(node):
                out.append(diag(
                    pf, node, "DSH204",
                    f".{node.func.attr}() in driver '{fn.qualname}': "
                    "per-device memory introspection on the step path; "
                    "sample via profiling.memory.device_memory_summary "
                    "at the steps_per_print cadence instead"))
            elif _is_device_get(node):
                sites.append((node, "jax.device_get", in_loop))
            elif _is_np_materialize(node):
                # np.asarray of a device array is an implicit device_get;
                # only the in-loop form is flagged (a single bulk copy on
                # host data is idiomatic and type-invisible to the linter)
                if in_loop:
                    sites.append((node, f"{call_name(node)}", in_loop))
        elif (isinstance(node, ast.Attribute)
              and isinstance(node.ctx, ast.Load)
              and isinstance(node.value, ast.Name)
              and node.value.id == "self" and node.attr in sync_props):
            sites.append((node, f"self.{node.attr} (sync property)",
                          in_loop))
    for node, kind, in_loop in sites:
        if in_loop:
            out.append(diag(pf, node, "DSH202",
                            f"{kind} inside a Python loop in driver "
                            f"'{fn.qualname}': one round-trip per "
                            "iteration; hoist into one batched "
                            "jax.device_get"))
    if len(sites) >= 2:
        for node, kind, _ in sites[1:]:
            out.append(diag(pf, node, "DSH203",
                            f"{kind} in driver '{fn.qualname}': "
                            f"{len(sites)} separate host-sync sites in "
                            "this function; batch into one "
                            "jax.device_get(pytree)"))
    return out


@register_file_checker
def check_hotpath(pf: ParsedFile) -> List:
    index = ModuleIndex(pf.tree)
    out = []
    for fn in sorted(index.hot, key=lambda f: f.node.lineno):
        out.extend(_check_hot_function(pf, index, fn))
    closure, unguarded = _driver_closure(index, _driver_roots(index))
    for fn in sorted(closure, key=lambda f: f.node.lineno):
        # cadence_only: every path from a driver root to fn crosses a
        # steps_per_print guard — skew export is in-contract there
        out.extend(_check_driver_function(pf, index, fn,
                                          cadence_only=fn not in unguarded))
    return out
