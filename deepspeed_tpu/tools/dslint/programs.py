"""Program-level semantic rules (DSP6xx): donation/aliasing safety and
collective semantics, checked on the COMPILED program.

dslint's other rule families lint Python ASTs; the two real bugs this
repo has shipped lived below what any AST rule can see — in the
optimized HLO that XLA/GSPMD emits:

- the ZeRO flatten that psum-SUMMED parameters across the tensor-
  parallel axis on every dp×tp mesh (finite loss masked it for eight
  rounds; caught only by the runtime dp=1 parity assert, PR 8);
- the donated ``device_put`` of a live numpy staging buffer that
  flakily corrupted the glibc heap on the second train step.

Both are *statically decidable* from artifacts the stack already
captures at AOT-compile time (the MemoryLedger/CommLedger hook walks
``compiled.as_text()`` once per program): donation shows up as the
module-header ``input_output_alias`` map, and a wrong-mesh-axis sum
shows up as an ``all-reduce`` whose replica groups span more devices
than the data axis.  This module turns each into a rule, so the next
instance is a CI failure instead of a 2-AM loss divergence.

Two analysis surfaces:

- **HLO artifacts** (:class:`ProgramArtifact` + :func:`verify_program`)
  — built live by ``engine.verify_programs()``
  (``profiling/verify.py``) or loaded from the ``<run_dir>/programs/``
  dump via ``python -m deepspeed_tpu.tools.dslint --programs
  <run_dir>``;
- **Python source** (the DSP603 dataflow checker registered below) —
  an AST companion that flags driver code reading a buffer after it
  was passed to a donating jit call (the heap-corruption shape).

Like the rest of dslint, this module is stdlib-only; the HLO collective
parser is borrowed lazily from ``profiling/comm.py`` (itself
stdlib+regex) so the ring-model accounting has exactly one
implementation.
"""

import ast
import dataclasses
import json
import os
import re
from typing import Dict, List, Optional, Tuple

from .core import (Diagnostic, ParsedFile, Rule, call_name, diag,
                   register_file_checker, register_rule)

# artifact sidecar format version (``<run_dir>/programs/<name>.json``)
ARTIFACT_SCHEMA_VERSION = 1
PROGRAMS_DIRNAME = "programs"

# -- rule catalog -----------------------------------------------------------

register_rule(Rule(
    id="DSP601", name="donation-not-materialized", severity="error",
    summary="jit entry point declares donate_argnums but the compiled "
            "executable materialized no input→output aliases",
    rationale="Donation is a capacity contract: the engine sizes HBM "
              "assuming state buffers are reused in place.  A program "
              "that silently drops every alias (dtype/sharding mismatch, "
              "backend limitation) doubles its state footprint and the "
              "capacity planner's verdict is wrong.",
    autofix_hint="Check that donated arguments' shapes/dtypes/shardings "
                 "match the outputs they should alias; see the "
                 "input_output_alias header of the dumped HLO."))

register_rule(Rule(
    id="DSP602", name="donation-unverifiable", severity="info",
    summary="donation aliases present in HLO but memory_analysis "
            "reports alias=0 (warm-cache deserialization caveat)",
    rationale="Executables deserialized from the persistent compile "
              "cache can report alias_size_in_bytes=0 even though the "
              "program text declares its input_output_alias map (PR 7 "
              "measured caveat, docs/observability.md).  Structural "
              "aliasing IS verified from the text; only the byte "
              "accounting is unverifiable — an explicit downgraded "
              "verdict, never silence.",
    autofix_hint="Cold-compile (clear the XLA cache) to re-verify the "
                 "byte accounting; predicted peaks are conservative "
                 "meanwhile."))

register_rule(Rule(
    id="DSP603", name="use-after-donation", severity="error",
    summary="a buffer reference is read after being passed to a "
            "donating jit call",
    rationale="A donated buffer is dead the moment the call is issued: "
              "XLA may reuse its memory for the outputs.  Reading the "
              "Python reference afterwards observes garbage — and when "
              "the donated value is a device_put of a live numpy "
              "staging buffer, the runtime can free numpy-owned memory "
              "and corrupt the allocator heap (observed: flaky glibc "
              "aborts on the 2nd train step, PR 8).",
    autofix_hint="Drop the reference after the donating call, or "
                 "re-home device_put results through a jitted copy so "
                 "the XLA allocator owns the donated buffer."))

register_rule(Rule(
    id="DSP611", name="param-sum-over-non-data-axis", severity="error",
    summary="cross-replica all-reduce sums a parameter-sized tensor "
            "over replica groups spanning a non-data mesh axis",
    rationale="Non-data mesh axes (model/pipe/seq/expert) hold REPLICAS "
              "of unsharded parameters, not partial values: an "
              "all-reduce whose groups span them multiplies every "
              "parameter by the axis product.  This is the flatten-×tp "
              "bug — loss stays finite (~ln vocab), so nothing "
              "downstream fails loudly.  Scope: the rule fires only "
              "when the full-mesh sum is the program's ONLY collective "
              "shape — the standalone init/flatten program signature.  "
              "Inside step programs GSPMD legitimately emits full-mesh "
              "assembly all-reduces over partition-exact "
              "dynamic-update-slice writes (measured parity-exact on "
              "this toolchain); those programs always carry data-axis-"
              "scoped collectives alongside and are exempt — the "
              "multichip dp=1 parity asserts remain their gate.",
    autofix_hint="Reduce over the data axis only (psum with the axis "
                 "name), or build the buffer host-side as "
                 "flatten_to_master now does."))

register_rule(Rule(
    id="DSP612", name="psum-for-pmean-suspect", severity="warning",
    summary="scalar cross-replica all-reduce with no mean-compensation "
            "scaling constant anywhere in the program",
    rationale="Step semantics for losses/metrics exchanged across data "
              "replicas almost always require a MEAN; a bare psum "
              "scales them by the group size and trains on a silently "
              "multiplied signal.  Heuristic: a correct pmean (or a "
              "global-batch-normalized loss) leaves a 1/k scaling "
              "constant with the group size dividing k in the "
              "optimized HLO; its absence is the psum signature.",
    autofix_hint="Use jax.lax.pmean (or divide by the axis size); if "
                 "the sum is intentional (e.g. a grad-norm psum), "
                 "ratchet it via `--baseline`."))

register_rule(Rule(
    id="DSP614", name="collective-analysis-unavailable",
    severity="warning",
    summary="the HLO collective parser (profiling/comm.py) could not "
            "be imported — DSP611/DSP612/DSP613 did NOT run",
    rationale="The collective-semantics checks borrow the CommLedger's "
              "parser so the wire model has one implementation; when "
              "that import fails (broken environment, vendored tools "
              "without the profiling package) the checks silently not "
              "running would read as 'verified clean' — the exact "
              "silence this rule family exists to eliminate.",
    autofix_hint="Run the verifier in an environment where "
                 "deepspeed_tpu.profiling imports (any env that can "
                 "train), or fix the import error it reports."))

register_rule(Rule(
    id="DSO701", name="serialized-collective", severity="warning",
    summary="fully serialized collective(s) with enough independent "
            "compute available to hide them",
    rationale="A sync-form collective blocks its dependents for its "
              "full wire time even when the program holds compute that "
              "depends on neither its inputs nor its outputs — wire "
              "seconds paid as step latency that an async "
              "-start/-done schedule would hide for free.  The overlap "
              "analyzer (profiling/overlap.py) only fires this when "
              "the independent-compute window clears a floor "
              "(DSO701_MIN_WINDOW_SECONDS): micro-programs have "
              "nothing to overlap WITH.",
    autofix_hint="Let XLA's async scheduler split the op "
                 "(--xla_tpu_enable_async_collective_*), or "
                 "restructure so dependent work moves off the "
                 "collective's path; ratchet intentional cases via "
                 "`--baseline`."))

register_rule(Rule(
    id="DSO702", name="serialized-host-transfer", severity="warning",
    summary="serialized host transfer(s) adjacent to independent "
            "compute — the offload tax, statically",
    rationale="Host<->device round trips (copy-start without "
              "overlapping schedule, or the engine's DECLARED "
              "offload-state stream running between dispatches) pay "
              "full wire latency while compute that could hide them "
              "sits idle — PERF.md's ~2x offload-tax accounting, per "
              "program.  The exposed seconds this rule quotes are the "
              "exact metric the overlapped-streaming work (ROADMAP "
              "item 2) must drive down; the --baseline ratchet records "
              "today's known-serialized stream without gating it.",
    autofix_hint="Double-buffer the chunk stream (prefetch group k+1 "
                 "while group k updates, overlap write-back with the "
                 "next fetch); on TPU lowerings, move transfers to "
                 "async copy-start/copy-done pairs."))

register_rule(Rule(
    id="DSO704", name="exposed-wire-regression", severity="warning",
    summary="a program's exposed wire grew past the baseline-recorded "
            "figure — the stream is re-serializing",
    rationale="DSO702 only fires when a host stream is FULLY "
              "serialized; a change that keeps the pipelined schedule "
              "but quietly grows its exposed fraction (fewer chunks, a "
              "shrunk prefetch queue, compute moved off the hiding "
              "window) would pass it.  The baseline's recorded "
              "exposed_wire_seconds metric is the ratchet: current "
              "exposure beyond the recorded value (+tolerance) fails "
              "CI even though every node still classifies as "
              "partially overlapped.",
    autofix_hint="Restore the overlap (offload_overlap/prefetch "
                 "depth), or re-record with --update-baseline if the "
                 "growth is intended and reviewed."))

register_rule(Rule(
    id="DSO705", name="attribution-drift", severity="warning",
    summary="the reconciled step budget drifts from the "
            "baseline-recorded attribution metrics beyond tolerance",
    rationale="The attribution model's worth is that its predicted "
              "budget stays reconciled with reality: a re-analyzed "
              "predicted_step_seconds drifting from the recorded "
              "figure means the declared budget (schedule, roofline "
              "inputs, stream declaration) changed without review, "
              "and a measured run whose step_unexplained_fraction "
              "exceeds the recorded ceiling means the model no longer "
              "explains where the step goes — either way the receipts "
              "bench/multichip quote are unaudited.",
    autofix_hint="Re-reconcile (fix the declaration or the model), or "
                 "re-record with --update-baseline if the drift is "
                 "intended and reviewed."))

register_rule(Rule(
    id="DSO703", name="overlap-model-drift", severity="warning",
    summary="recorded overlap summary drifts from the HLO re-analysis "
            "beyond tolerance",
    rationale="The sidecar's recorded exposure figures are what bench "
              "receipts and the ratchet baseline quote; if re-analyzing "
              "the dumped HLO disagrees, the artifact is stale (edited, "
              "or recorded by a drifted analyzer) and the quoted "
              "exposed-wire receipts are unauditable — the DSP613 "
              "argument, applied to the exposure model.",
    autofix_hint="Re-dump the program artifacts from a fresh compile "
                 "(delete <run_dir>/programs and rerun)."))

register_rule(Rule(
    id="DSS801", name="declared-sharded-materialized-replicated",
    severity="error",
    summary="a tensor declared sharded over a mesh axis compiled with "
            "a replicated (or coarser) layout — per-device memory "
            "silently multiplies by the dropped axis product",
    rationale="Parameter sharding (ZeRO stages, tensor parallelism) is "
              "a capacity contract: the planner and the bench receipts "
              "divide state bytes by the declared axis product.  GSPMD "
              "can silently materialize a replicated layout instead (a "
              "dropped out_sharding, a constraint lost through a "
              "fusion/while body) and NOTHING fails — training is "
              "numerically identical, loss is finite, and every device "
              "pays ×dp resident bytes.  The silent dp-fold-of-memory "
              "bug stage 3 will be built against; the same silence "
              "class as the PR 8 flatten replica-sum bug.",
    autofix_hint="Pin the layout with in_shardings/out_shardings (or "
                 "lax.with_sharding_constraint inside the jit) and "
                 "re-dump; the entry parameter named in the message "
                 "shows the materialized annotation."))

register_rule(Rule(
    id="DSS802", name="unpriced-reshard", severity="warning",
    summary="a state family materializes with DIFFERENT shard layouts "
            "across programs of one run — the boundary pays an "
            "unpriced reshard",
    rationale="When the producer of a tensor family (e.g. cast_params) "
              "compiles one layout and its consumer (train_step, "
              "serve_decode) another, the runtime inserts all-to-all / "
              "collective-permute / copy traffic at the program "
              "boundary that no ledger priced — wire seconds and HBM "
              "spikes invisible to every receipt.  One layout per "
              "family per run, or an explicit reshard program that the "
              "comm ledger prices.",
    autofix_hint="Align the producer's out_shardings with the "
                 "consumer's in_shardings (the declared_sharding "
                 "sidecars name both layouts), or ratchet an "
                 "intentional boundary via --baseline."))

register_rule(Rule(
    id="DSS803", name="param-bytes-ratchet", severity="warning",
    summary="per-device parameter bytes grew past the "
            "baseline-recorded figure — sharding is regressing",
    rationale="DSS801 only fires when a DECLARED-sharded tensor "
              "materializes replicated; a change that weakens the "
              "declaration itself (or re-replicates state the baseline "
              "era had sharded) passes it.  The baseline's recorded "
              "param_bytes_per_device metric is the ratchet — the "
              "DSO704/705 mechanism applied to resident parameter "
              "memory, and the receipt half of ROADMAP item 2's "
              "planner-verified ÷dp criterion.",
    autofix_hint="Restore the sharded layout, or re-record with "
                 "--update-baseline if the growth is intended and "
                 "reviewed."))

register_rule(Rule(
    id="DSS804", name="sharding-analysis-unavailable",
    severity="warning",
    summary="the HLO sharding parser (profiling/sharding.py) could "
            "not be imported — DSS801/DSS802/DSS803 did NOT run",
    rationale="The sharding-residency checks borrow the profiling "
              "package's parser so the layout math has one "
              "implementation; when that import fails the checks "
              "silently not running would read as 'verified clean' — "
              "the DSP614 contract: UNVERIFIED, never silently clean.",
    autofix_hint="Run the verifier in an environment where "
                 "deepspeed_tpu.profiling imports (any env that can "
                 "train), or fix the import error it reports."))

register_rule(Rule(
    id="DSP613", name="comm-ledger-drift", severity="warning",
    summary="recorded CommLedger totals drift from the HLO re-parse "
            "beyond tolerance",
    rationale="The run artifact's recorded collective/wire-byte totals "
              "are what bench receipts and regression gates quote; if "
              "re-walking the dumped HLO disagrees, the artifact is "
              "stale (edited, or recorded by a drifted parser) and the "
              "quoted receipts are unauditable.",
    autofix_hint="Re-dump the program artifacts from a fresh compile "
                 "(delete <run_dir>/programs and rerun)."))


# ---------------------------------------------------------------------------
# HLO text helpers
# ---------------------------------------------------------------------------

# one module-header alias entry: ``{1}: (0, {}, may-alias)`` —
# (output tuple index path): (parameter number, param index path, kind)
_ALIAS_ENTRY_RE = re.compile(
    r"\{(?P<out>[0-9, ]*)\}\s*:\s*\((?P<param>\d+),\s*\{[0-9, ]*\},\s*"
    r"(?P<kind>may-alias|must-alias)\)")
_ALIAS_HEADER_RE = re.compile(r"input_output_alias=\{")

# scalar f32/f64 constants in optimized HLO (array literals don't match)
_CONST_RE = re.compile(r"constant\((-?[0-9][0-9.eE+-]*)\)")


def parse_input_output_aliases(hlo_text: str) -> List[Tuple[str, int]]:
    """``[(output_index_path, parameter_number)]`` from the module
    header's ``input_output_alias`` map (empty when the program
    materialized no aliases)."""
    m = _ALIAS_HEADER_RE.search(hlo_text)
    if m is None:
        return []
    # entries live between the header's braces; scanning the following
    # header line is enough (entries never span lines)
    segment = hlo_text[m.end():hlo_text.find("\n", m.end())]
    return [(e.group("out").strip(), int(e.group("param")))
            for e in _ALIAS_ENTRY_RE.finditer(segment)]


def _parse_collectives(hlo_text: str, all_participants: int):
    """The CommLedger's own parser, borrowed lazily (one wire-model
    implementation); None when unavailable (dslint running without the
    package's profiling modules)."""
    try:
        from ...profiling import comm as comm_prof
    except Exception:
        return None
    return comm_prof.parse_hlo_collectives(
        hlo_text, all_participants=all_participants)


def _collective_summary(ops):
    try:
        from ...profiling import comm as comm_prof
    except Exception:
        return None
    return comm_prof.collective_summary(ops)


def has_mean_scaling_evidence(hlo_text: str, group: int) -> bool:
    """Whether the module holds a scaling constant consistent with a
    mean over a ``group``-wide replica group: any fractional constant
    ``c`` with ``1/c`` an integer that ``group`` divides.  Covers both
    the direct pmean lowering (``multiply(all-reduce, 1/g)``) and a
    loss normalized by the global element count (``1/(g·k)``)."""
    if group <= 1:
        return True
    for tok in set(_CONST_RE.findall(hlo_text)):
        try:
            c = float(tok)
        except ValueError:
            continue
        if not 0.0 < abs(c) < 1.0:
            continue
        inv = 1.0 / abs(c)
        k = round(inv)
        if k and abs(inv - k) <= 1e-6 * inv and k % group == 0:
            return True
    return False


# ---------------------------------------------------------------------------
# Program artifacts
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ProgramArtifact:
    """One compiled program plus the metadata the DSP6xx rules need.

    Built live from an engine's ledger (``profiling/verify.py``) or
    loaded from a ``<run_dir>/programs/`` dump.  ``path`` is what
    diagnostics point at (the ``.hlo`` file, or a ``<program>`` pseudo
    path for in-memory verification)."""

    name: str
    hlo: str
    path: str = ""
    # declared pytree-level donate_argnums (empty tuple/None = no
    # donation declared; the DSP60x checks then have nothing to verify)
    donate_argnums: Optional[Tuple[int, ...]] = None
    # memory_analysis alias bytes (None = analysis unavailable)
    alias_size_in_bytes: Optional[int] = None
    mesh_axes: Dict[str, int] = dataclasses.field(default_factory=dict)
    data_axis: str = "data"
    # total bytes of the flat parameter master (the DSP611 payload
    # floor); None disables the parameter-shape test
    param_bytes: Optional[int] = None
    # the CommLedger entry recorded at compile time (DSP613 cross-check;
    # its "overlap" sub-dict is the DSO703 cross-check)
    comm: Optional[dict] = None
    # init-provenance note from the flat coordinator (informational)
    master_provenance: Optional[str] = None
    # engine-declared per-step host-state stream bytes (the offload
    # round trips that run BETWEEN dispatches, invisible in this
    # program's HLO) — producers set it only on update programs
    host_state_wire_bytes: Optional[int] = None
    # the declared ISSUE SCHEDULE of that stream ({overlap,
    # prefetch_depth, chunks, groups, form, ...}): how the engine
    # actually sequences the chunk transfers — what the overlap
    # analyzer prices exposure from (None = serialized by construction)
    host_stream_schedule: Optional[dict] = None
    # the declared bucketed-collective schedule (overlap_comm bucket
    # geometry, {overlap, rs_buckets, ag_buckets, ...}) of the ZeRO-2
    # gradient exchange — producers set it only on exchange programs;
    # None = no bucketed exchange declared (no claim either way)
    collective_schedule: Optional[dict] = None
    # device_kind string the roofline/wire tables resolve against
    device_kind: Optional[str] = None
    # the engine-DECLARED sharding spec ({tag, mesh_axes, families:
    # {name: {leaves: [{bytes, axes, divisor}], total_bytes}}}), built
    # from the same mesh/PartitionSpec tuples the jits were given —
    # what the DSS8xx sharding auditor reconciles the materialized HLO
    # layouts against; None = nothing declared (no claim either way)
    declared_sharding: Optional[dict] = None

    def __post_init__(self):
        if not self.path:
            self.path = f"<{self.name}>"
        if self.donate_argnums is not None:
            self.donate_argnums = tuple(int(i) for i in self.donate_argnums)

    @property
    def total_devices(self) -> int:
        n = 1
        for size in self.mesh_axes.values():
            n *= int(size)
        return n

    def sidecar(self) -> dict:
        """The JSON sidecar ``profiling/verify.ProgramDumper`` writes."""
        return {
            "artifact_schema_version": ARTIFACT_SCHEMA_VERSION,
            "program": self.name,
            "hlo_file": f"{self.name}.hlo",
            "donate_argnums": (list(self.donate_argnums)
                               if self.donate_argnums is not None else None),
            "alias_size_in_bytes": self.alias_size_in_bytes,
            "mesh_axes": dict(self.mesh_axes),
            "data_axis": self.data_axis,
            "param_bytes": self.param_bytes,
            "comm": self.comm,
            "master_provenance": self.master_provenance,
            "host_state_wire_bytes": self.host_state_wire_bytes,
            "host_stream_schedule": self.host_stream_schedule,
            "collective_schedule": self.collective_schedule,
            "device_kind": self.device_kind,
            "declared_sharding": self.declared_sharding,
        }


def _load_declared_sharding(side: dict) -> Optional[dict]:
    """Type-validated ``declared_sharding`` from one sidecar dict.
    Raises ``TypeError``/``ValueError`` (→ the CLI's malformed-sidecar
    exit-2 contract) when the field is present but not the declared
    shape — a tampered sidecar must fail loudly, not quietly disable
    the DSS8xx reconciliation."""
    declared = side.get("declared_sharding")
    if declared is None:
        return None
    if not isinstance(declared, dict):
        raise TypeError(
            f"declared_sharding must be an object, got "
            f"{type(declared).__name__}")
    families = declared.get("families")
    if families is not None and not isinstance(families, dict):
        raise TypeError(
            f"declared_sharding.families must be an object, got "
            f"{type(families).__name__}")
    for fam, spec in (families or {}).items():
        if not isinstance(spec, dict) \
                or not isinstance(spec.get("leaves", []), list):
            raise TypeError(
                f"declared_sharding.families[{fam!r}] must be an "
                "object with a 'leaves' list")
    return dict(declared)


def load_run_artifacts(run_dir: str) -> List[ProgramArtifact]:
    """Artifacts from ``<run_dir>/programs/*.json`` (+ their ``.hlo``
    texts).  Accepts the programs dir itself too.  Raises
    ``FileNotFoundError`` when neither exists."""
    progdir = os.path.join(run_dir, PROGRAMS_DIRNAME)
    if not os.path.isdir(progdir):
        if os.path.isdir(run_dir) and any(
                n.endswith(".json") for n in os.listdir(run_dir)):
            progdir = run_dir
        else:
            raise FileNotFoundError(
                f"no program artifacts under {run_dir!r} (expected "
                f"{PROGRAMS_DIRNAME}/<name>.json sidecars — run with "
                "profiling.program_dump enabled)")
    out = []
    for name in sorted(os.listdir(progdir)):
        if not name.endswith(".json"):
            continue
        path = os.path.join(progdir, name)
        with open(path, "r", encoding="utf-8") as f:
            side = json.load(f)
        if not isinstance(side, dict) or "program" not in side:
            continue  # foreign json in a shared dir
        hlo_name = side.get("hlo_file") or f"{side['program']}.hlo"
        if not isinstance(hlo_name, str):
            raise ValueError(
                f"malformed program sidecar {path}: hlo_file must be a "
                f"string, got {type(hlo_name).__name__}")
        hlo_path = os.path.join(progdir, hlo_name)
        try:
            with open(hlo_path, "r", encoding="utf-8") as f:
                hlo = f.read()
        except OSError:
            hlo = ""
        try:
            out.append(ProgramArtifact(
                name=str(side["program"]), hlo=hlo, path=hlo_path,
                donate_argnums=(tuple(side["donate_argnums"])
                                if side.get("donate_argnums") else None),
                alias_size_in_bytes=side.get("alias_size_in_bytes"),
                mesh_axes=dict(side.get("mesh_axes") or {}),
                data_axis=side.get("data_axis") or "data",
                param_bytes=side.get("param_bytes"),
                comm=side.get("comm"),
                master_provenance=side.get("master_provenance"),
                host_state_wire_bytes=(
                    int(side["host_state_wire_bytes"])
                    if side.get("host_state_wire_bytes") is not None
                    else None),
                host_stream_schedule=(
                    dict(side["host_stream_schedule"])
                    if isinstance(side.get("host_stream_schedule"), dict)
                    else None),
                collective_schedule=(
                    dict(side["collective_schedule"])
                    if isinstance(side.get("collective_schedule"), dict)
                    else None),
                device_kind=side.get("device_kind"),
                declared_sharding=_load_declared_sharding(side)))
        except (TypeError, ValueError) as e:
            # type-malformed sidecar (donate_argnums: 5, mesh_axes as a
            # list, ...): a usage-class load failure the CLI reports as
            # exit 2, never a traceback
            raise ValueError(
                f"malformed program sidecar {path}: {e}") from e
    if not out:
        # a run dir full of OTHER json (latency-rank*.json etc.) must
        # not read as "0 programs, verified clean" — a run that never
        # dumped (program_dump off) fails the CI verify step loudly
        raise FileNotFoundError(
            f"no program artifacts under {run_dir!r} (found json files "
            f"but none with a 'program' sidecar key — was "
            "profiling.program_dump enabled for this run?)")
    return out


# ---------------------------------------------------------------------------
# HLO-side verification passes
# ---------------------------------------------------------------------------

def _pdiag(artifact, rule_id, message) -> Diagnostic:
    return Diagnostic(path=artifact.path, line=1, col=1, rule_id=rule_id,
                      message=f"[{artifact.name}] {message}")


def check_donation(artifact: ProgramArtifact) -> List[Diagnostic]:
    """DSP601/DSP602: declared donation must materialize as
    input→output aliases in the compiled module."""
    declared = artifact.donate_argnums
    if not declared or not artifact.hlo:
        return []
    aliases = parse_input_output_aliases(artifact.hlo)
    if not aliases:
        return [_pdiag(
            artifact, "DSP601",
            f"donate_argnums={tuple(declared)} declared but the compiled "
            "module header carries NO input_output_alias entries — every "
            "donated state buffer is copied, not reused")]
    # Partial-drop lower bound: each donated pytree argument flattens
    # to >= 1 HLO parameter, so fewer DISTINCT aliased parameters than
    # declared argnums proves at least one donated argument aliased
    # nothing at all.  This is a lower bound only — per-ARGUMENT
    # coverage needs the pytree->parameter mapping, which the artifact
    # does not carry, so a dropped buffer inside a multi-leaf argument
    # (XLA's "Some donated buffers were not usable" warning) can still
    # pass; the verdict is program-granular by design.
    aliased_params = {param for _, param in aliases}
    if len(aliased_params) < len(declared):
        return [_pdiag(
            artifact, "DSP602",
            f"only {len(aliased_params)} distinct aliased parameter(s) "
            f"for {len(declared)} donated argument(s) "
            f"(donate_argnums={tuple(declared)}): at least one donated "
            "argument materialized no alias — its buffers are copied, "
            "not reused, and the capacity math overcounts")]
    if artifact.alias_size_in_bytes == 0 \
            or artifact.alias_size_in_bytes is None:
        # byte accounting unverifiable either way — explicit downgraded
        # verdict, never silence: 0 is the documented warm-cache
        # deserialization caveat, None means the backend (or sidecar)
        # carried no memory_analysis at all
        why = ("memory_analysis reports alias=0 bytes "
               "(cache-deserialized executable)"
               if artifact.alias_size_in_bytes == 0 else
               "no memory_analysis byte data available for this "
               "executable")
        return [_pdiag(
            artifact, "DSP602",
            f"{len(aliases)} input_output_alias entr"
            f"{'y' if len(aliases) == 1 else 'ies'} verified from HLO "
            f"text, but {why}; byte accounting unverifiable, predicted "
            "peaks conservative")]
    return []


def check_collectives(artifact: ProgramArtifact) -> List[Diagnostic]:
    """DSP611/DSP612/DSP613 over one program's optimized HLO."""
    if not artifact.hlo:
        return []
    ops = _parse_collectives(artifact.hlo, artifact.total_devices)
    if ops is None:
        # parser unavailable: the checks did NOT run — say so loudly
        # instead of reading as verified-clean (DSP614)
        return [_pdiag(
            artifact, "DSP614",
            "collective parser (deepspeed_tpu.profiling.comm) "
            "unimportable in this environment — DSP611/DSP612/DSP613 "
            "were skipped, this program's collective semantics are "
            "UNVERIFIED")]
    out: List[Diagnostic] = []
    dp = max(int(artifact.mesh_axes.get(artifact.data_axis, 1)), 1)

    # DSP611: parameter-sized all-reduce spanning a non-data axis.
    # Exemption (see the rule rationale): a program that ALSO holds
    # collectives of any other shape — data-axis-scoped reductions,
    # gathers, scatters — is a step program whose full-mesh sum is a
    # GSPMD assembly over partition-exact DUS writes (parity-exact by
    # measurement); only the init/flatten signature, where the suspect
    # sum is the sole collective shape, fires.
    if artifact.param_bytes:
        suspects = [rec for rec in ops
                    if rec["op"] == "all-reduce" and rec["group"] > dp
                    and rec["out_bytes"] >= artifact.param_bytes]
        assembly_evidence = any(
            rec["op"] != "all-reduce" or rec["group"] <= dp
            for rec in ops if rec not in suspects)
        for rec in () if assembly_evidence else suspects:
            factor = rec["group"] // dp
            out.append(_pdiag(
                artifact, "DSP611",
                f"all-reduce over {rec['group']} devices sums a "
                f"parameter-sized tensor ({rec['out_bytes']} bytes >= "
                f"flat master {artifact.param_bytes}) but the "
                f"{artifact.data_axis} axis is only {dp} wide: the "
                f"non-data replicas get SUMMED and every parameter "
                f"arrives ×{factor} (the flatten-×tp bug shape)"))

    # DSP612: scalar psum with no mean-compensation constant in sight
    for rec in ops:
        if (rec["op"] == "all-reduce" and rec["group"] > 1
                and rec["out_bytes"] <= 8
                and not has_mean_scaling_evidence(artifact.hlo,
                                                 rec["group"])):
            out.append(_pdiag(
                artifact, "DSP612",
                f"scalar all-reduce over {rec['group']} replicas with no "
                f"1/k scaling constant (k divisible by {rec['group']}) "
                "anywhere in the module — psum where the step semantics "
                "likely require a mean"))

    # DSP613: recorded ledger entry vs re-parse
    if artifact.comm:
        fresh = _collective_summary(ops)
        if fresh is not None:
            drifts = []
            if fresh["collectives"] != artifact.comm.get("collectives"):
                drifts.append(
                    f"collectives {artifact.comm.get('collectives')} -> "
                    f"{fresh['collectives']}")
            for field in ("payload_bytes", "wire_bytes"):
                rec_v = artifact.comm.get(field)
                new_v = fresh[field]
                if rec_v is None:
                    continue
                tol = max(abs(new_v), 1) * 0.02
                if abs(int(rec_v) - int(new_v)) > tol:
                    drifts.append(f"{field} {rec_v} -> {new_v}")
            if drifts:
                out.append(_pdiag(
                    artifact, "DSP613",
                    "recorded comm-ledger totals drift from the HLO "
                    f"re-parse: {'; '.join(drifts)} (stale or tampered "
                    "artifact)"))
    return out


# ---------------------------------------------------------------------------
# DSS8xx: sharding residency audit (profiling/sharding.py)
# ---------------------------------------------------------------------------

# DSS801 fires only on tensors at least this large: CI fixtures are
# MiB-scale, and a sub-MiB fold cannot meaningfully move capacity
SHARDING_MIN_TENSOR_BYTES = 1 << 20

# relative growth of param_bytes_per_device beyond the recorded metric
# that trips DSS803 (byte counts are exact per geometry; the tolerance
# absorbs dtype/padding drift of a reviewed model resize, nothing more)
PARAM_BYTES_RATCHET_TOL = 0.10


def _load_sharding():
    """The profiling package's sharding parser, borrowed lazily (one
    layout-math implementation); None when unavailable — the DSS804
    loud-failure path."""
    try:
        from ...profiling import sharding as sharding_prof
    except Exception:
        return None
    return sharding_prof


def program_sharding(artifact: ProgramArtifact):
    """The sharding residency summary (profiling/sharding.py) of one
    artifact — declared-vs-materialized reconciliation included when
    the artifact carries a declared spec — memoized on the artifact;
    None when the parser is unavailable or the text holds no
    computation."""
    if "_sharding_summary" not in artifact.__dict__:
        summary = None
        mod = _load_sharding()
        if mod is not None and artifact.hlo:
            try:
                summary = mod.analyze_sharding(
                    artifact.hlo, declared=artifact.declared_sharding)
            except Exception:
                summary = None
        artifact.__dict__["_sharding_summary"] = summary
    return artifact.__dict__["_sharding_summary"]


def check_sharding(artifact: ProgramArtifact) -> List[Diagnostic]:
    """DSS801/DSS804 over one program: every declared-sharded tensor
    must materialize its divisor in the compiled layout."""
    if not artifact.hlo or artifact.declared_sharding is None:
        # nothing declared: no claim either way (pre-DSS8 sidecars
        # stay clean; engines always declare from this round on)
        return []
    if _load_sharding() is None:
        return [_pdiag(
            artifact, "DSS804",
            "sharding parser (deepspeed_tpu.profiling.sharding) "
            "unimportable in this environment — DSS801/DSS802/DSS803 "
            "were skipped, this program's parameter residency is "
            "UNVERIFIED")]
    summary = program_sharding(artifact)
    if summary is None:
        return []
    out: List[Diagnostic] = []
    for fam in sorted(summary["families"]):
        for mm in summary["families"][fam]["mismatches"]:
            if mm["bytes"] < SHARDING_MIN_TENSOR_BYTES:
                continue
            ddiv = mm["declared_divisor"]
            mdiv = max(mm["materialized_divisor"], 1)
            fold = ddiv // mdiv
            axes = "/".join(mm["axes"]) or "?"
            out.append(_pdiag(
                artifact, "DSS801",
                f"{fam} tensor ({mm['bytes']} bytes) declared sharded "
                f"over axis '{axes}' (÷{ddiv}) but materialized "
                f"{'replicated' if mdiv == 1 else f'÷{mdiv}'}: "
                f"per-device resident bytes ×{fold} "
                f"({mm['bytes'] // ddiv} declared -> "
                f"{mm['bytes'] // mdiv} actual bytes/device) — the "
                "silent dp-fold-of-memory shape (pin the layout with "
                "out_shardings/with_sharding_constraint)"))
    return out


def check_sharding_consistency(artifacts) -> List[Diagnostic]:
    """DSS802 across the programs of one run: a state family that
    materializes with different shard divisors in two programs pays an
    unpriced reshard at the boundary.  Reference layout per family =
    the program carrying the most matched bytes (names break ties);
    every disagreeing program gets one finding."""
    placements = {}  # family -> [(artifact, divisor, matched_bytes)]
    for artifact in artifacts:
        if artifact.declared_sharding is None:
            continue
        summary = program_sharding(artifact)
        if summary is None:
            continue
        for fam, info in summary["families"].items():
            if info["materialized_divisor"] is None:
                continue
            placements.setdefault(fam, []).append(
                (artifact, info["materialized_divisor"],
                 info["matched_bytes"]))
    out: List[Diagnostic] = []
    for fam in sorted(placements):
        entries = placements[fam]
        if len({div for _, div, _ in entries}) <= 1:
            continue
        ref_artifact, ref_div, _ = max(
            entries, key=lambda e: (e[2], e[0].name))
        for artifact, div, _ in sorted(entries, key=lambda e: e[0].name):
            if div == ref_div:
                continue
            resharded = _load_sharding()
            n_reshard = (resharded.count_reshard_ops(artifact.hlo)
                         if resharded is not None else 0)
            out.append(_pdiag(
                artifact, "DSS802",
                f"family '{fam}' materializes ÷{div} here but ÷"
                f"{ref_div} in [{ref_artifact.name}]: the program "
                "boundary pays an unpriced reshard (producer/consumer "
                f"layout mismatch; {n_reshard} all-to-all/"
                "collective-permute op(s) in this module) — align the "
                "out_shardings with the consumer or price an explicit "
                "reshard program"))
    return out


def sharding_metric_key(tag: str, name: str) -> str:
    """Baseline ``metrics`` key for one program's per-device parameter
    bytes.  TAG-qualified (unlike the exposure keys): the canonical CI
    fixtures (zero2-overlap dp4, offload dp1) share program names AND
    model geometry, and both must ratchet independently."""
    return f"<programs>|param_bytes_per_device|{tag}|{name}"


def _sharding_tag(artifact):
    tag = (artifact.declared_sharding or {}).get("tag")
    return str(tag) if tag else None


def sharding_metrics(artifacts) -> dict:
    """``{metric key: param_bytes_per_device}`` for every artifact
    whose params family matched the compiled layout — what
    ``--update-baseline`` records so DSS803 can ratchet resident
    parameter memory (the receipt half of ROADMAP item 2's ÷dp
    criterion)."""
    out = {}
    for artifact in artifacts:
        tag = _sharding_tag(artifact)
        if tag is None:
            continue
        summary = program_sharding(artifact)
        if summary is None or summary["param_bytes_per_device"] is None:
            continue
        out[sharding_metric_key(tag, artifact.name)] = float(
            summary["param_bytes_per_device"])
    return out


def check_sharding_ratchet(artifacts, baseline_metrics) -> List[Diagnostic]:
    """DSS803: programs whose re-analyzed per-device parameter bytes
    exceed the baseline-recorded figure by more than the tolerance.
    Programs without a recorded metric are not checked — the ratchet
    only ever tightens what a reviewer recorded."""
    out: List[Diagnostic] = []
    if not baseline_metrics:
        return out
    for artifact in artifacts:
        tag = _sharding_tag(artifact)
        if tag is None:
            continue
        recorded = baseline_metrics.get(
            sharding_metric_key(tag, artifact.name))
        if recorded is None:
            continue
        summary = program_sharding(artifact)
        if summary is None or summary["param_bytes_per_device"] is None:
            continue
        current = float(summary["param_bytes_per_device"])
        ceiling = float(recorded) * (1.0 + PARAM_BYTES_RATCHET_TOL)
        if current > ceiling:
            out.append(_pdiag(
                artifact, "DSS803",
                f"param_bytes_per_device grew {float(recorded):.0f} -> "
                f"{current:.0f} (+{PARAM_BYTES_RATCHET_TOL:.0%} "
                "tolerance exceeded): resident parameter memory is "
                "regressing (weakened sharding or re-replicated "
                "state) — restore the layout or re-record with "
                "--update-baseline"))
    return out


def program_overlap(artifact: ProgramArtifact):
    """The overlap/critical-path analysis (profiling/overlap.py) for
    one artifact, memoized on the artifact; None when the analyzer is
    unavailable or the text holds no computation."""
    if "_overlap_summary" not in artifact.__dict__:
        summary = None
        try:
            from ...profiling import overlap as overlap_prof

            # max_nodes=None: the rule checks must see EVERY node — a
            # collective-heavy program truncated at the telemetry cap
            # would silently drop the declared host-stream node (it is
            # appended last) and every finding past the cap
            summary = overlap_prof.analyze_hlo(
                artifact.hlo,
                total_devices=artifact.total_devices,
                device_kind=artifact.device_kind or "",
                declared_host_wire_bytes=(
                    artifact.host_state_wire_bytes or 0),
                declared_host_stream=artifact.host_stream_schedule,
                declared_collective_schedule=artifact.collective_schedule,
                max_nodes=None)
        except Exception:
            summary = None
        artifact.__dict__["_overlap_summary"] = summary
    return artifact.__dict__["_overlap_summary"]


# relative growth of a program's exposed_wire_seconds beyond its
# baseline-recorded metric that trips DSO704 (generous: the figure is
# model-derived and roofline-table sensitive)
EXPOSED_WIRE_RATCHET_TOL = 0.25
# absolute floor on the ratchet ceiling: a recorded metric at (or
# rounding to) 0.0 must not make every epsilon of cost-model noise a
# CI failure — 10 µs of exposure is below anything worth gating
EXPOSED_WIRE_RATCHET_EPS = 1e-5


def exposure_metric_key(name: str) -> str:
    """Baseline ``metrics`` key for one program's exposed wire."""
    return f"<programs>|exposed_wire_seconds|{name}"


def comm_exposure_metric_key(name: str, tag=None) -> str:
    """Baseline ``metrics`` key for one program's exposed COLLECTIVE
    wire under a declared overlap_comm schedule.  A distinct metric
    name, not a reuse of :func:`exposure_metric_key`: the checked-in
    baseline records the offload fixture's host-stream exposure and the
    zero-2 fixture's collective exposure for programs that share the
    ``train_step`` name — one key would collide across the two
    recorded run dirs.  TAG-qualified when the artifact declares a
    sharding tag (round 20: the zero-2-overlap AND stage-3 fixtures
    both dump an overlapped ``train_step`` with the same model
    geometry — a name-only key would be last-write-wins across the
    recorded run dirs, corrupting whichever fixture regenerated
    first); ``tag=None`` keeps the legacy name-only form for
    artifacts without a declared sharding."""
    if tag:
        return f"<programs>|comm_exposed_wire_seconds|{tag}|{name}"
    return f"<programs>|comm_exposed_wire_seconds|{name}"


def _exposure_keys(artifact):
    """The baseline metric keys this artifact ratchets under: the
    host-stream key when it declares an offload stream, the
    collective key when it declares an OVERLAPPED bucketed exchange
    (a serialized control must not record/ratchet its own exposure —
    it exists to be worse)."""
    keys = []
    if artifact.host_state_wire_bytes:
        keys.append(exposure_metric_key(artifact.name))
    if (artifact.collective_schedule or {}).get("overlap"):
        keys.append(comm_exposure_metric_key(artifact.name,
                                             _sharding_tag(artifact)))
    return keys


def exposure_metrics(artifacts) -> dict:
    """``{metric key: exposed_wire_seconds}`` for every artifact that
    declares a host stream or an overlapped collective schedule — what
    ``--update-baseline`` records so a later run can ratchet against
    it (``check_exposure_ratchet``)."""
    out = {}
    for artifact in artifacts:
        keys = _exposure_keys(artifact)
        if not keys:
            continue
        summary = program_overlap(artifact)
        if summary is None:
            continue
        for key in keys:
            out[key] = round(float(summary["exposed_wire_seconds"]), 9)
    return out


def check_exposure_ratchet(artifacts, baseline_metrics) -> List[Diagnostic]:
    """DSO704: programs whose re-analyzed exposed wire exceeds the
    baseline-recorded metric by more than the tolerance.  Programs
    without a recorded metric are not checked (the ratchet only ever
    tightens what a reviewer recorded)."""
    out: List[Diagnostic] = []
    if not baseline_metrics:
        return out
    for artifact in artifacts:
        recorded = None
        for key in _exposure_keys(artifact):
            if baseline_metrics.get(key) is not None:
                recorded = baseline_metrics[key]
                break
        if recorded is None:
            continue
        summary = program_overlap(artifact)
        if summary is None:
            continue
        current = float(summary["exposed_wire_seconds"])
        ceiling = (float(recorded) * (1.0 + EXPOSED_WIRE_RATCHET_TOL)
                   + EXPOSED_WIRE_RATCHET_EPS)
        if current > ceiling:
            out.append(_pdiag(
                artifact, "DSO704",
                f"exposed_wire_seconds grew {float(recorded):.6f} -> "
                f"{current:.6f} (+{EXPOSED_WIRE_RATCHET_TOL:.0%} "
                "tolerance exceeded): the stream/exchange is "
                "re-serializing — restore the overlapped schedule or "
                "re-record with --update-baseline"))
    return out


# two-sided drift band on the re-analyzed predicted_step_seconds vs the
# baseline-recorded figure (model-derived and deterministic per
# toolchain, so a generous band only catches real declaration drift)
PREDICTED_STEP_RATCHET_TOL = 0.25
PREDICTED_STEP_RATCHET_EPS = 1e-5
# absolute headroom over the recorded step_unexplained_fraction ceiling
# (the fraction is measured-latency-derived, hence noisy)
UNEXPLAINED_RATCHET_MARGIN = 0.05


def predicted_step_metric_key(name: str) -> str:
    """Baseline ``metrics`` key for one program's predicted step
    seconds (the attribution budget's deterministic half)."""
    return f"<programs>|predicted_step_seconds|{name}"


def unexplained_metric_key(name: str) -> str:
    """Baseline ``metrics`` key for one program's reconciled
    unexplained-fraction ceiling (the measured half; recorded only
    when the run dir carries latency evidence)."""
    return f"<programs>|step_unexplained_fraction|{name}"


def program_attribution(artifact: ProgramArtifact):
    """The attribution phase budget (profiling/attribution) of one
    artifact's re-analyzed overlap summary; None when the analyzer is
    unavailable or the text holds no computation."""
    summary = program_overlap(artifact)
    if summary is None:
        return None
    try:
        from ...profiling import attribution as attr_prof
    except Exception:
        return None
    return attr_prof.program_budget(summary)


def _run_dir_measured_p50(run_dir):
    """Fleet-median measured p50 seconds from a run dir's
    ``latency-rank*.json`` skew-exchange files (the offline CLI's
    measured evidence); None when the dir holds none or the profiling
    package is unavailable."""
    if not run_dir:
        return None
    try:
        from ...profiling import attribution as attr_prof
        from ...profiling import comm as comm_prof
    except Exception:
        return None
    # relative staleness guard: an elastic run leaves dead ranks' last
    # publishes behind, and offline analysis cannot use wall-clock age
    fleet = attr_prof.fresh_fleet_snapshots(
        comm_prof.read_fleet_latencies(str(run_dir)))
    vals = [float(snap["p50"]) for snap in fleet.values()
            if snap.get("p50") and float(snap["p50"]) > 0]
    return attr_prof.median_of_window(vals, window=max(len(vals), 1))


def attribution_metrics(artifacts, run_dir=None) -> dict:
    """Attribution metric entries for ``--update-baseline``: per
    host-stream-declaring program (the same gating as
    :func:`exposure_metrics` — the offload step is the canonical CI
    anchor), the re-analyzed ``predicted_step_seconds`` and — when the
    run dir carries measured latency — the reconciled
    ``step_unexplained_fraction`` as the recorded ceiling.

    Metric keys are PROGRAM-NAME-scoped (the DSO704 exposure-metric
    convention): recording over multiple ``--programs`` dirs that dump
    the same program name collapses to one figure (last dir wins).
    The checked-in baseline anchors exactly one run dir; keep it that
    way, or name programs distinctly across dirs."""
    out = {}
    measured = _run_dir_measured_p50(run_dir)
    for artifact in artifacts:
        if not artifact.host_state_wire_bytes:
            continue
        budget = program_attribution(artifact)
        if budget is None:
            continue
        predicted = float(budget["predicted_seconds"])
        out[predicted_step_metric_key(artifact.name)] = round(predicted, 9)
        if measured and measured > 0:
            out[unexplained_metric_key(artifact.name)] = round(
                (measured - predicted) / measured, 6)
    return out


def check_attribution_ratchet(artifacts_by_dir,
                              baseline_metrics) -> List[Diagnostic]:
    """DSO705 over ``[(run_dir, artifacts)]``: programs whose
    re-analyzed predicted step drifts beyond the two-sided band around
    the recorded figure, or whose reconciled unexplained fraction (when
    the run dir carries measured latency) exceeds the recorded ceiling
    plus margin.  Programs without a recorded metric are not checked —
    the ratchet only ever tightens what a reviewer recorded."""
    out: List[Diagnostic] = []
    if not baseline_metrics:
        return out
    for run_dir, artifacts in artifacts_by_dir:
        measured = None
        measured_resolved = False
        for artifact in artifacts:
            if not artifact.host_state_wire_bytes:
                # the attribution metrics are recorded ONLY for
                # host-stream-declaring programs (attribution_metrics'
                # gate); a same-NAMED program from another fixture dir
                # (the zero-2 overlap fixture's train_step vs the
                # offload fixture's) must not ratchet against it
                continue
            rec_pred = baseline_metrics.get(
                predicted_step_metric_key(artifact.name))
            rec_ceil = baseline_metrics.get(
                unexplained_metric_key(artifact.name))
            if rec_pred is None and rec_ceil is None:
                continue
            budget = program_attribution(artifact)
            if budget is None:
                continue
            predicted = float(budget["predicted_seconds"])
            if rec_pred is not None:
                band = (abs(float(rec_pred)) * PREDICTED_STEP_RATCHET_TOL
                        + PREDICTED_STEP_RATCHET_EPS)
                if abs(predicted - float(rec_pred)) > band:
                    out.append(_pdiag(
                        artifact, "DSO705",
                        f"predicted_step_seconds drifted "
                        f"{float(rec_pred):.6f} -> {predicted:.6f} "
                        f"(±{PREDICTED_STEP_RATCHET_TOL:.0%} band "
                        "exceeded): the declared budget changed — "
                        "re-reconcile or re-record with "
                        "--update-baseline"))
            if rec_ceil is None:
                continue
            if not measured_resolved:
                measured = _run_dir_measured_p50(run_dir)
                measured_resolved = True
            if not measured:
                continue
            fraction = (measured - predicted) / measured
            if fraction > float(rec_ceil) + UNEXPLAINED_RATCHET_MARGIN:
                out.append(_pdiag(
                    artifact, "DSO705",
                    f"step_unexplained_fraction {fraction:.4f} exceeds "
                    f"the recorded ceiling {float(rec_ceil):.4f} "
                    f"(+{UNEXPLAINED_RATCHET_MARGIN} margin): the "
                    "budget no longer explains the measured step — "
                    "re-reconcile or re-record with --update-baseline"))
    return out


def check_overlap(artifact: ProgramArtifact) -> List[Diagnostic]:
    """DSO701/DSO702/DSO703 over one program's overlap analysis.

    One finding per (rule, program), aggregating every offending node:
    the ratchet baseline keys on (rule, program), so per-node findings
    would break the baseline count on any re-dump that re-splits the
    stream."""
    if not artifact.hlo:
        return []
    try:
        from ...profiling.overlap import (DSO701_MIN_WINDOW_SECONDS,
                                          KIND_COLLECTIVE, KIND_HOST,
                                          MAX_WINDOW_INSTRUCTIONS,
                                          SERIALIZED)
    except Exception:
        # the profiling package is unimportable — check_collectives'
        # DSP614 already says every HLO-side heuristic was skipped; a
        # second flag would be noise
        return []
    summary = program_overlap(artifact)
    if summary is None:
        # header-only artifact (no computation body): nothing is
        # scheduled, so there is no overlap to verify — same silence as
        # an empty collective walk
        return []
    out: List[Diagnostic] = []

    nodes = summary.get("nodes") or []
    # Window analysis degrades to None past MAX_WINDOW_INSTRUCTIONS —
    # exactly the production-size programs the analyzer targets.  The
    # window-gated checks below then never fire, and silence would
    # read as overlap-clean: say so loudly instead (the DSP614
    # contract).  Declared-stream nodes carry an explicit window and
    # are unaffected.
    unknown = [n for n in nodes
               if n["classification"] == SERIALIZED and n["seconds"] > 0
               and n.get("window_seconds") is None]
    if unknown:
        out.append(_pdiag(
            artifact, "DSP614",
            f"{len(unknown)} serialized wire node(s) have UNKNOWN "
            "independent-compute windows (program exceeds the "
            f"{MAX_WINDOW_INSTRUCTIONS}-instruction window-analysis "
            "cap) — the DSO701/DSO702 window checks did NOT run for "
            "them; their exposure is UNVERIFIED, not clean"))
    # DSO701: serialized collectives with a real window to hide them.
    # Two windows count: the DAG-independence window (floored at
    # DSO701_MIN_WINDOW_SECONDS — micro-programs have nothing to
    # overlap with), and the DECLARED potential window on nodes covered
    # by an overlap_comm collective schedule with overlap off
    # (source "hlo+declared"): there the ENGINE declared a bucketed
    # schedule exists that would free the window, so any nonzero
    # potential fires — the serialized control's receipt.
    declared_off = (artifact.collective_schedule is not None
                    and not artifact.collective_schedule.get("overlap"))
    declared_on = (artifact.collective_schedule is not None
                   and bool(artifact.collective_schedule.get("overlap")))

    def _fires(n):
        if n.get("source") == "hlo+declared":
            # scheduled exchange nodes: under an OVERLAPPED schedule
            # the residual exposure is the priced fill/drain — the
            # DSO704 exposure ratchet owns it, not DSO701; under the
            # serialized control ANY declared potential window fires
            # (the engine itself declared bucketing would free it)
            if declared_on:
                return False
            return (declared_off
                    and (n.get("window_seconds") or 0.0) > 0)
        return ((n.get("window_seconds") or 0.0)
                >= DSO701_MIN_WINDOW_SECONDS)

    culprits = [n for n in nodes
                if n["kind"] == KIND_COLLECTIVE
                and n["classification"] == SERIALIZED
                and n["seconds"] > 0 and _fires(n)]
    if culprits:
        wire_ms = sum(n["seconds"] for n in culprits) * 1e3
        window_ms = max(n["window_seconds"] for n in culprits) * 1e3
        declared = any(n.get("source") == "hlo+declared"
                       for n in culprits)
        hint = (" — overlap_comm would bucket and hide this exchange"
                if declared else
                " (no -start/-done overlap materialized)")
        out.append(_pdiag(
            artifact, "DSO701",
            f"{len(culprits)} fully serialized collective(s) paying "
            f"{wire_ms:.3f} ms of exposed wire with up to "
            f"{window_ms:.3f} ms of independent compute available to "
            f"hide them{hint}"))
    # DSO702: serialized host transfers next to independent compute
    host = [n for n in nodes
            if n["kind"] == KIND_HOST
            and n["classification"] == SERIALIZED
            and n["seconds"] > 0
            and (n.get("window_seconds") or 0.0) > 0]
    if host:
        total_bytes = sum(n["wire_bytes"] for n in host)
        exposed_ms = sum(n["seconds"] - n["hidden_seconds"]
                         for n in host) * 1e3
        sources = sorted({n["source"] for n in host})
        out.append(_pdiag(
            artifact, "DSO702",
            f"{len(host)} serialized host transfer(s) ({total_bytes} "
            f"bytes, {exposed_ms:.3f} ms exposed wire; source: "
            f"{'/'.join(sources)}) adjacent to an independent compute "
            "region — the offload tax, statically (exposed_wire_"
            f"seconds={summary['exposed_wire_seconds']:.6f})"))
    # DSO703: recorded exposure vs re-analysis
    recorded = (artifact.comm or {}).get("overlap")
    if recorded:
        drifts = []
        for field in ("wire_seconds", "exposed_wire_seconds"):
            rec_v, new_v = recorded.get(field), summary[field]
            if rec_v is None:
                continue
            tol = max(abs(new_v), 1e-12) * 0.05
            if abs(float(rec_v) - float(new_v)) > tol:
                drifts.append(f"{field} {rec_v} -> {new_v}")
        for field in ("collectives", "host_transfers"):
            rec_v = (recorded.get(field) or {}).get("total")
            if rec_v is not None and rec_v != summary[field]["total"]:
                drifts.append(
                    f"{field} {rec_v} -> {summary[field]['total']}")
        if drifts:
            out.append(_pdiag(
                artifact, "DSO703",
                "recorded overlap summary drifts from the HLO "
                f"re-analysis: {'; '.join(drifts)} (stale or tampered "
                "artifact)"))
    return out


def verify_program(artifact: ProgramArtifact) -> List[Diagnostic]:
    """All DSP6xx/DSO7xx/DSS8xx HLO-side diagnostics for one program
    artifact."""
    if not artifact.hlo:
        # a sidecar whose HLO text is missing/empty would otherwise
        # make every HLO-side rule early-return — "verified clean" on
        # exactly the stale/tampered-dump scenario DSP613 exists for
        return [_pdiag(
            artifact, "DSP613",
            "sidecar present but the program's HLO text is missing or "
            "empty — artifact unverifiable (stale or tampered dump; "
            "re-dump with profiling.program_dump enabled)")]
    return (check_donation(artifact) + check_collectives(artifact)
            + check_overlap(artifact) + check_sharding(artifact))


def verify_artifacts(artifacts) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    for artifact in artifacts:
        out.extend(verify_program(artifact))
    out.extend(check_sharding_consistency(artifacts))
    out.sort(key=lambda d: (d.path, d.rule_id, d.message))
    return out


# ---------------------------------------------------------------------------
# DSP603: AST dataflow — read-after-donation in driver code
# ---------------------------------------------------------------------------

_NUMPY_ALLOC_FNS = {"zeros", "empty", "ones", "full", "asarray", "array",
                    "frombuffer", "copy", "ascontiguousarray",
                    "zeros_like", "empty_like"}
_MISSING = object()


def _literal_argnums(node) -> Optional[Tuple[int, ...]]:
    """Literal donate_argnums value -> positions tuple, None when the
    expression is computed (engine-style ``donate`` variables)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            if not (isinstance(elt, ast.Constant)
                    and isinstance(elt.value, int)):
                return None
            out.append(elt.value)
        return tuple(out)
    return None


def _donating_jit_spec(expr):
    """donate positions of the first ``jit(..., donate_argnums=...)``
    call inside ``expr`` (``_MISSING`` when none)."""
    for sub in ast.walk(expr):
        if not isinstance(sub, ast.Call):
            continue
        if call_name(sub).rsplit(".", 1)[-1] != "jit":
            continue
        for kw in sub.keywords:
            if kw.arg == "donate_argnums":
                return _literal_argnums(kw.value)
    return _MISSING


def _target_key(tgt) -> Optional[str]:
    if isinstance(tgt, ast.Name):
        return tgt.id
    if (isinstance(tgt, ast.Attribute) and isinstance(tgt.value, ast.Name)
            and tgt.value.id == "self"):
        return f"self.{tgt.attr}"
    return None


def _callee_key(call: ast.Call) -> Optional[str]:
    if isinstance(call.func, ast.Name):
        return call.func.id
    if (isinstance(call.func, ast.Attribute)
            and isinstance(call.func.value, ast.Name)
            and call.func.value.id == "self"):
        return f"self.{call.func.attr}"
    return None


def _collect_donors(tree) -> Dict[str, Optional[Tuple[int, ...]]]:
    """Names (``x`` / ``self.x``) bound to donating jit callables
    anywhere in the module, with their donated positions (None =
    positions not statically known)."""
    donors: Dict[str, Optional[Tuple[int, ...]]] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        key = _target_key(node.targets[0])
        if key is None:
            continue
        spec = _donating_jit_spec(node.value)
        if spec is not _MISSING:
            donors[key] = spec
    return donors


def _is_numpy_alloc(expr) -> bool:
    return (isinstance(expr, ast.Call)
            and isinstance(expr.func, ast.Attribute)
            and expr.func.attr in _NUMPY_ALLOC_FNS
            and isinstance(expr.func.value, ast.Name)
            and expr.func.value.id in ("np", "numpy"))


def _is_device_put(expr) -> bool:
    return (isinstance(expr, ast.Call)
            and call_name(expr).rsplit(".", 1)[-1] == "device_put")


def check_use_after_donation(pf: ParsedFile,
                             index=None) -> List[Diagnostic]:
    """The DSP603 dataflow pass over one module.

    Intra-procedural and name-based by design: only plain local names
    are tracked (engine code passing ``self.state[...]`` pytree slots
    that the call's outputs re-bind is the sanctioned pattern and never
    matches).  A later re-binding of the name clears the watch."""
    from .analysis import ModuleIndex, body_nodes

    if index is None:
        index = ModuleIndex(pf.tree)
    donors = _collect_donors(pf.tree)
    out: List[Diagnostic] = []
    for fn in index.functions:
        # last simple assignment per local name (for device_put / numpy
        # staging provenance), in source order
        assigns: Dict[str, ast.expr] = {}
        events = []  # (lineno, col, kind, payload)
        for node, _ in body_nodes(fn, index.node_map):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                events.append((node.lineno, node.col_offset, "assign",
                               (node.targets[0].id, node.value)))
            elif isinstance(node, ast.Name):
                if isinstance(node.ctx, ast.Store):
                    events.append((node.lineno, node.col_offset, "store",
                                   node.id))
                elif isinstance(node.ctx, (ast.Del,)):
                    events.append((node.lineno, node.col_offset, "store",
                                   node.id))
                elif isinstance(node.ctx, ast.Load):
                    events.append((node.lineno, node.col_offset, "load",
                                   node))
            if isinstance(node, ast.Call):
                callee = _callee_key(node)
                if callee in donors:
                    events.append((node.lineno, node.col_offset, "donate",
                                   (node, donors[callee], callee)))
        # within one statement line: argument loads evaluate first, then
        # the donating call, then the target re-binding — so
        # ``acc = donor(acc)`` watches and immediately clears ``acc``
        _PRIO = {"load": 0, "donate": 1, "assign": 2, "store": 2}
        events.sort(key=lambda e: (e[0], _PRIO[e[2]], e[1]))

        # watched[name] -> (donating call node, callee, staged_numpy)
        watched: Dict[str, tuple] = {}
        for lineno, col, kind, payload in events:
            if kind == "assign":
                name, value = payload
                assigns[name] = value
                watched.pop(name, None)
            elif kind == "store":
                watched.pop(payload, None)
            elif kind == "donate":
                call, positions, callee = payload
                if positions is None:
                    # computed donate_argnums: only the high-confidence
                    # staged-numpy shape is worth flagging
                    cand = list(enumerate(call.args))
                else:
                    cand = [(i, call.args[i]) for i in positions
                            if i < len(call.args)]
                for i, arg in cand:
                    names = []
                    staged = False
                    src = arg
                    if isinstance(src, ast.Name):
                        names.append(src.id)
                        src = assigns.get(src.id, src)
                    if _is_device_put(src) and src.args \
                            and isinstance(src.args[0], ast.Name):
                        base = src.args[0].id
                        names.append(base)
                        staged = _is_numpy_alloc(assigns.get(base, base))
                    if positions is None and not staged:
                        continue
                    for nm in names:
                        watched[nm] = (call, callee, staged)
            elif kind == "load":
                node = payload
                info = watched.get(node.id)
                if info is None:
                    continue
                call_end = getattr(info[0], "end_lineno", info[0].lineno)
                if node.lineno <= (call_end or info[0].lineno):
                    continue
                call, callee, staged = info
                extra = (" — and it is a live numpy STAGING buffer whose "
                         "memory the runtime may free (heap corruption)"
                         if staged else "")
                # no line number in the message: baseline keys embed the
                # message verbatim, and line numbers drift with
                # unrelated edits (the diagnostic's own location already
                # points at the read site)
                out.append(diag(
                    pf, node, "DSP603",
                    f"'{node.id}' read after being donated to "
                    f"{callee}(...): the buffer may already be reused "
                    f"by its outputs{extra}"))
    return out


@register_file_checker
def check_donation_dataflow(pf: ParsedFile) -> List[Diagnostic]:
    return check_use_after_donation(pf)
