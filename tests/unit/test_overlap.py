"""DSO7xx overlap-analyzer tests (``profiling/overlap.py`` +
``tools/dslint/programs.py`` rules + CLI surfaces).

Hand-written scheduled-HLO fixtures pin every layer: the instruction
/ computation parser, the roofline cost model and critical path, the
host/p2p transfer parser (the CommLedger satellite), the per-node
overlap classification (sync = serialized, async pair hidden by the
schedule window between ``-start`` and ``-done``), the DSO701/702/703
rules, the ``--sarif`` CLI output round-tripped against ``--json``,
and the bench-schema registration of the exposure receipts.

All figures below assume the v5e table in ``profiling/utilization.py``
(peak 197 TF/s, HBM 819 GB/s, ICI 45 GB/s, host 14 GB/s): an
f32[8192,8192] dot costs ~5.6 ms (flops-bound), the f32[1024,8192]
group-4 all-reduce moves 2·(3/4)·32 MiB ≈ 50 MiB of wire ≈ 1.1 ms.
"""

import io
import json
import os
from contextlib import redirect_stdout

from deepspeed_tpu.profiling import overlap as ov
from deepspeed_tpu.profiling.utilization import chip_specs
from deepspeed_tpu.tools.dslint import programs as dsp
from deepspeed_tpu.tools.dslint.cli import main as dslint_main

V5E = chip_specs("TPU v5e")

_HEADER = "HloModule fixture, is_scheduled=true\n\n"

_BIG_DOT = ("  %dot.big = f32[8192,8192]{1,0} dot(f32[8192,8192]{1,0} "
            "%p1, f32[8192,8192]{1,0} %p1), lhs_contracting_dims={1}, "
            "rhs_contracting_dims={0}\n")

# sync all-reduce next to an independent flops-bound dot: fully
# serialized by construction, with a >1 ms window available -> DSO701
SERIAL_AR = _HEADER + (
    "ENTRY %main.1 (p0: f32[1024,8192], p1: f32[8192,8192]) -> "
    "(f32[1024,8192], f32[8192,8192]) {\n"
    "  %p0 = f32[1024,8192]{1,0} parameter(0)\n"
    "  %p1 = f32[8192,8192]{1,0} parameter(1)\n"
    + _BIG_DOT +
    "  %all-reduce.1 = f32[1024,8192]{1,0} all-reduce("
    "f32[1024,8192]{1,0} %p0), replica_groups={{0,1,2,3}}\n"
    "  ROOT %tuple.1 = (f32[1024,8192]{1,0}, f32[8192,8192]{1,0}) "
    "tuple(%all-reduce.1, %dot.big)\n"
    "}\n")

# the same collective as an async pair with the dot scheduled inside
# the start/done window: hidden compute >= wire -> overlapped, clean
OVERLAPPED_AR = _HEADER + (
    "ENTRY %main.1 (p0: f32[1024,8192], p1: f32[8192,8192]) -> "
    "(f32[1024,8192], f32[8192,8192]) {\n"
    "  %p0 = f32[1024,8192]{1,0} parameter(0)\n"
    "  %p1 = f32[8192,8192]{1,0} parameter(1)\n"
    "  %all-reduce-start.1 = (f32[1024,8192]{1,0}, f32[1024,8192]{1,0})"
    " all-reduce-start(f32[1024,8192]{1,0} %p0), "
    "replica_groups={{0,1,2,3}}\n"
    + _BIG_DOT +
    "  %all-reduce-done.1 = f32[1024,8192]{1,0} all-reduce-done("
    "(f32[1024,8192]{1,0}, f32[1024,8192]{1,0}) %all-reduce-start.1)\n"
    "  ROOT %tuple.1 = (f32[1024,8192]{1,0}, f32[8192,8192]{1,0}) "
    "tuple(%all-reduce-done.1, %dot.big)\n"
    "}\n")

# async pair hiding only a smaller dot: 0 < hidden < wire -> partial
PARTIAL_AR = _HEADER + (
    "ENTRY %main.1 (p0: f32[1024,8192], p1: f32[4096,4096]) -> "
    "(f32[1024,8192], f32[4096,4096]) {\n"
    "  %p0 = f32[1024,8192]{1,0} parameter(0)\n"
    "  %p1 = f32[4096,4096]{1,0} parameter(1)\n"
    "  %all-reduce-start.1 = (f32[1024,8192]{1,0}, f32[1024,8192]{1,0})"
    " all-reduce-start(f32[1024,8192]{1,0} %p0), "
    "replica_groups={{0,1,2,3}}\n"
    "  %dot.small = f32[4096,4096]{1,0} dot(f32[4096,4096]{1,0} %p1, "
    "f32[4096,4096]{1,0} %p1), lhs_contracting_dims={1}, "
    "rhs_contracting_dims={0}\n"
    "  %all-reduce-done.1 = f32[1024,8192]{1,0} all-reduce-done("
    "(f32[1024,8192]{1,0}, f32[1024,8192]{1,0}) %all-reduce-start.1)\n"
    "  ROOT %tuple.1 = (f32[1024,8192]{1,0}, f32[4096,4096]{1,0}) "
    "tuple(%all-reduce-done.1, %dot.small)\n"
    "}\n")

# a host copy pair the scheduler left back-to-back, next to an
# independent dot -> DSO702 (the offload tax, HLO-visible form)
SERIAL_HOST_COPY = _HEADER + (
    "ENTRY %main.1 (p0: f32[8388608], p1: f32[8192,8192]) -> "
    "(f32[8388608], f32[8192,8192]) {\n"
    "  %p0 = f32[8388608]{0} parameter(0)\n"
    "  %p1 = f32[8192,8192]{1,0} parameter(1)\n"
    "  %copy-start.1 = (f32[8388608]{0:S(5)}, f32[8388608]{0}, u32[]) "
    "copy-start(f32[8388608]{0} %p0)\n"
    "  %copy-done.1 = f32[8388608]{0:S(5)} copy-done("
    "(f32[8388608]{0:S(5)}, f32[8388608]{0}, u32[]) %copy-start.1)\n"
    + _BIG_DOT +
    "  ROOT %tuple.1 = (f32[8388608]{0:S(5)}, f32[8192,8192]{1,0}) "
    "tuple(%copy-done.1, %dot.big)\n"
    "}\n")

# pure-compute module for critical-path / declared-stream tests
COMPUTE_ONLY = _HEADER + (
    "ENTRY %main.1 (p0: f32[4096,4096]) -> f32[4096,4096] {\n"
    "  %p0 = f32[4096,4096]{1,0} parameter(0)\n"
    "  %dot.1 = f32[4096,4096]{1,0} dot(f32[4096,4096]{1,0} %p0, "
    "f32[4096,4096]{1,0} %p0), lhs_contracting_dims={1}, "
    "rhs_contracting_dims={0}\n"
    "  %dot.2 = f32[4096,4096]{1,0} dot(f32[4096,4096]{1,0} %dot.1, "
    "f32[4096,4096]{1,0} %p0), lhs_contracting_dims={1}, "
    "rhs_contracting_dims={0}\n"
    "  %dot.3 = f32[4096,4096]{1,0} dot(f32[4096,4096]{1,0} %p0, "
    "f32[4096,4096]{1,0} %p0), lhs_contracting_dims={1}, "
    "rhs_contracting_dims={0}\n"
    "  ROOT %tuple.1 = (f32[4096,4096]{1,0}, f32[4096,4096]{1,0}) "
    "tuple(%dot.2, %dot.3)\n"
    "}\n")


def _ar_wire_seconds():
    # f32[1024,8192] = 32 MiB; ring all-reduce over group 4 moves
    # 2*(3/4) of it; ICI 45 GB/s
    payload = 1024 * 8192 * 4
    return 2 * payload * 3 // 4 / (V5E["ici_gbps"] * 1e9)


def _dot_seconds(n):
    return 2 * n ** 3 / (V5E["peak_tflops"] * 1e12)


# ------------------------------------------------------------ parsing
def test_parse_computations_and_instructions():
    comps, entry, scheduled = ov.parse_hlo_computations(SERIAL_AR)
    assert scheduled and entry == "main.1"
    main = comps["main.1"]
    assert [i.op for i in main.instructions] == [
        "parameter", "parameter", "dot", "all-reduce", "tuple"]
    ar = main.by_name["all-reduce.1"]
    assert "%p0" in ar.operands and "replica_groups" in ar.attrs


def test_parse_hlo_transfers_and_summary():
    hlo = (
        "  %copy-start.1 = (f32[1024]{0:S(5)}, f32[1024]{0}, u32[]) "
        "copy-start(f32[1024]{0} %a)\n"
        "  %copy-done.1 = f32[1024]{0:S(5)} copy-done(%copy-start.1)\n"
        "  %copy-start.2 = (f32[256]{0}, f32[256]{0}, u32[]) "
        "copy-start(f32[256]{0} %b)\n"
        "  %send.1 = (f32[512]{0}, u32[], token[]) send(f32[512]{0} "
        "%c, token[] %tok), channel_id=1, is_host_transfer=true\n"
        "  %send-done.1 = token[] send-done(%send.1), channel_id=1\n"
        "  %recv.1 = (f32[2048]{0}, u32[], token[]) recv(token[] "
        "%tok2), channel_id=2\n"
        "  %recv-done.1 = (f32[2048]{0}, token[]) recv-done(%recv.1)\n")
    recs = ov.parse_hlo_transfers(hlo)
    # -done halves never double-count; the async result tuple takes its
    # LARGEST element, not the sum
    assert [(r["op"], r["bytes"], r["host"]) for r in recs] == [
        ("copy-start", 4096, True),    # S(5): a host DMA
        ("copy-start", 1024, False),   # device-local async copy
        ("send", 2048, True),          # is_host_transfer=true
        ("recv", 8192, False),         # device point-to-point
    ]
    assert ov.transfer_summary(recs) == {
        "host_transfers": 2, "host_transfer_bytes": 4096 + 2048,
        "p2p_transfers": 1, "p2p_transfer_bytes": 8192}


def test_critical_path_vs_total_compute():
    s = ov.analyze_hlo(COMPUTE_ONLY, device_kind="TPU v5e")
    d = _dot_seconds(4096)
    # three equal dots, two chained: cp = 2 dots, compute total = 3
    assert abs(s["compute_seconds"] - 3 * d) / d < 0.1
    assert abs(s["critical_path_seconds"] - 2 * d) / d < 0.1
    assert s["wire_seconds"] == 0 and s["overlap_fraction"] == 1.0


def test_called_computations_are_not_double_counted():
    """A fusion body's cost is charged at the call site (whose roofline
    folds the body flops in) — summing the body computation again would
    report ~2x compute for fully-fused programs."""
    hlo = _HEADER + (
        "%fused_computation (param_0: f32[4096,4096]) -> "
        "f32[4096,4096] {\n"
        "  %param_0 = f32[4096,4096]{1,0} parameter(0)\n"
        "  ROOT %dot.f = f32[4096,4096]{1,0} dot(f32[4096,4096]{1,0} "
        "%param_0, f32[4096,4096]{1,0} %param_0), "
        "lhs_contracting_dims={1}, rhs_contracting_dims={0}\n"
        "}\n\n"
        "ENTRY %main.1 (p0: f32[4096,4096]) -> f32[4096,4096] {\n"
        "  %p0 = f32[4096,4096]{1,0} parameter(0)\n"
        "  ROOT %fusion.1 = f32[4096,4096]{1,0} fusion("
        "f32[4096,4096]{1,0} %p0), kind=kLoop, "
        "calls=%fused_computation\n"
        "}\n")
    s = ov.analyze_hlo(hlo, device_kind="TPU v5e")
    d = _dot_seconds(4096)
    assert abs(s["compute_seconds"] - d) / d < 0.1
    assert abs(s["critical_path_seconds"] - d) / d < 0.1


# --------------------------------------------------- classification
def test_sync_collective_is_serialized_with_window():
    s = ov.analyze_hlo(SERIAL_AR, total_devices=4, device_kind="TPU v5e")
    assert s["collectives"] == {"total": 1, "overlapped": 0,
                                "partially_exposed": 0, "serialized": 1}
    (node,) = s["nodes"]
    assert node["classification"] == ov.SERIALIZED
    assert abs(node["seconds"] - _ar_wire_seconds()) < 1e-6
    # the big dot is independent of the all-reduce: its ~5.6 ms is the
    # available window
    assert node["window_seconds"] > ov.DSO701_MIN_WINDOW_SECONDS
    assert s["exposed_wire_seconds"] == s["wire_seconds"] > 0
    assert s["overlap_fraction"] == 0.0


def test_async_pair_fully_hidden_is_overlapped():
    s = ov.analyze_hlo(OVERLAPPED_AR, total_devices=4,
                       device_kind="TPU v5e")
    assert s["collectives"]["overlapped"] == 1
    assert s["exposed_wire_seconds"] == 0.0
    assert s["overlap_fraction"] == 1.0
    # the hidden wire must not stretch the critical path beyond the
    # compute that hides it (start issues at t~0, dot covers the wire)
    assert s["critical_path_seconds"] < _ar_wire_seconds() + \
        _dot_seconds(8192)


def test_async_pair_partially_hidden():
    s = ov.analyze_hlo(PARTIAL_AR, total_devices=4, device_kind="TPU v5e")
    assert s["collectives"]["partially_exposed"] == 1
    (node,) = s["nodes"]
    hidden = _dot_seconds(4096)
    assert abs(node["hidden_seconds"] - hidden) / hidden < 0.1
    assert 0 < s["exposed_wire_seconds"] < s["wire_seconds"]
    assert 0.0 < s["overlap_fraction"] < 1.0


def test_serialized_host_copy_and_declared_stream():
    s = ov.analyze_hlo(SERIAL_HOST_COPY, device_kind="TPU v5e")
    assert s["host_transfers"]["serialized"] == 1
    (node,) = s["nodes"]
    assert node["kind"] == ov.KIND_HOST and node["source"] == "hlo"
    assert node["window_seconds"] > 0  # the dot could have hidden it
    # a DECLARED stream (engine host_state_bytes_per_step) larger than
    # what the HLO accounts for adds the residual as one serialized
    # node whose window is the whole program's compute
    declared = 8388608 * 4 + (32 << 20)
    s2 = ov.analyze_hlo(SERIAL_HOST_COPY, device_kind="TPU v5e",
                        declared_host_wire_bytes=declared)
    extra = [n for n in s2["nodes"] if n["source"] == "declared"]
    assert len(extra) == 1 and extra[0]["wire_bytes"] == 32 << 20
    assert extra[0]["window_seconds"] == s2["compute_seconds"]
    # and a declared stream already covered by HLO transfers adds none
    s3 = ov.analyze_hlo(SERIAL_HOST_COPY, device_kind="TPU v5e",
                        declared_host_wire_bytes=1024)
    assert not [n for n in s3["nodes"] if n["source"] == "declared"]


def test_analysis_is_deterministic():
    a = ov.analyze_hlo(SERIAL_HOST_COPY, device_kind="TPU v5e",
                       declared_host_wire_bytes=123456)
    b = ov.analyze_hlo(SERIAL_HOST_COPY, device_kind="TPU v5e",
                       declared_host_wire_bytes=123456)
    assert a == b


# ------------------------------------------------------- DSO7x rules
def _artifact(hlo, name="fix", **kw):
    kw.setdefault("mesh_axes", {"data": 4})
    kw.setdefault("device_kind", "TPU v5e")
    return dsp.ProgramArtifact(name=name, hlo=hlo, **kw)


def rule_ids(diags):
    return sorted(d.rule_id for d in diags)


def test_dso701_serialized_collective_with_window():
    diags = dsp.verify_program(_artifact(SERIAL_AR))
    assert rule_ids(diags) == ["DSO701"]
    assert "independent compute" in diags[0].message


def test_overlapped_program_is_clean():
    assert dsp.verify_program(_artifact(OVERLAPPED_AR)) == []
    # partial exposure is not flagged either (DSO701 is about FULLY
    # serialized collectives; the exposure metric rides the receipts)
    assert dsp.verify_program(_artifact(PARTIAL_AR)) == []


def test_dso702_serialized_host_transfer():
    diags = dsp.verify_program(_artifact(SERIAL_HOST_COPY))
    assert rule_ids(diags) == ["DSO702"]
    assert "exposed_wire_seconds=" in diags[0].message
    # declared-stream form (no HLO transfer ops at all, offload tax
    # known from the engine's wire accounting)
    diags = dsp.verify_program(_artifact(
        COMPUTE_ONLY, host_state_wire_bytes=64 << 20))
    assert rule_ids(diags) == ["DSO702"]
    assert "declared" in diags[0].message


def test_dso703_overlap_model_drift():
    fresh = dsp.program_overlap(_artifact(SERIAL_AR))
    ok = _artifact(SERIAL_AR, comm={"overlap": {
        "wire_seconds": fresh["wire_seconds"],
        "exposed_wire_seconds": fresh["exposed_wire_seconds"],
        "collectives": {"total": 1}, "host_transfers": {"total": 0}}})
    assert "DSO703" not in rule_ids(dsp.verify_program(ok))
    drifted = _artifact(SERIAL_AR, comm={"overlap": {
        "wire_seconds": fresh["wire_seconds"] * 3,
        "exposed_wire_seconds": fresh["exposed_wire_seconds"],
        "collectives": {"total": 2}, "host_transfers": {"total": 0}}})
    diags = dsp.verify_program(drifted)
    assert "DSO703" in rule_ids(diags)
    msg = next(d.message for d in diags if d.rule_id == "DSO703")
    assert "wire_seconds" in msg and "collectives 2 -> 1" in msg


def test_header_only_artifact_has_no_overlap_claim():
    art = _artifact("HloModule m, entry_computation_layout={...}\n")
    assert dsp.program_overlap(art) is None
    assert dsp.verify_program(art) == []


def test_rule_checks_see_past_the_telemetry_node_cap():
    """The telemetry event caps the node list at 32, but the rule
    checks must see EVERY node: a program with > 32 serialized
    collectives plus a declared host stream (appended LAST) still
    fires DSO702."""
    body = ["  %p0 = f32[1024,8192]{1,0} parameter(0)",
            "  %p1 = f32[8192,8192]{1,0} parameter(1)", _BIG_DOT.rstrip()]
    for i in range(40):
        body.append(
            f"  %all-reduce.{i} = f32[1024,8192]{{1,0}} all-reduce("
            f"f32[1024,8192]{{1,0}} %p0), replica_groups={{{{0,1,2,3}}}}")
    body.append("  ROOT %tuple.1 = (f32[1024,8192]{1,0}) "
                "tuple(%all-reduce.0)")
    hlo = _HEADER + ("ENTRY %main.1 (p0: f32[1024,8192], "
                     "p1: f32[8192,8192]) -> (f32[1024,8192]) {\n"
                     + "\n".join(body) + "\n}\n")
    art = _artifact(hlo, name="train_step",
                    host_state_wire_bytes=64 << 20)
    summary = dsp.program_overlap(art)
    assert summary["collectives"]["total"] == 40
    assert summary["nodes_truncated"] == 0  # untruncated for the rules
    assert len(summary["nodes"]) == 41
    ids = rule_ids(dsp.verify_program(art))
    assert "DSO702" in ids and "DSO701" in ids
    # the telemetry-facing default DOES truncate (event size bound)
    capped = ov.analyze_hlo(hlo, total_devices=4, device_kind="TPU v5e",
                            declared_host_wire_bytes=64 << 20)
    assert len(capped["nodes"]) == 32 and capped["nodes_truncated"] == 9
    assert capped["collectives"]["total"] == 40  # buckets never truncate


# ----------------------------------------------- CLI: sarif + ratchet
def _write_run_dir(tmp_path, hlo, name="fix", **side_extra):
    progdir = tmp_path / "programs"
    progdir.mkdir(parents=True, exist_ok=True)
    (progdir / f"{name}.hlo").write_text(hlo)
    side = {"artifact_schema_version": 1, "program": name,
            "hlo_file": f"{name}.hlo", "mesh_axes": {"data": 4},
            "device_kind": "TPU v5e"}
    side.update(side_extra)
    (progdir / f"{name}.json").write_text(json.dumps(side))
    return tmp_path


def test_sarif_round_trips_against_json(tmp_path):
    run_dir = _write_run_dir(tmp_path / "run", SERIAL_AR)
    # a second program whose donation verdict downgrades (aliases in
    # the header, alias bytes 0): an INFO-severity DSP602 — must emit
    # as a note-level SARIF result and never count as active
    _write_run_dir(
        tmp_path / "run",
        "HloModule m, input_output_alias={ {0}: (0, {}, may-alias) }, "
        "entry_computation_layout={...}\n",
        name="downgraded", donate_argnums=[0], alias_size_in_bytes=0)
    jout, sout = tmp_path / "r.json", tmp_path / "r.sarif"
    src = tmp_path / "clean.py"
    src.write_text("x = 1\n")
    with redirect_stdout(io.StringIO()):
        rc = dslint_main([str(src), "--programs", str(run_dir),
                          "--json", str(jout), "--sarif", str(sout)])
    assert rc == 1  # the DSO701 warning
    jrep = json.loads(jout.read_text())
    sarif = json.loads(sout.read_text())
    assert sarif["version"] == "2.1.0"
    run = sarif["runs"][0]
    assert run["tool"]["driver"]["name"] == "dslint"
    rules = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert {"DSO701", "DSO702", "DSO703", "DSP601"} <= rules
    # the round-trip invariant: unsuppressed error/warning results ==
    # --json violations; info results ride along as notes
    active = [r for r in run["results"]
              if not r.get("suppressions")
              and r["level"] in ("error", "warning")]
    assert len(active) == jrep["violations"] == 1
    (res,) = active
    assert res["ruleId"] == "DSO701" and res["level"] == "warning"
    loc = res["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("fix.hlo")
    assert loc["region"]["startLine"] == 1
    notes = [r for r in run["results"] if r["level"] == "note"]
    assert [r["ruleId"] for r in notes] == ["DSP602"]
    assert not notes[0].get("suppressions")


def test_sarif_marks_baselined_findings_external(tmp_path):
    run_dir = _write_run_dir(tmp_path / "run", SERIAL_AR)
    baseline = tmp_path / "baseline.json"
    with redirect_stdout(io.StringIO()):
        assert dslint_main(["--programs", str(run_dir), "--baseline",
                            str(baseline), "--update-baseline"]) == 0
        sout = tmp_path / "r.sarif"
        rc = dslint_main(["--programs", str(run_dir), "--baseline",
                          str(baseline), "--sarif", str(sout)])
    assert rc == 0
    results = json.loads(sout.read_text())["runs"][0]["results"]
    assert len(results) == 1
    assert results[0]["suppressions"] == [{"kind": "external"}]


def test_program_baseline_key_covers_dso7(tmp_path):
    from deepspeed_tpu.tools.dslint.cli import baseline_key
    diags = dsp.verify_program(_artifact(SERIAL_AR, name="train_step"))
    assert baseline_key(diags[0]) == "<programs>|DSO701|train_step"


# ---------------------------------------------------------- receipts


class _FakeCompiled:
    def __init__(self, hlo):
        self._hlo = hlo

    def as_text(self):
        return self._hlo

    def memory_analysis(self):
        return None


def test_window_cap_degrade_is_loud_not_clean(monkeypatch):
    """Past MAX_WINDOW_INSTRUCTIONS the independence bitsets degrade to
    unknown windows — the window-gated rules then CANNOT run, and that
    must surface as a DSP614 'unverified' warning, never as clean."""
    monkeypatch.setattr(ov, "MAX_WINDOW_INSTRUCTIONS", 3)
    art = _artifact(SERIAL_AR)
    ids = rule_ids(dsp.verify_program(art))
    assert "DSP614" in ids and "DSO701" not in ids
    msg = next(d.message for d in dsp.verify_program(_artifact(SERIAL_AR))
               if d.rule_id == "DSP614")
    assert "UNVERIFIED" in msg and "window" in msg
    # the declared stream carries its own window and stays flagged
    # even on over-cap programs
    ids2 = rule_ids(dsp.verify_program(_artifact(
        SERIAL_AR, name="train_step", host_state_wire_bytes=64 << 20)))
    assert "DSO702" in ids2 and "DSP614" in ids2


def test_ledger_transfer_fields_come_from_the_analysis_nodes():
    """One classification: the entry's host_transfer_bytes must equal
    the byte total of the overlap analysis' own KIND_HOST hlo-source
    nodes (the set the declared-residual subtraction uses)."""
    from deepspeed_tpu.profiling.comm import CommLedger

    ledger = CommLedger(enabled=True, mesh_axes={"data": 4})
    entry = ledger.record("fwd_bwd", _FakeCompiled(SERIAL_HOST_COPY))
    ovl = entry["overlap"]
    hlo_hosts = [n for n in ovl["nodes"]
                 if n["kind"] == ov.KIND_HOST and n["source"] == "hlo"]
    assert entry["host_transfers"] == len(hlo_hosts) == 1
    assert entry["host_transfer_bytes"] == \
        sum(n["wire_bytes"] for n in hlo_hosts) == 32 << 20
    assert ovl["hlo_transfer_summary"]["host_transfer_bytes"] == 32 << 20


def test_comm_ledger_records_transfers_and_overlap():
    from deepspeed_tpu.profiling.comm import CommLedger

    ledger = CommLedger(enabled=True, mesh_axes={"data": 4})
    ledger.overlap_context_fn = lambda: {
        "host_state_wire_bytes": 48 << 20, "device_kind": "TPU v5e"}
    entry = ledger.record("train_step", _FakeCompiled(SERIAL_HOST_COPY))
    # the S(5) copy-start is a host DMA: 8388608 f32 = 32 MiB
    assert entry["host_transfers"] == 1
    assert entry["host_transfer_bytes"] == 32 << 20
    assert entry["p2p_transfers"] == 0
    ovl = entry["overlap"]
    # declared 48 MiB minus the 32 MiB the HLO accounts for: one extra
    # 16 MiB declared-stream node (train_step IS an update program)
    declared = [n for n in ovl["nodes"] if n["source"] == "declared"]
    assert len(declared) == 1 and declared[0]["wire_bytes"] == 16 << 20
    assert ovl["exposed_wire_seconds"] > 0
    # a NON-update program never carries the declared stream
    entry2 = ledger.record("fwd_bwd", _FakeCompiled(SERIAL_HOST_COPY))
    assert not [n for n in entry2["overlap"]["nodes"]
                if n["source"] == "declared"]


def test_step_overlap_stepwise_aggregation():
    from deepspeed_tpu.profiling.comm import CommLedger

    ledger = CommLedger(enabled=True, mesh_axes={"data": 4})
    ledger.record("fwd_bwd", _FakeCompiled(SERIAL_AR))
    ledger.record("apply_update", _FakeCompiled(COMPUTE_ONLY))
    step = ledger.step_overlap(grad_accumulation_steps=2)
    single = ledger.entry("fwd_bwd")["overlap"]
    assert step["program"] == "stepwise"
    assert abs(step["wire_seconds"] - 2 * single["wire_seconds"]) < 1e-9
    assert step["exposed_wire_seconds"] == step["wire_seconds"]
    assert step["overlap_fraction"] == 0.0


# --------------------------------- round 12: pipelined declared stream
def test_declared_stream_pipelined_schedule_lowers_exposure():
    """The declared-schedule model: the same declared bytes classify as
    one fully serialized node without a schedule, and as a
    fill/drain-exposed PARTIAL node under the double-buffered schedule
    — exposure strictly lower, wire identical (the pipeline moves the
    same one sweep each way)."""
    declared = 64 << 20
    base = ov.analyze_hlo(COMPUTE_ONLY, device_kind="TPU v5e",
                          declared_host_wire_bytes=declared)
    piped = ov.analyze_hlo(
        COMPUTE_ONLY, device_kind="TPU v5e",
        declared_host_wire_bytes=declared,
        declared_host_stream={"overlap": True, "chunks": 16,
                              "prefetch_depth": 2, "form": "scan"})
    assert piped["wire_seconds"] == base["wire_seconds"]
    assert piped["exposed_wire_seconds"] < base["exposed_wire_seconds"]
    assert piped["overlap_fraction"] > base["overlap_fraction"]
    (node,) = [n for n in piped["nodes"] if n["source"] == "declared"]
    # fill/drain (one chunk's round trip) is always exposed — the model
    # never claims a free lunch
    secs = declared / (V5E["host_gbps"] * 1e9)
    assert node["seconds"] - node["hidden_seconds"] >= secs / 16 - 1e-12
    assert node["classification"] == ov.PARTIAL
    # overlap: false (or a single chunk) keeps the serialized verdict
    ser = ov.analyze_hlo(
        COMPUTE_ONLY, device_kind="TPU v5e",
        declared_host_wire_bytes=declared,
        declared_host_stream={"overlap": False, "chunks": 16})
    (snode,) = [n for n in ser["nodes"] if n["source"] == "declared"]
    assert snode["classification"] == ov.SERIALIZED


def test_declared_stream_hiding_is_budgeted_by_compute():
    """Components share ONE compute budget: a declared stream whose
    steady state exceeds the program's compute stays mostly exposed —
    the model can never hide more wire than the program holds."""
    huge = 8 << 30  # ~0.57 s of host wire vs ~17 ms of compute
    s = ov.analyze_hlo(
        COMPUTE_ONLY, device_kind="TPU v5e",
        declared_host_wire_bytes=huge,
        declared_host_stream={"overlap": True, "chunks": 64,
                              "prefetch_depth": 2})
    (node,) = [n for n in s["nodes"] if n["source"] == "declared"]
    assert node["hidden_seconds"] <= s["compute_seconds"] + 1e-12
    assert node["hidden_seconds"] > 0


def test_declared_grad_stream_rides_the_schedule():
    """offload_gradients declares its spill+reload wire as a second
    component; it draws hiding budget AFTER the state stream and the
    two components never hide more than the program's compute."""
    sched = {"overlap": True, "chunks": 16, "prefetch_depth": 2,
             "grad_wire_bytes": 32 << 20}
    s = ov.analyze_hlo(COMPUTE_ONLY, device_kind="TPU v5e",
                       declared_host_wire_bytes=64 << 20,
                       declared_host_stream=sched)
    declared = [n for n in s["nodes"] if n["source"] == "declared"]
    assert sorted(n["op"] for n in declared) == ["grad-stream",
                                                 "host-stream"]
    hidden = sum(n["hidden_seconds"] for n in declared)
    assert 0 < hidden <= s["compute_seconds"] + 1e-12
    # without a schedule the grad stream is not declared at all (the
    # engine only emits grad_wire_bytes inside a schedule)
    s2 = ov.analyze_hlo(COMPUTE_ONLY, device_kind="TPU v5e",
                        declared_host_wire_bytes=64 << 20)
    assert [n["op"] for n in s2["nodes"]
            if n["source"] == "declared"] == ["host-stream"]


def test_dso702_not_fired_for_pipelined_declared_stream():
    """The pipelined schedule's declared node is PARTIAL, so DSO702
    (fully serialized host transfers) stays quiet — re-serializing
    (schedule overlap False) brings it back."""
    piped = _artifact(COMPUTE_ONLY, name="train_step",
                      host_state_wire_bytes=64 << 20,
                      host_stream_schedule={"overlap": True, "chunks": 8,
                                            "prefetch_depth": 2})
    assert "DSO702" not in rule_ids(dsp.verify_program(piped))
    ser = _artifact(COMPUTE_ONLY, name="train_step",
                    host_state_wire_bytes=64 << 20,
                    host_stream_schedule={"overlap": False, "chunks": 8})
    assert "DSO702" in rule_ids(dsp.verify_program(ser))


def test_schedule_survives_the_sidecar_round_trip(tmp_path):
    """The sidecar carries host_stream_schedule, so the offline
    ``--programs`` re-analysis prices the SAME schedule the live hook
    recorded (the DSO703 like-for-like contract)."""
    sched = {"overlap": True, "chunks": 8, "prefetch_depth": 2,
             "form": "scan", "groups": 2}
    art = _artifact(COMPUTE_ONLY, name="train_step",
                    host_state_wire_bytes=64 << 20,
                    host_stream_schedule=sched)
    side = art.sidecar()
    assert side["host_stream_schedule"] == sched
    run_dir = _write_run_dir(tmp_path / "run", COMPUTE_ONLY,
                             name="train_step",
                             host_state_wire_bytes=64 << 20,
                             host_stream_schedule=sched)
    (loaded,) = dsp.load_run_artifacts(str(run_dir))
    assert loaded.host_stream_schedule == sched
    assert (dsp.program_overlap(loaded)["exposed_wire_seconds"]
            == dsp.program_overlap(art)["exposed_wire_seconds"])


# ------------------------------------------- DSO704: exposure ratchet
def test_dso704_exposure_ratchet():
    """check_exposure_ratchet: growth past the recorded metric's
    tolerance fires; within-tolerance and unrecorded programs stay
    quiet."""
    art = _artifact(COMPUTE_ONLY, name="train_step",
                    host_state_wire_bytes=64 << 20,
                    host_stream_schedule={"overlap": True, "chunks": 8,
                                          "prefetch_depth": 2})
    metrics = dsp.exposure_metrics([art])
    key = dsp.exposure_metric_key("train_step")
    assert list(metrics) == [key] and metrics[key] > 0
    # within tolerance: quiet
    assert dsp.check_exposure_ratchet([art], metrics) == []
    # recorded figure far below current: DSO704 fires
    tight = {key: metrics[key] / 10.0}
    diags = dsp.check_exposure_ratchet([art], tight)
    assert rule_ids(diags) == ["DSO704"]
    assert "re-serializing" in diags[0].message
    # unrecorded program: the ratchet only tightens what was recorded
    assert dsp.check_exposure_ratchet(
        [_artifact(COMPUTE_ONLY, name="other",
                   host_state_wire_bytes=64 << 20)], metrics) == []


def test_cli_baseline_metrics_ratchet(tmp_path):
    """End-to-end: --update-baseline records the exposed-wire metric;
    a later run whose exposure grew past tolerance exits 1 with a
    DSO704 finding the violations baseline cannot absolve."""
    sched_on = {"overlap": True, "chunks": 8, "prefetch_depth": 2}
    run_on = _write_run_dir(tmp_path / "on", COMPUTE_ONLY,
                            name="train_step",
                            host_state_wire_bytes=64 << 20,
                            host_stream_schedule=sched_on)
    baseline = tmp_path / "baseline.json"
    with redirect_stdout(io.StringIO()):
        assert dslint_main(["--programs", str(run_on), "--baseline",
                            str(baseline), "--update-baseline"]) == 0
        assert dslint_main(["--programs", str(run_on), "--baseline",
                            str(baseline)]) == 0
    data = json.loads(baseline.read_text())
    key = dsp.exposure_metric_key("train_step")
    assert data["violations"] == {} and key in data["metrics"]
    # the regression: the same program re-dumped with a serialized
    # schedule — exposure grows ~8x past the 25% tolerance
    run_off = _write_run_dir(tmp_path / "off", COMPUTE_ONLY,
                             name="train_step",
                             host_state_wire_bytes=64 << 20,
                             host_stream_schedule={"overlap": False,
                                                   "chunks": 8})
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = dslint_main(["--programs", str(run_off), "--baseline",
                          str(baseline)])
    assert rc == 1
    out = buf.getvalue()
    assert "DSO704" in out and "re-serializing" in out


def test_declared_grad_stream_reduced_by_hlo_excess():
    """TPU lowerings can materialize the grad spill as real HLO host
    transfers; HLO-accounted bytes beyond the state declaration reduce
    the declared grad component so nothing is double-counted."""
    sched = {"overlap": True, "chunks": 8, "prefetch_depth": 2,
             "grad_wire_bytes": 32 << 20}
    # SERIAL_HOST_COPY carries one 32 MiB HLO host transfer; declare
    # 16 MiB of state -> 16 MiB of HLO excess absorbs half the grads
    s = ov.analyze_hlo(SERIAL_HOST_COPY, device_kind="TPU v5e",
                       declared_host_wire_bytes=16 << 20,
                       declared_host_stream=sched)
    grad = [n for n in s["nodes"] if n["op"] == "grad-stream"]
    assert len(grad) == 1 and grad[0]["wire_bytes"] == 16 << 20
    # no HLO transfers at all (CPU form): the full grad declaration
    s2 = ov.analyze_hlo(COMPUTE_ONLY, device_kind="TPU v5e",
                        declared_host_wire_bytes=16 << 20,
                        declared_host_stream=sched)
    (grad2,) = [n for n in s2["nodes"] if n["op"] == "grad-stream"]
    assert grad2["wire_bytes"] == 32 << 20
    # HLO excess >= grad declaration: the grad node disappears
    s3 = ov.analyze_hlo(SERIAL_HOST_COPY, device_kind="TPU v5e",
                        declared_host_wire_bytes=0,
                        declared_host_stream={**sched,
                                              "grad_wire_bytes": 1 << 20})
    assert not [n for n in s3["nodes"] if n["op"] == "grad-stream"]


def test_dso704_ratchet_has_an_absolute_floor():
    """A recorded metric of 0.0 must not turn cost-model epsilons into
    CI failures: the ceiling carries an absolute 10 µs floor."""
    art = _artifact(COMPUTE_ONLY, name="train_step",
                    host_state_wire_bytes=1 << 10,
                    host_stream_schedule={"overlap": True, "chunks": 64,
                                          "prefetch_depth": 2})
    cur = dsp.program_overlap(art)["exposed_wire_seconds"]
    assert 0 < cur < dsp.EXPOSED_WIRE_RATCHET_EPS
    key = dsp.exposure_metric_key("train_step")
    assert dsp.check_exposure_ratchet([art], {key: 0.0}) == []


# ---------------------------------- declared collective schedule (r14)
# a bucketed zero-2 exchange as CPU HLO shows it: sync reduce-scatters
# (one per bucket) + a sync all-gather, next to an independent
# flops-bound dot (the "rest of the backward")
BUCKETED_EXCHANGE = _HEADER + (
    "ENTRY %main.1 (p0: f32[1024,8192], p1: f32[8192,8192]) -> "
    "(f32[256,8192], f32[256,8192], f32[1024,8192], f32[8192,8192]) {\n"
    "  %p0 = f32[1024,8192]{1,0} parameter(0)\n"
    "  %p1 = f32[8192,8192]{1,0} parameter(1)\n"
    + _BIG_DOT +
    "  %reduce-scatter.1 = f32[256,8192]{1,0} reduce-scatter("
    "f32[1024,8192]{1,0} %p0), replica_groups={{0,1,2,3}}, "
    "dimensions={0}\n"
    "  %reduce-scatter.2 = f32[256,8192]{1,0} reduce-scatter("
    "f32[1024,8192]{1,0} %p0), replica_groups={{0,1,2,3}}, "
    "dimensions={0}\n"
    "  %all-gather.1 = f32[1024,8192]{1,0} all-gather("
    "f32[256,8192]{1,0} %reduce-scatter.1), "
    "replica_groups={{0,1,2,3}}, dimensions={0}\n"
    "  ROOT %tuple.1 = (f32[256,8192]{1,0}, f32[256,8192]{1,0}, "
    "f32[1024,8192]{1,0}, f32[8192,8192]{1,0}) tuple("
    "%reduce-scatter.1, %reduce-scatter.2, %all-gather.1, %dot.big)\n"
    "}\n")

_SCHED_ON = {"overlap": True, "rs_buckets": 2, "ag_buckets": 1}
_SCHED_OFF = {"overlap": False, "rs_buckets": 2, "ag_buckets": 1}


def test_declared_collective_schedule_pipelined_pricing():
    """overlap on: steady-state buckets hide up to the shared compute
    budget, fill/drain (one bucket's wire) stays exposed, nodes are
    re-sourced ``hlo+declared``; all-reduces / no-schedule runs are
    untouched."""
    base = ov.analyze_hlo(BUCKETED_EXCHANGE, total_devices=4,
                          device_kind="TPU v5e", max_nodes=None)
    on = ov.analyze_hlo(BUCKETED_EXCHANGE, total_devices=4,
                        device_kind="TPU v5e", max_nodes=None,
                        declared_collective_schedule=_SCHED_ON)
    assert on["exposed_wire_seconds"] < base["exposed_wire_seconds"]
    matching = [n for n in on["nodes"]
                if n["op"] in ("reduce-scatter", "all-gather")]
    assert matching and all(n["source"] == "hlo+declared"
                            for n in matching)
    # fill/drain floor: at least one bucket's wire stays exposed
    total = sum(n["seconds"] for n in matching)
    exposed = sum(n["seconds"] - n["hidden_seconds"] for n in matching)
    assert exposed >= total / len(matching) * (1 - 1e-9)
    # the hiding never exceeds the program's compute
    hidden = sum(n["hidden_seconds"] for n in matching)
    assert hidden <= on["compute_seconds"] + 1e-12
    # no node fully serialized any more -> DSO701 stays quiet
    assert dsp.verify_program(_artifact(
        BUCKETED_EXCHANGE, collective_schedule=_SCHED_ON)) == []


def test_declared_collective_schedule_serialized_control():
    """overlap off: exposure unchanged (everything stays serialized)
    but the POTENTIAL window is recorded and DSO701 fires — the
    engine declared a bucketed schedule could hide this exchange."""
    base = ov.analyze_hlo(BUCKETED_EXCHANGE, total_devices=4,
                          device_kind="TPU v5e", max_nodes=None)
    off = ov.analyze_hlo(BUCKETED_EXCHANGE, total_devices=4,
                         device_kind="TPU v5e", max_nodes=None,
                         declared_collective_schedule=_SCHED_OFF)
    assert off["exposed_wire_seconds"] == base["exposed_wire_seconds"]
    matching = [n for n in off["nodes"]
                if n["op"] in ("reduce-scatter", "all-gather")]
    potential = off["compute_seconds"] * 2 / 3  # (B-1)/B over 3 buckets
    for n in matching:
        assert n["source"] == "hlo+declared"
        assert n["classification"] == ov.SERIALIZED
        assert n["window_seconds"] >= potential * (1 - 1e-9)
    diags = dsp.verify_program(_artifact(
        BUCKETED_EXCHANGE, collective_schedule=_SCHED_OFF))
    assert rule_ids(diags) == ["DSO701"]
    assert "overlap_comm would bucket" in diags[0].message


def test_declared_collective_schedule_ignores_other_collectives():
    """The schedule re-prices only reduce-scatter/all-gather: a sync
    all-reduce (loss pmean) keeps its HLO classification, window rules
    and all."""
    on = ov.analyze_hlo(SERIAL_AR, total_devices=4,
                        device_kind="TPU v5e", max_nodes=None,
                        declared_collective_schedule=_SCHED_ON)
    ar = [n for n in on["nodes"] if n["op"] == "all-reduce"]
    assert ar and ar[0]["source"] == "hlo" and (
        ar[0]["classification"] == ov.SERIALIZED)


def test_collective_schedule_sidecar_roundtrip(tmp_path):
    art = _artifact(BUCKETED_EXCHANGE, name="train_step",
                    collective_schedule=_SCHED_ON)
    progdir = tmp_path / "programs"
    progdir.mkdir()
    (progdir / "train_step.hlo").write_text(BUCKETED_EXCHANGE)
    (progdir / "train_step.json").write_text(
        json.dumps(art.sidecar()))
    loaded = dsp.load_run_artifacts(str(tmp_path))
    assert loaded[0].collective_schedule == _SCHED_ON
    # and the offline re-analysis agrees with the live one (DSO703's
    # like-with-like contract)
    assert dsp.program_overlap(loaded[0])["exposed_wire_seconds"] == (
        ov.analyze_hlo(BUCKETED_EXCHANGE, total_devices=4,
                       device_kind="TPU v5e", max_nodes=None,
                       declared_collective_schedule=_SCHED_ON)[
            "exposed_wire_seconds"])


def test_comm_exposure_metric_keys_and_ratchet():
    """The baseline records the collective exposure under its OWN key
    (comm_exposed_wire_seconds — the offload host-stream metric for a
    same-named program must not collide), only for OVERLAPPED
    schedules; the DSO704 ratchet reads it back."""
    on = _artifact(BUCKETED_EXCHANGE, name="train_step",
                   collective_schedule=_SCHED_ON)
    off = _artifact(BUCKETED_EXCHANGE, name="train_step",
                    collective_schedule=_SCHED_OFF)
    metrics = dsp.exposure_metrics([on])
    key = dsp.comm_exposure_metric_key("train_step")
    assert set(metrics) == {key}
    assert key != dsp.exposure_metric_key("train_step")
    # the serialized control records nothing (it exists to be worse)
    assert dsp.exposure_metrics([off]) == {}
    # ratchet: growth past tolerance trips DSO704 through the new key
    tight = {key: metrics[key] / 2.0}
    diags = dsp.check_exposure_ratchet([on], tight)
    assert rule_ids(diags) == ["DSO704"]
    assert not dsp.check_exposure_ratchet([on], metrics)
