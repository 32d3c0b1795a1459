"""DSP6xx program-verifier tests (``tools/dslint/programs.py`` +
``profiling/verify.py``): the alias-header parser and donation verdicts
(incl. the warm-cache alias=0 downgrade), regression fixtures replaying
BOTH PR 8 bugs statically (the psum-over-dp×tp flatten and the donated
live numpy staging buffer), psum-for-pmean detection, comm-ledger drift,
the run-dir artifact dump + ``dslint --programs`` CLI, and the engine
hook at AOT-plan time."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu as deepspeed
from deepspeed_tpu.parallel import make_mesh
from deepspeed_tpu.runtime.zero.coordinator import FlatParamCoordinator
from deepspeed_tpu.tools.dslint import failing
from deepspeed_tpu.tools.dslint import programs as dsp
from deepspeed_tpu.tools.dslint.cli import main as dslint_main
from deepspeed_tpu.tools.dslint.core import ParsedFile
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .simple_model import SimpleModel, base_config, random_batches

HIDDEN = 64


def rule_ids(diags):
    return sorted(d.rule_id for d in diags)


# ------------------------------------------------------ alias parsing
def test_parse_input_output_aliases():
    hdr = ("HloModule jit_x, is_scheduled=true, input_output_alias="
           "{ {1}: (0, {}, may-alias), {2}: (1, {}, must-alias) }, "
           "entry_computation_layout={...}\n  %body...")
    assert dsp.parse_input_output_aliases(hdr) == [("1", 0), ("2", 1)]
    assert dsp.parse_input_output_aliases("HloModule jit_x\n %b") == []


def test_donation_verdicts_601_602_and_clean():
    hlo_aliased = ("HloModule m, input_output_alias={ {0}: (0, {}, "
                   "may-alias) }, entry_computation_layout={...}\n")
    hlo_bare = "HloModule m, entry_computation_layout={...}\n"
    # declared donation, no aliases materialized -> hard error
    art = dsp.ProgramArtifact(name="p", hlo=hlo_bare,
                              donate_argnums=(0, 1))
    assert rule_ids(dsp.verify_program(art)) == ["DSP601"]
    # aliases in text + nonzero alias bytes -> fully verified
    art = dsp.ProgramArtifact(name="p", hlo=hlo_aliased,
                              donate_argnums=(0,),
                              alias_size_in_bytes=4096)
    assert dsp.verify_program(art) == []
    # aliases in text, memory_analysis says 0 -> the documented
    # warm-cache deserialization caveat: downgraded verdict, NOT silence
    art = dsp.ProgramArtifact(name="p", hlo=hlo_aliased,
                              donate_argnums=(0,),
                              alias_size_in_bytes=0)
    diags = dsp.verify_program(art)
    assert rule_ids(diags) == ["DSP602"]
    assert "cache-deserialized" in diags[0].message
    # DSP602 is a downgraded verdict: visible but never CI-failing
    assert failing(diags) == []
    # no donation declared -> nothing to verify
    art = dsp.ProgramArtifact(name="p", hlo=hlo_bare, donate_argnums=())
    assert dsp.verify_program(art) == []


def test_donation_verified_on_real_compiled_program():
    f = jax.jit(lambda x, y: (x + y, y * 2), donate_argnums=(0,))
    compiled = f.lower(jnp.zeros((256, 128), jnp.float32),
                       jnp.ones((256, 128), jnp.float32)).compile()
    art = dsp.ProgramArtifact(
        name="donating", hlo=compiled.as_text(), donate_argnums=(0,),
        alias_size_in_bytes=int(
            compiled.memory_analysis().alias_size_in_bytes))
    # cold compile: alias in text; warm (persistent test cache) may
    # report alias=0 -> DSP602.  Either way: zero hard violations
    assert not any(d.rule_id == "DSP601"
                   for d in dsp.verify_program(art))
    assert dsp.parse_input_output_aliases(art.hlo)


# ------------------------------------- PR 8 bug replay 1: flatten x tp
def _coordinator(cpu_devices, axes):
    mesh = make_mesh(axes, devices=cpu_devices[:int(np.prod(
        list(axes.values())))])
    params = {"w": np.zeros((100, 64), np.float32),
              "b": np.zeros((64,), np.float32)}
    return mesh, params, FlatParamCoordinator(
        mesh, params, stage=2, dp_size=axes.get("data", 1))


def test_rebroken_flatten_psum_over_tp_trips_dsp611(cpu_devices):
    """THE regression fixture: the pre-PR 8 ``flatten_to_master`` on a
    dp×tp mesh assembled the flat master by a sum over EVERY device, so
    the model-axis replicas were summed too and each parameter arrived
    ×tp; the verifier must catch that sum STATICALLY.  The jitted
    whole-tree flatten no longer compiles to it (jax 0.9.0 assembles
    with partition-indexed slices and no collective), so the assembly is
    written out: each device puts its data shard into a zero master and
    the masters are summed over both axes."""
    mesh, _, coord = _coordinator(cpu_devices, {"data": 2, "model": 2})
    rows, lanes = coord.segments.shape

    def assemble(shard):
        full = jax.lax.dynamic_update_slice(
            jnp.zeros((rows, lanes), shard.dtype), shard,
            (jax.lax.axis_index("data") * shard.shape[0], 0))
        return jax.lax.psum(full, ("data", "model"))

    with mesh:
        compiled = jax.jit(shard_map(
            assemble, mesh=mesh, in_specs=P("data"), out_specs=P(),
            check_vma=False)).lower(
                jnp.zeros((rows, lanes), jnp.float32)).compile()
    art = dsp.ProgramArtifact(
        name="flatten_to_master", hlo=compiled.as_text(),
        mesh_axes={"data": 2, "model": 2},
        param_bytes=int(np.prod(coord.segments.shape)) * 4)
    diags = dsp.verify_program(art)
    assert "DSP611" in rule_ids(diags), rule_ids(diags)
    msg = [d for d in diags if d.rule_id == "DSP611"][0].message
    assert "×2" in msg and "data axis is only 2" in msg


def test_fixed_flatten_paths_verify_clean(cpu_devices):
    # dp-only mesh: the jitted flatten is still the shipped path and
    # must verify clean (its all-reduce groups == the data axis)
    mesh, params, coord = _coordinator(cpu_devices, {"data": 4})
    with mesh:
        compiled = jax.jit(
            coord._flatten_traced,
            out_shardings=coord.master_device_sharding).lower(
                params).compile()
    art = dsp.ProgramArtifact(
        name="flatten_to_master", hlo=compiled.as_text(),
        mesh_axes={"data": 4},
        param_bytes=int(np.prod(coord.segments.shape)) * 4)
    assert dsp.verify_program(art) == []
    # ... and the fixed multi-axis path records its laundering
    # provenance for the verification artifacts
    mesh2, params2, coord2 = _coordinator(cpu_devices,
                                          {"data": 2, "model": 2})
    coord2.flatten_to_master(params2)
    assert coord2.master_provenance == "jit_copy"


# ------------------------------------------------ DSP612 psum-for-pmean
def _shard_scalar_program(cpu_devices, fn):
    mesh = make_mesh({"data": 4}, devices=cpu_devices[:4])
    with mesh:
        return jax.jit(shard_map(
            fn, mesh=mesh, in_specs=P("data"), out_specs=P(),
            axis_names={"data"}, check_vma=False)).lower(
                jnp.zeros((8, 16))).compile()


def test_psum_for_pmean_suspect_trips_and_pmean_clean(cpu_devices):
    psum_c = _shard_scalar_program(
        cpu_devices, lambda x: jax.lax.psum(jnp.sum(x), "data"))
    pmean_c = _shard_scalar_program(
        cpu_devices, lambda x: jax.lax.pmean(jnp.sum(x), "data"))
    bad = dsp.ProgramArtifact(name="psum", hlo=psum_c.as_text(),
                              mesh_axes={"data": 4})
    good = dsp.ProgramArtifact(name="pmean", hlo=pmean_c.as_text(),
                               mesh_axes={"data": 4})
    assert rule_ids(dsp.verify_program(bad)) == ["DSP612"]
    assert dsp.verify_program(good) == []


def test_mean_scaling_evidence_accepts_global_batch_normalization():
    # a loss normalized by the global element count (1/(g*k)) is mean
    # evidence too — the engine's fused step carries 1/1024-style
    # constants, not 1/dp
    hlo = "  %c = f32[] constant(0.0009765625)\n"     # 1/1024
    assert dsp.has_mean_scaling_evidence(hlo, 4)
    assert not dsp.has_mean_scaling_evidence(hlo, 3)  # 3 !| 1024
    assert dsp.has_mean_scaling_evidence("constant(0.25)", 4)
    assert not dsp.has_mean_scaling_evidence("constant(0.3)", 4)
    assert dsp.has_mean_scaling_evidence("", 1)       # no group, no sum


# ------------------------------------------------- DSP613 ledger drift
def test_comm_ledger_drift_trips_on_tampered_entry(cpu_devices):
    compiled = _shard_scalar_program(
        cpu_devices, lambda x: jax.lax.pmean(jnp.sum(x), "data"))
    from deepspeed_tpu.profiling.comm import (collective_summary,
                                              parse_hlo_collectives)

    hlo = compiled.as_text()
    fresh = collective_summary(parse_hlo_collectives(
        hlo, all_participants=4))
    ok = dsp.ProgramArtifact(name="p", hlo=hlo, mesh_axes={"data": 4},
                             comm=fresh)
    assert dsp.verify_program(ok) == []
    tampered = dict(fresh, wire_bytes=fresh["wire_bytes"] * 10 + 64,
                    collectives=fresh["collectives"] + 1)
    bad = dsp.ProgramArtifact(name="p", hlo=hlo, mesh_axes={"data": 4},
                              comm=tampered)
    assert rule_ids(dsp.verify_program(bad)) == ["DSP613"]


# --------------------- PR 8 bug replay 2: donated live staging buffer
_STAGED_DONATION = '''
import jax
import numpy as np

step = jax.jit(lambda m, g: m + g, donate_argnums=(0,))

def driver(sharding, g):
    buf = np.zeros((1024, 1024), np.float32)
    master = jax.device_put(buf, sharding)
    out = step(master, g)
    buf[0, 0] = 1.0
    return out
'''


def lint_src(tmp_path, source, name="fixture.py"):
    path = tmp_path / name
    path.write_text(source)
    pf = ParsedFile.parse(str(path), source)
    return dsp.check_use_after_donation(pf)


def test_donated_numpy_staging_read_after_trips_dsp603(tmp_path):
    """THE second regression fixture: the PR 8 heap-corruption shape —
    a device_put of a live numpy staging buffer donated into a jit,
    the staging buffer touched afterwards — caught at the AST level,
    no flaky glibc abort required."""
    diags = lint_src(tmp_path, _STAGED_DONATION)
    assert rule_ids(diags) == ["DSP603"]
    assert "STAGING" in diags[0].message
    assert "heap corruption" in diags[0].message


def test_plain_name_read_after_donation_trips(tmp_path):
    diags = lint_src(tmp_path, '''
import jax

apply_fn = jax.jit(lambda m, g: m + g, donate_argnums=(0,))

def driver(master, g):
    new_master = apply_fn(master, g)
    return master.sum() + new_master.sum()
''')
    assert rule_ids(diags) == ["DSP603"]


def test_dsp603_clean_twins(tmp_path):
    # (a) rebinding the donated name to the call result kills the watch
    assert lint_src(tmp_path, '''
import jax

accum_fn = jax.jit(lambda a, g: a + g, donate_argnums=(0,))

def driver(acc, grads):
    for g in grads:
        acc = accum_fn(acc, g)
    return acc
''') == []
    # (b) the fixed PR 8 shape: staging deleted, buffer re-homed
    # through a jitted copy before the donating call
    assert lint_src(tmp_path, '''
import jax
import jax.numpy as jnp
import numpy as np

step = jax.jit(lambda m, g: m + g, donate_argnums=(0,))

def driver(sharding, g):
    buf = np.zeros((4, 4), np.float32)
    staged = jax.device_put(buf, sharding)
    del buf
    master = jax.jit(lambda m: m + jnp.zeros((), m.dtype))(staged)
    out = step(master, g)
    return out
''') == []
    # (c) engine-style pytree-slot calls are the sanctioned pattern:
    # self.state[...] arguments are rebound by the outputs, not names
    assert lint_src(tmp_path, '''
import jax

class Engine:
    def __init__(self):
        self._apply_fn = jax.jit(lambda m, g: m + g, donate_argnums=(0,))

    def step(self, g):
        self.state["master"] = self._apply_fn(self.state["master"], g)
        return self.state["master"]
''') == []
    # (d) the non-donated argument stays readable
    assert lint_src(tmp_path, '''
import jax

step = jax.jit(lambda m, g: m + g, donate_argnums=(0,))

def driver(master, g):
    out = step(master, g)
    return g.sum() + out.sum()
''') == []


def test_dsp603_computed_argnums_only_flags_staged_numpy(tmp_path):
    # engine-style computed donate tuples: positions unknown -> only
    # the high-confidence staged-numpy shape is flagged
    src = '''
import jax
import numpy as np

donate = (0,) + (1,)
step = jax.jit(lambda m, g: m + g, donate_argnums=donate)

def staged(sharding, g):
    buf = np.zeros((4, 4), np.float32)
    out = step(jax.device_put(buf, sharding), g)
    return buf.sum() + out.sum()

def plain(master, g):
    out = step(master, g)
    return master.sum() + out.sum()
'''
    diags = lint_src(tmp_path, src)
    assert rule_ids(diags) == ["DSP603"]
    assert diags[0].line == 11            # the buf read in staged()


# ------------------------------------ artifacts: dump + CLI --programs
def _program_engine(cpu_devices, tmp_path, **profiling):
    cfg = base_config(
        steps_per_print=10 ** 9,
        telemetry={"enabled": True, "run_dir": str(tmp_path / "run")},
        profiling=dict({"comm_ledger": True}, **profiling))
    cfg["zero_optimization"] = {"stage": 2}
    mesh = make_mesh({"data": 4}, devices=cpu_devices[:4])
    engine, *_ = deepspeed.initialize(
        model=SimpleModel(HIDDEN, nlayers=2), config=cfg, mesh=mesh)
    return engine


def test_program_dump_and_cli_roundtrip(cpu_devices, tmp_path, capsys):
    engine = _program_engine(cpu_devices, tmp_path)
    engine.train_batch(iter([random_batches(1, 16, HIDDEN, seed=0)[0]]))
    engine.close()
    progdir = tmp_path / "run" / "programs"
    names = sorted(os.listdir(progdir))
    assert "train_step.hlo" in names and "train_step.json" in names
    side = json.loads((progdir / "train_step.json").read_text())
    assert side["artifact_schema_version"] == dsp.ARTIFACT_SCHEMA_VERSION
    assert side["donate_argnums"] == [0, 1, 5]
    assert side["mesh_axes"] == {"data": 4}
    assert side["param_bytes"] > 0
    assert side["comm"]["collectives"] > 0
    # offline load agrees with the sidecars
    arts = {a.name: a for a in dsp.load_run_artifacts(str(tmp_path / "run"))}
    assert arts["train_step"].donate_argnums == (0, 1, 5)
    assert "input_output_alias" in arts["train_step"].hlo
    # library-side offline verification returns the engine-report
    # shape and agrees with the CLI
    from deepspeed_tpu.profiling.verify import verify_run_dir
    offline = verify_run_dir(tmp_path / "run")
    assert offline["violations"] == 0 and offline["errors"] == 0
    assert offline["programs_checked"] >= 2
    # the CLI self-verify invocation: zero DSP violations at HEAD
    assert dslint_main(["--programs", str(tmp_path / "run")]) == 0
    out = capsys.readouterr().out
    assert "0 violation(s)" in out
    # a tampered artifact fails through the same CLI path
    side["comm"]["wire_bytes"] = side["comm"]["wire_bytes"] * 10 + 64
    side["comm"]["collectives"] += 3
    (progdir / "train_step.json").write_text(json.dumps(side))
    assert dslint_main(["--programs", str(tmp_path / "run")]) == 1
    assert "DSP613" in capsys.readouterr().out


def test_cli_programs_missing_dir_exits_2(tmp_path, capsys):
    assert dslint_main(["--programs", str(tmp_path)]) == 2
    assert "program artifacts" in capsys.readouterr().err


def test_program_dump_off_without_run_dir(cpu_devices):
    cfg = base_config(steps_per_print=10 ** 9,
                      profiling={"comm_ledger": True})
    mesh = make_mesh({"data": 2}, devices=cpu_devices[:2])
    engine, *_ = deepspeed.initialize(
        model=SimpleModel(HIDDEN, nlayers=2), config=cfg, mesh=mesh)
    assert engine.memory_ledger.dumper is None   # no telemetry run dir
    # ... but the in-memory hook still verifies
    engine.train_batch(iter([random_batches(1, 16, HIDDEN, seed=1)[0]]))
    report = engine.verify_programs()
    assert report["violations"] == 0
    assert report["programs_checked"] >= 1


# --------------------------------------------- engine hook at plan time
def test_verify_programs_at_aot_plan_time(cpu_devices, tmp_path):
    """The capacity-planner integration shape: plan mode compiles the
    step without running it, and verify_programs() renders a verdict
    from the same ledger hook."""
    cfg = base_config(steps_per_print=10 ** 9,
                      profiling={"comm_ledger": True})
    cfg["zero_optimization"] = {"stage": 2}
    mesh = make_mesh({"data": 2}, devices=cpu_devices[:2])
    engine, *_ = deepspeed.initialize(
        model=SimpleModel(HIDDEN, nlayers=2), config=cfg, mesh=mesh,
        aot_plan=True)
    batch = random_batches(1, 16, HIDDEN, seed=2)[0]
    engine.aot_compile_train_step(batch)
    report = engine.verify_programs()
    assert report is not None and report["programs_checked"] >= 1
    assert report["violations"] == 0, [
        d.format() for d in report["diagnostics"]]
    engine.close()


def test_verify_report_shape_and_downgrade_count():
    from deepspeed_tpu.profiling.verify import _report

    hlo = ("HloModule m, input_output_alias={ {0}: (0, {}, may-alias) }"
           ", entry\n")
    diags = dsp.verify_program(dsp.ProgramArtifact(
        name="p", hlo=hlo, donate_argnums=(0,), alias_size_in_bytes=0))
    report = _report(diags, 1)
    # "overlap" is None here: a header-only artifact has no scheduled
    # computation to analyze, and the report must say "no claim"
    # rather than a silent fully-overlapped 0
    assert report == {"programs_checked": 1, "violations": 0,
                      "errors": 0, "downgraded": 1, "overlap": None,
                      "sharding": None, "diagnostics": diags}


# ------------------------------------------- review-hardening paths
def test_cli_programs_foreign_json_only_exits_2(tmp_path, capsys):
    """A telemetry run dir that never dumped programs still holds
    latency-rank*.json etc. — that must be exit 2 ('no artifacts'),
    never a silent 0-violations pass."""
    (tmp_path / "latency-rank0.json").write_text('{"p50": 0.01}')
    assert dslint_main(["--programs", str(tmp_path)]) == 2
    assert "program_dump" in capsys.readouterr().err


def test_missing_hlo_text_is_a_violation_not_clean(tmp_path, capsys):
    """A sidecar whose .hlo file is missing/empty must fail (DSP613),
    not neutralize every HLO-side rule."""
    progdir = tmp_path / "programs"
    progdir.mkdir()
    (progdir / "train_step.json").write_text(json.dumps(
        {"artifact_schema_version": 1, "program": "train_step",
         "donate_argnums": [0, 1], "mesh_axes": {"data": 4}}))
    # no train_step.hlo on disk
    assert dslint_main(["--programs", str(tmp_path)]) == 1
    assert "DSP613" in capsys.readouterr().out
    art = dsp.ProgramArtifact(name="p", hlo="", donate_argnums=(0,))
    diags = dsp.verify_program(art)
    assert rule_ids(diags) == ["DSP613"]
    assert "missing or empty" in diags[0].message


def test_absent_alias_byte_data_downgrades_not_silent():
    """alias_size None (backend/sidecar without memory_analysis) is as
    unverifiable as the ==0 warm-cache case: explicit DSP602, never
    the silent-verified verdict."""
    hlo = ("HloModule m, input_output_alias={ {0}: (0, {}, may-alias) }"
           ", entry\n")
    diags = dsp.verify_program(dsp.ProgramArtifact(
        name="p", hlo=hlo, donate_argnums=(0,),
        alias_size_in_bytes=None))
    assert rule_ids(diags) == ["DSP602"]
    assert "no memory_analysis byte data" in diags[0].message
    assert failing(diags) == []


def test_baseline_key_stable_for_program_findings(tmp_path):
    """Program findings ratchet by (rule, program), not by the run-dir
    path or the byte counts in the message — a baselined intentional
    psum keeps matching after a re-dump or a model resize."""
    from deepspeed_tpu.tools.dslint.cli import baseline_key
    from deepspeed_tpu.tools.dslint.core import Diagnostic

    a = Diagnostic(path="/run1/programs/train_step.hlo", line=1, col=1,
                   rule_id="DSP612",
                   message="[train_step] scalar all-reduce over 4 "
                           "replicas with no 1/k scaling constant ...")
    b = Diagnostic(path="/tmp/other_run/programs/train_step.hlo",
                   line=1, col=1, rule_id="DSP612",
                   message="[train_step] scalar all-reduce over 8 "
                           "replicas with no 1/k scaling constant ...")
    assert baseline_key(a) == baseline_key(b) \
        == "<programs>|DSP612|train_step"
    # AST diagnostics keep the path+message identity
    c = Diagnostic(path="x.py", line=3, col=1, rule_id="DSH101",
                   message=".item() in jit")
    assert baseline_key(c) == "x.py|DSH101|.item() in jit"


def test_capacity_exit_code_fails_on_dsp_violations(monkeypatch):
    """'fails the PLAN, not the 2-AM run': a fitting plan with a DSP
    violation must exit nonzero."""
    from deepspeed_tpu.profiling import capacity

    def fake_plan(config, model, batch, mesh=None, capacity_bytes=None,
                  headroom=capacity.DEFAULT_HEADROOM):
        return {"analysis_available": True, "dsp_violations": 1,
                "dsp_errors": 1, "dsp_downgraded": 0,
                "dsp_findings": ["<train_step>:1:1: DSP601 ..."],
                "predicted_peak_hbm_bytes": 1, "predicted_temp_bytes": 1,
                "argument_bytes": 1, "output_bytes": 1, "alias_bytes": 1,
                "generated_code_bytes": 0, "predicted_host_bytes": 0,
                "host_buffer_bytes": 0, "host_buffer_count": 0,
                "host_state_wire_bytes_per_step": None,
                "capacity_bytes": capacity_bytes, "headroom": headroom,
                "plan_seconds": 0.0, "fit": True}

    monkeypatch.setattr(capacity, "plan", fake_plan)
    import json as _json
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        f.write(_json.dumps({"train_batch_size": 4}))
        cfg = f.name
    rc = capacity.main(["--config", cfg, "--model", "gpt2-medium",
                        "--capacity-gb", "16", "--json"])
    assert rc == 1          # fit=True but the program failed to verify
    os.unlink(cfg)


def test_foreign_non_dict_json_is_skipped_not_traceback(tmp_path,
                                                        capsys):
    """A run dir whose only json is a bare value (metrics.json holding
    a number) is 'no artifacts' (exit 2), never a TypeError traceback."""
    (tmp_path / "metrics.json").write_text("42")
    assert dslint_main(["--programs", str(tmp_path)]) == 2
    assert "program artifacts" in capsys.readouterr().err
    # ... and a non-dict json sitting NEXT to real sidecars is skipped
    progdir = tmp_path / "programs"
    progdir.mkdir()
    (progdir / "junk.json").write_text('"just a string"')
    (progdir / "p.json").write_text(json.dumps(
        {"artifact_schema_version": 1, "program": "p"}))
    (progdir / "p.hlo").write_text("HloModule m, entry\n")
    arts = dsp.load_run_artifacts(str(tmp_path))
    assert [a.name for a in arts] == ["p"]


def test_unavailable_collective_parser_is_loud_dsp614(monkeypatch):
    """If profiling.comm cannot import, the collective checks must
    report DSP614 ('UNVERIFIED'), not silently verify clean — even on
    the marquee flatten-×tp artifact."""
    monkeypatch.setattr(dsp, "_parse_collectives",
                        lambda hlo, n: None)
    art = dsp.ProgramArtifact(
        name="flatten_to_master",
        hlo="  %ar = f32[16384]{0} all-reduce(f32[16384]{0} %x), "
            "replica_groups={{0,1,2,3}}, to_apply=%add\n",
        mesh_axes={"data": 2, "model": 2}, param_bytes=65536)
    diags = dsp.verify_program(art)
    assert rule_ids(diags) == ["DSP614"]
    assert "UNVERIFIED" in diags[0].message


def test_partial_donation_drop_lower_bound():
    """Fewer distinct aliased parameters than declared donated argnums
    proves a whole donated argument aliased nothing: explicit DSP602,
    not silent-verified."""
    hlo = ("HloModule m, input_output_alias={ {0}: (0, {}, may-alias), "
           "{1}: (0, {}, may-alias) }, entry\n")   # 1 distinct param
    diags = dsp.verify_program(dsp.ProgramArtifact(
        name="p", hlo=hlo, donate_argnums=(0, 1, 4),
        alias_size_in_bytes=4096))
    assert rule_ids(diags) == ["DSP602"]
    assert "at least one donated argument" in diags[0].message
    # enough distinct params for every declared argnum -> verified
    hlo_ok = ("HloModule m, input_output_alias={ {0}: (0, {}, "
              "may-alias), {1}: (1, {}, may-alias), {2}: (4, {}, "
              "may-alias) }, entry\n")
    assert dsp.verify_program(dsp.ProgramArtifact(
        name="p", hlo=hlo_ok, donate_argnums=(0, 1, 4),
        alias_size_in_bytes=4096)) == []


def test_malformed_sidecar_types_exit_2_not_traceback(tmp_path, capsys):
    progdir = tmp_path / "programs"
    progdir.mkdir()
    (progdir / "p.json").write_text(json.dumps(
        {"artifact_schema_version": 1, "program": "p",
         "donate_argnums": 5}))           # int, not a list
    (progdir / "p.hlo").write_text("HloModule m, entry\n")
    assert dslint_main(["--programs", str(tmp_path)]) == 2
    assert "malformed program sidecar" in capsys.readouterr().err
    (progdir / "p.json").write_text(json.dumps(
        {"artifact_schema_version": 1, "program": "p",
         "mesh_axes": [4]}))              # list, not a dict
    assert dslint_main(["--programs", str(tmp_path)]) == 2


def test_program_dump_true_forces_the_hook_with_ledgers_off(
        cpu_devices, tmp_path):
    """Explicit program_dump=true must dump even when memory_ledger and
    comm_ledger are BOTH explicitly false (the knob's 'true forces the
    dump' contract) — the shared AOT hook goes live for the dumper."""
    cfg = base_config(
        steps_per_print=10 ** 9,
        telemetry={"enabled": True, "run_dir": str(tmp_path / "run")},
        profiling={"memory_ledger": False, "comm_ledger": False,
                   "program_dump": True})
    mesh = make_mesh({"data": 2}, devices=cpu_devices[:2])
    engine, *_ = deepspeed.initialize(
        model=SimpleModel(HIDDEN, nlayers=2), config=cfg, mesh=mesh)
    assert engine.memory_ledger.enabled
    assert engine.memory_ledger.dumper is not None
    engine.train_batch(iter([random_batches(1, 16, HIDDEN, seed=3)[0]]))
    engine.close()
    names = os.listdir(tmp_path / "run" / "programs")
    assert "train_step.hlo" in names and "train_step.json" in names
    assert dslint_main(["--programs", str(tmp_path / "run")]) == 0


def test_null_hlo_file_sidecar_exits_2_not_traceback(tmp_path, capsys):
    progdir = tmp_path / "programs"
    progdir.mkdir()
    (progdir / "p.json").write_text(json.dumps(
        {"artifact_schema_version": 1, "program": "p",
         "hlo_file": None}))          # null: falls back to p.hlo
    (progdir / "p.hlo").write_text("HloModule m, entry\n")
    assert dslint_main(["--programs", str(tmp_path)]) == 0
    (progdir / "p.json").write_text(json.dumps(
        {"artifact_schema_version": 1, "program": "p",
         "hlo_file": 42}))            # non-string: malformed
    assert dslint_main(["--programs", str(tmp_path)]) == 2
    assert "hlo_file" in capsys.readouterr().err


def test_capacity_warnings_report_but_do_not_gate(monkeypatch):
    """Heuristic DSP warnings (psum-for-pmean suspect, ledger drift)
    have no ratchet on the planner surface, so they print in the
    report but must not turn a fitting plan into exit 1 — only
    error-severity findings gate."""
    from deepspeed_tpu.profiling import capacity

    def fake_plan(config, model, batch, mesh=None, capacity_bytes=None,
                  headroom=capacity.DEFAULT_HEADROOM):
        return {"analysis_available": True, "dsp_violations": 1,
                "dsp_errors": 0,          # the one finding is a warning
                "dsp_downgraded": 0,
                "dsp_findings": ["<p>:1:1: DSP612 [warning] ..."],
                "predicted_peak_hbm_bytes": 1, "predicted_temp_bytes": 1,
                "argument_bytes": 1, "output_bytes": 1, "alias_bytes": 1,
                "generated_code_bytes": 0, "predicted_host_bytes": 0,
                "host_buffer_bytes": 0, "host_buffer_count": 0,
                "host_state_wire_bytes_per_step": None,
                "capacity_bytes": capacity_bytes, "headroom": headroom,
                "plan_seconds": 0.0, "fit": True}

    monkeypatch.setattr(capacity, "plan", fake_plan)
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        f.write('{"train_batch_size": 4}')
        cfg = f.name
    assert capacity.main(["--config", cfg, "--model", "gpt2-medium",
                          "--capacity-gb", "16", "--json"]) == 0
    os.unlink(cfg)


def test_dsp603_message_carries_no_line_number(tmp_path):
    """baseline keys embed messages verbatim; a line number in the
    DSP603 message would break the ratchet on any unrelated edit."""
    diags = lint_src(tmp_path, _STAGED_DONATION)
    assert rule_ids(diags) == ["DSP603"]
    import re as _re

    assert not _re.search(r"line \d+", diags[0].message)
    assert diags[0].line > 0          # the location IS the read site


def test_verify_withholds_verdict_when_no_hlo_available(cpu_devices,
                                                        monkeypatch):
    """If no compiled program yields HLO text, verify_programs() must
    return None ('could not verify'), never a 0-violation report —
    receipts then omit the field instead of claiming clean."""
    cfg = base_config(steps_per_print=10 ** 9,
                      profiling={"comm_ledger": True})
    mesh = make_mesh({"data": 2}, devices=cpu_devices[:2])
    engine, *_ = deepspeed.initialize(
        model=SimpleModel(HIDDEN, nlayers=2), config=cfg, mesh=mesh)
    engine.train_batch(iter([random_batches(1, 16, HIDDEN, seed=5)[0]]))
    from deepspeed_tpu.profiling import verify as pv

    monkeypatch.setattr(pv, "build_engine_artifact",
                        lambda engine, name, compiled: None)
    assert engine.verify_programs() is None


# ------------------------------------------- DSS8xx sharding auditor
def _declared(tag, mesh_axes, **families):
    """An engine-shaped declared_sharding dict from
    ``family=[(global_bytes, axes, divisor), ...]`` kwargs."""
    from deepspeed_tpu.profiling import sharding as sharding_prof

    return {"tag": tag, "mesh_axes": dict(mesh_axes),
            "families": {fam: sharding_prof.build_declared_family(leaves)
                         for fam, leaves in families.items()}}


def _jit_param_program(mesh, x, spec):
    from jax.sharding import NamedSharding

    sh = NamedSharding(mesh, spec)
    with mesh:
        return jax.jit(lambda p: p * 2.0, in_shardings=sh,
                       out_shardings=sh).lower(x).compile()


def test_rebroken_replicated_params_trip_dss801(cpu_devices):
    """THE round-17 regression fixture: parameters DECLARED ÷dp that
    compile fully replicated on the dp mesh — numerically identical,
    loss finite, every device silently paying ×dp resident bytes —
    must fail statically, with the fold priced in the message."""
    mesh = make_mesh({"data": 4}, devices=cpu_devices[:4])
    x = jnp.zeros((512, 1024), jnp.float32)       # 2 MiB ≥ audit floor
    nb = x.size * 4
    decl = _declared("zero3|data4", {"data": 4},
                     params=[(nb, ["data"], 4)])
    compiled = _jit_param_program(mesh, x, P())   # the re-broken layout
    art = dsp.ProgramArtifact(
        name="train_step", hlo=compiled.as_text(),
        mesh_axes={"data": 4}, declared_sharding=decl)
    diags = dsp.verify_program(art)
    assert "DSS801" in rule_ids(diags), rule_ids(diags)
    bad = [d for d in diags if d.rule_id == "DSS801"][0]
    assert bad.severity == "error" and failing([bad])
    assert "×4" in bad.message and "replicated" in bad.message
    assert f"{nb // 4} declared -> {nb} actual" in bad.message
    # ... and the summary prices the fold: per-device == global
    summary = dsp.program_sharding(art)
    assert summary["param_bytes_per_device"] == nb
    assert summary["param_shard_divisor"] == 1
    # the FIXED layout (the same declaration actually materialized)
    # verifies clean and halves^2 the receipt
    compiled_ok = _jit_param_program(mesh, x, P("data"))
    ok = dsp.ProgramArtifact(
        name="train_step", hlo=compiled_ok.as_text(),
        mesh_axes={"data": 4}, declared_sharding=decl)
    assert dsp.verify_program(ok) == []
    summary_ok = dsp.program_sharding(ok)
    assert summary_ok["param_bytes_per_device"] == nb // 4
    assert summary_ok["param_shard_divisor"] == 4


def test_sub_mib_fold_stays_quiet(cpu_devices):
    """DSS801 has a 1 MiB floor: a small declared-sharded tensor that
    materializes replicated is noise, not a capacity regression."""
    mesh = make_mesh({"data": 4}, devices=cpu_devices[:4])
    x = jnp.zeros((64, 64), jnp.float32)          # 16 KiB
    decl = _declared("zero3|data4", {"data": 4},
                     params=[(x.size * 4, ["data"], 4)])
    compiled = _jit_param_program(mesh, x, P())
    art = dsp.ProgramArtifact(
        name="train_step", hlo=compiled.as_text(),
        mesh_axes={"data": 4}, declared_sharding=decl)
    assert dsp.verify_program(art) == []
    # the mismatch is still RECORDED (receipts see it) — only the
    # diagnostic is floored
    summary = dsp.program_sharding(art)
    assert summary["families"]["params"]["mismatches"]


def test_cross_program_layout_divergence_trips_dss802(cpu_devices):
    """The same declared family materializing ÷4 in one program and
    replicated in another pays an unpriced reshard at the boundary:
    DSS802 on the divergent program, naming both layouts."""
    mesh = make_mesh({"data": 4}, devices=cpu_devices[:4])
    x = jnp.zeros((512, 1024), jnp.float32)
    nb = x.size * 4
    # declared replicated in BOTH sidecars so DSS801 stays out of the
    # frame: DSS802 compares what MATERIALIZED, not what was declared
    decl = _declared("zero2|data4", {"data": 4},
                     params=[(nb, [], 1)])
    art_sharded = dsp.ProgramArtifact(
        name="z_step", hlo=_jit_param_program(mesh, x, P("data")).as_text(),
        mesh_axes={"data": 4}, declared_sharding=decl)
    art_replicated = dsp.ProgramArtifact(
        name="a_step", hlo=_jit_param_program(mesh, x, P()).as_text(),
        mesh_axes={"data": 4}, declared_sharding=decl)
    diags = dsp.check_sharding_consistency([art_sharded, art_replicated])
    assert rule_ids(diags) == ["DSS802"]
    msg = diags[0].message
    assert "family 'params'" in msg
    assert "÷1" in msg and "÷4" in msg and "[z_step]" in msg
    # same artifacts through the CLI-facing batch entry point
    assert "DSS802" in rule_ids(
        dsp.verify_artifacts([art_sharded, art_replicated]))
    # agreeing layouts: silent
    art_sharded2 = dsp.ProgramArtifact(
        name="b_step", hlo=art_sharded.hlo,
        mesh_axes={"data": 4}, declared_sharding=decl)
    assert dsp.check_sharding_consistency(
        [art_sharded, art_sharded2]) == []


def test_param_bytes_ratchet_trips_dss803(cpu_devices):
    """A re-replication that the declaration ALSO weakened (so DSS801
    cannot fire) still trips the baseline ratchet: the recorded
    per-device figure is the contract."""
    mesh = make_mesh({"data": 4}, devices=cpu_devices[:4])
    x = jnp.zeros((512, 1024), jnp.float32)
    nb = x.size * 4
    hlo_rep = _jit_param_program(mesh, x, P()).as_text()
    # the declaration says replicated (weakened), matching the compile
    decl = _declared("zero2|data4", {"data": 4}, params=[(nb, [], 1)])
    art = dsp.ProgramArtifact(
        name="train_step", hlo=hlo_rep,
        mesh_axes={"data": 4}, declared_sharding=decl)
    assert dsp.verify_program(art) == []          # DSS801 blind here
    key = dsp.sharding_metric_key("zero2|data4", "train_step")
    # baseline recorded the ÷4 era: ×4 growth far exceeds tolerance
    diags = dsp.check_sharding_ratchet([art], {key: nb / 4})
    assert rule_ids(diags) == ["DSS803"]
    assert f"grew {nb // 4} -> {nb}" in diags[0].message
    # within tolerance (same figure): silent; no recorded key: silent
    assert dsp.check_sharding_ratchet([art], {key: float(nb)}) == []
    assert dsp.check_sharding_ratchet([art], {}) == []
    # ... and sharding_metrics records exactly this key
    assert dsp.sharding_metrics([art]) == {key: float(nb)}


def test_unavailable_sharding_parser_is_loud_dss804(monkeypatch):
    """If profiling.sharding cannot import, a program WITH a declared
    spec must report DSS804 ('UNVERIFIED'), not silently verify clean
    — the DSP614 contract applied to residency."""
    monkeypatch.setattr(dsp, "_load_sharding", lambda: None)
    art = dsp.ProgramArtifact(
        name="train_step", hlo="HloModule m, entry\n",
        mesh_axes={"data": 4},
        declared_sharding=_declared("zero2|data4", {"data": 4},
                                    params=[(1 << 21, ["data"], 4)]))
    diags = dsp.verify_program(art)
    assert rule_ids(diags) == ["DSS804"]
    assert "UNVERIFIED" in diags[0].message
    # warning severity: the planner's error-count gate ignores it, but
    # the CLI still fails fresh (only --baseline can absolve it) — the
    # same contract as DSP614
    assert diags[0].severity == "warning"
    # no declaration -> nothing to verify, no noise
    bare = dsp.ProgramArtifact(name="p", hlo="HloModule m, entry\n")
    assert dsp.verify_program(bare) == []


def test_declared_sharding_sidecar_roundtrip(cpu_devices, tmp_path):
    """The engine's declared spec survives ProgramDumper → sidecar →
    offline load byte-identically, and the offline report carries the
    per-device residency receipt."""
    engine = _program_engine(cpu_devices, tmp_path)
    engine.train_batch(iter([random_batches(1, 16, HIDDEN, seed=7)[0]]))
    engine.close()
    progdir = tmp_path / "run" / "programs"
    side = json.loads((progdir / "train_step.json").read_text())
    decl = side["declared_sharding"]
    assert decl["tag"] == "zero2|data4"
    assert set(decl["families"]) >= {"params", "master", "optimizer"}
    for fam in ("params", "master", "optimizer"):
        assert decl["families"][fam]["total_bytes"] > 0
        assert decl["families"][fam]["leaves"]
    # offline load agrees byte-for-byte with the sidecar
    arts = {a.name: a
            for a in dsp.load_run_artifacts(str(tmp_path / "run"))}
    assert arts["train_step"].declared_sharding == decl
    # the offline report prices residency from the same artifacts
    from deepspeed_tpu.profiling.verify import verify_run_dir
    offline = verify_run_dir(tmp_path / "run")
    assert offline["violations"] == 0
    sh = offline["sharding"]["train_step"]
    assert sh["param_bytes_per_device"] > 0
    assert sh["param_shard_divisor"] >= 1
    # ... and the CLI path stays clean over the same run dir
    assert dslint_main(["--programs", str(tmp_path / "run")]) == 0


def test_malformed_declared_sharding_sidecar_exits_2(tmp_path, capsys):
    """A type-tampered declared_sharding must fail the CLI loudly
    (exit 2), never quietly disable the DSS8xx reconciliation."""
    progdir = tmp_path / "programs"
    progdir.mkdir()
    (progdir / "p.hlo").write_text("HloModule m, entry\n")
    (progdir / "p.json").write_text(json.dumps(
        {"artifact_schema_version": 1, "program": "p",
         "declared_sharding": "zero2|data4"}))     # string, not object
    assert dslint_main(["--programs", str(tmp_path)]) == 2
    assert "malformed program sidecar" in capsys.readouterr().err
    (progdir / "p.json").write_text(json.dumps(
        {"artifact_schema_version": 1, "program": "p",
         "declared_sharding": {"tag": "t", "families": 3}}))
    assert dslint_main(["--programs", str(tmp_path)]) == 2
    (progdir / "p.json").write_text(json.dumps(
        {"artifact_schema_version": 1, "program": "p",
         "declared_sharding": {"tag": "t",
                               "families": {"params": {"leaves": 5}}}}))
    assert dslint_main(["--programs", str(tmp_path)]) == 2
    # absent field (a pre-DSS8 sidecar): loads and verifies clean
    (progdir / "p.json").write_text(json.dumps(
        {"artifact_schema_version": 1, "program": "p"}))
    assert dslint_main(["--programs", str(tmp_path)]) == 0
