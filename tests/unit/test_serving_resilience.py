"""Serving-replica health plane (inference/resilience.py): the
freshness hang quorum, the weight-fingerprint consensus, SIGTERM drain,
and the zero-added-syncs guarantee with the whole plane armed.

The real-launcher serving chaos e2e (test_serving_chaos_e2e.py) drives
the same machinery across actual processes; these units pin each
verdict path in isolation.
"""

import json
import os
import signal
import time

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngine
from deepspeed_tpu.inference import resilience as sres
from deepspeed_tpu.resilience import integrity as integ
from deepspeed_tpu.resilience.chaos import ChaosMonkey
from deepspeed_tpu.resilience.constants import (EXIT_INTEGRITY_EVICT,
                                                FleetIntegrityError,
                                                TrainingDivergedError)

from .test_inference import (count_device_gets, seeded_prompts,
                             serve_config, tiny_model,
                             model_and_params)  # noqa: F401 — fixture


# ---------------------------------------------------------------------------
# serving_hang_quorum: freshness-majority over incomparable counters
# ---------------------------------------------------------------------------

def _fleet(now, **beats):
    """{rank: {"step", "ts"}} from rank=(step, age_secs) kwargs."""
    return {int(r[1:]): {"step": step, "ts": now - age}
            for r, (step, age) in beats.items()}


class TestServingHangQuorum:
    def test_names_stale_peer_with_fresh_majority(self):
        now = time.time()
        fleet = _fleet(now, r0=(7, 0.0), r1=(3, 9.0), r2=(40, 0.1))
        v = sres.serving_hang_quorum(fleet, 0, 3, 1.0, now=now)
        assert v is not None and v["suspect"] == 1
        assert v["stalled_secs"] == pytest.approx(9.0)
        assert v["leaders"] == 2 and v["fleet"] == 3

    def test_slow_but_fresh_replica_is_never_named(self):
        # rank 1 is far behind in iterations but its beat is FRESH: a
        # busy replica chewing a long batch, not a hang.  The training
        # quorum would see it parked at a low step; the serving quorum
        # must not care about step position at all
        now = time.time()
        fleet = _fleet(now, r0=(500, 0.0), r1=(2, 0.2), r2=(480, 0.1))
        assert sres.serving_hang_quorum(fleet, 0, 3, 1.0, now=now) is None

    def test_stale_self_abstains(self):
        # this rank's own beat is stale — it may be the wedged one, and
        # a wedged rank must never convict a peer
        now = time.time()
        fleet = _fleet(now, r0=(7, 5.0), r1=(3, 9.0), r2=(40, 0.1))
        assert sres.serving_hang_quorum(fleet, 0, 3, 1.0, now=now) is None

    def test_no_fresh_majority_abstains(self):
        # 1 fresh of fleet 3: a partition this small must not evict
        now = time.time()
        fleet = _fleet(now, r0=(7, 0.0), r1=(3, 9.0), r2=(40, 8.0))
        assert sres.serving_hang_quorum(fleet, 0, 3, 1.0, now=now) is None

    def test_unpublished_ranks_count_against_quorum(self):
        # fleet_size 4 but only 2 published: 2 fresh of FLEET 4 is not
        # a strict majority even though every publisher is fresh
        now = time.time()
        fleet = _fleet(now, r0=(7, 0.0), r1=(3, 9.0))
        assert sres.serving_hang_quorum(fleet, 0, 4, 1.0, now=now) is None
        # the same two beats in a fleet of 3... still 1 fresh short?
        # no: 1 fresh of 3 fails, 2 fresh of 3 passes
        fleet2 = _fleet(now, r0=(7, 0.0), r1=(3, 9.0), r2=(9, 0.1))
        assert sres.serving_hang_quorum(fleet2, 0, 3, 1.0,
                                        now=now)["suspect"] == 1

    def test_names_the_stalest_when_several_are_stale(self):
        now = time.time()
        fleet = _fleet(now, r0=(1, 0.0), r1=(1, 3.0), r2=(1, 7.0),
                       r3=(1, 0.1), r4=(1, 0.2))
        v = sres.serving_hang_quorum(fleet, 0, 5, 1.0, now=now)
        assert v["suspect"] == 2

    def test_single_replica_never_fires(self):
        now = time.time()
        assert sres.serving_hang_quorum(_fleet(now, r0=(1, 0.0)), 0, 1,
                                        1.0, now=now) is None


# ---------------------------------------------------------------------------
# weight-fingerprint exchange + consensus
# ---------------------------------------------------------------------------

class TestWeightFingerprintExchange:
    def test_publish_read_roundtrip_under_fixed_step(self, tmp_path):
        for rank, fp in ((0, 0xAB12), (1, 0xAB12), (2, 0xFF00)):
            assert sres.publish_weight_fingerprint(tmp_path, rank, fp)
        fleet = sres.read_fleet_weight_fingerprints(tmp_path, 3)
        assert set(fleet) == {0, 1, 2}
        assert fleet[0] == {sres.SERVING_FINGERPRINT_STEP: "0000ab12"}
        v = integ.fingerprint_consensus(fleet, 3)
        assert v["verdict"] == integ.VERDICT_OUTLIER
        assert v["suspects"] == [2]

    def test_republish_refreshes_timestamp(self, tmp_path):
        sres.publish_weight_fingerprint(tmp_path, 0, 1)
        path = tmp_path / integ.fingerprint_filename(0)
        first = json.loads(path.read_text())["ts"]
        time.sleep(0.02)
        sres.publish_weight_fingerprint(tmp_path, 0, 1)
        assert json.loads(path.read_text())["ts"] > first


def _mk_engine(model_and_params, tmp_path=None, **cfg_overrides):
    model, params = model_and_params
    config = serve_config(**cfg_overrides)
    if tmp_path is not None:
        config["telemetry"] = {"enabled": True, "run_dir": str(tmp_path)}
    return InferenceEngine(model, params, config=config)


class TestServingHealthConsensus:
    def test_fingerprint_is_deterministic_and_flip_sensitive(
            self, model_and_params):
        e1 = _mk_engine(model_and_params)
        e2 = _mk_engine(model_and_params)
        h1 = sres.ServingHealth(e1, "/tmp/unused", 0, 2)
        h2 = sres.ServingHealth(e2, "/tmp/unused", 1, 2)
        fp1 = int(jax.device_get(h1.fingerprint_device()))
        fp2 = int(jax.device_get(h2.fingerprint_device()))
        assert fp1 == fp2, "same weights must fingerprint identically"
        ChaosMonkey(seed=3).bitflip_params(e2)
        fp2b = int(jax.device_get(h2.fingerprint_device()))
        assert fp2b != fp2, "a single flipped bit must change the sum"
        e1.close()
        e2.close()

    def test_outlier_verdict_convicts_and_raises(self, model_and_params,
                                                 tmp_path):
        engine = _mk_engine(model_and_params, tmp_path=tmp_path / "t")
        health = sres.ServingHealth(engine, tmp_path, 0, 3)
        # two healthy peers agree; this replica publishes the odd one out
        integ.publish_rank_fingerprint(
            tmp_path, 1, {sres.SERVING_FINGERPRINT_STEP: "00000aaa"})
        integ.publish_rank_fingerprint(
            tmp_path, 2, {sres.SERVING_FINGERPRINT_STEP: "00000aaa"})
        with pytest.raises(FleetIntegrityError) as err:
            health.note_weight_fingerprint(0xBBB)
        assert err.value.exit_code == EXIT_INTEGRITY_EVICT
        assert err.value.suspect == 0
        assert health.violations == 1
        verdict = integ.read_verdict(tmp_path)
        assert verdict is not None
        assert verdict["kind"] == integ.KIND_SDC
        assert verdict["suspect"] == 0
        engine.close()
        events = [json.loads(line) for line in
                  open(tmp_path / "t" / "events-rank0.jsonl")]
        evict = [e for e in events if e["type"] == "serving"
                 and e["data"].get("kind") == "evict"]
        assert evict and evict[0]["data"]["suspect"] == 0
        integ_events = [e for e in events if e["type"] == "integrity"]
        assert any(e["data"]["verdict"] == "outlier" for e in integ_events)

    def test_majority_agreement_is_ok(self, model_and_params, tmp_path):
        engine = _mk_engine(model_and_params)
        health = sres.ServingHealth(engine, tmp_path, 0, 3)
        integ.publish_rank_fingerprint(
            tmp_path, 1, {sres.SERVING_FINGERPRINT_STEP: "00000bbb"})
        integ.publish_rank_fingerprint(
            tmp_path, 2, {sres.SERVING_FINGERPRINT_STEP: "00000bbb"})
        v = health.note_weight_fingerprint(0xBBB)
        assert v["verdict"] == integ.VERDICT_OK
        assert health.violations == 0
        assert integ.read_verdict(tmp_path) is None
        engine.close()

    def test_lone_replica_is_pending_not_convicted(self, model_and_params,
                                                   tmp_path):
        # fleet_size 1 (or peers not yet published): nobody to vote
        # with — the verdict is pending, never an eviction
        engine = _mk_engine(model_and_params)
        health = sres.ServingHealth(engine, tmp_path, 0, 1)
        v = health.note_weight_fingerprint(0x123)
        assert v["verdict"] == integ.VERDICT_PENDING
        engine.close()

    def test_no_majority_poisons(self, model_and_params, tmp_path):
        engine = _mk_engine(model_and_params)
        health = sres.ServingHealth(engine, tmp_path, 0, 2)
        integ.publish_rank_fingerprint(
            tmp_path, 1, {sres.SERVING_FINGERPRINT_STEP: "00000ccc"})
        with pytest.raises(TrainingDivergedError):
            health.note_weight_fingerprint(0xDDD)
        engine.close()

    def test_warn_action_only_counts(self, model_and_params, tmp_path):
        engine = _mk_engine(model_and_params)
        health = sres.ServingHealth(engine, tmp_path, 0, 3,
                                    action="warn")
        integ.publish_rank_fingerprint(
            tmp_path, 1, {sres.SERVING_FINGERPRINT_STEP: "00000aaa"})
        integ.publish_rank_fingerprint(
            tmp_path, 2, {sres.SERVING_FINGERPRINT_STEP: "00000aaa"})
        v = health.note_weight_fingerprint(0xBBB)
        assert v["verdict"] == integ.VERDICT_OUTLIER
        assert health.violations == 1
        assert integ.read_verdict(tmp_path) is None  # telemetry only
        engine.close()


class TestHangEviction:
    def test_stale_peer_convicted_through_heartbeat_monitor(
            self, model_and_params, tmp_path):
        """End-to-end through FleetHeartbeat with the serving quorum
        injected: rank 1's beat goes stale while ranks 0 and 2 keep
        beating (the strict fresh majority) — rank 0's monitor must
        write a hang verdict naming 1 and request the respawnable
        eviction exit."""
        engine = _mk_engine(model_and_params, tmp_path=tmp_path / "t")
        codes = []
        health = sres.ServingHealth(engine, tmp_path, 0, 3,
                                    peer_timeout_secs=0.4,
                                    poll_interval=0.05,
                                    exit_fn=codes.append)
        integ.publish_rank_heartbeat(tmp_path, 1, 3)  # beats once, wedges
        engine.attach_health(health)
        deadline = time.monotonic() + 5.0
        step = 0
        while not health.heartbeat.fired and time.monotonic() < deadline:
            step += 1
            health.beat(step)                     # this rank stays live...
            integ.publish_rank_heartbeat(tmp_path, 2, step)  # ...peer 2 too
            time.sleep(0.05)
        assert health.heartbeat.fired, "hang quorum never fired"
        assert codes == [EXIT_INTEGRITY_EVICT]
        verdict = integ.read_verdict(tmp_path)
        assert verdict is not None
        assert verdict["kind"] == integ.KIND_HANG
        assert verdict["suspect"] == 1
        engine.close()
        events = [json.loads(line) for line in
                  open(tmp_path / "t" / "events-rank0.jsonl")]
        assert any(e["type"] == "serving"
                   and e["data"].get("kind") == "evict"
                   and e["data"].get("suspect") == 1 for e in events)


# ---------------------------------------------------------------------------
# drain deadline contract + SIGTERM preemption
# ---------------------------------------------------------------------------

class TestDrainDeadline:
    def test_explicit_value_wins(self, monkeypatch):
        monkeypatch.setenv("DS_TERM_DRAIN_DEADLINE_SECS", "7.5")
        assert sres.drain_deadline_secs() == 7.5

    def test_default_is_ninety_percent_of_grace(self, monkeypatch):
        monkeypatch.delenv("DS_TERM_DRAIN_DEADLINE_SECS", raising=False)
        monkeypatch.setenv("DS_TERM_GRACE_SECS", "10")
        assert sres.drain_deadline_secs() == pytest.approx(9.0)

    def test_malformed_degrades_never_aborts(self, monkeypatch):
        monkeypatch.setenv("DS_TERM_DRAIN_DEADLINE_SECS", "90s")
        monkeypatch.setenv("DS_TERM_GRACE_SECS", "20")
        assert sres.drain_deadline_secs() == pytest.approx(18.0)

    def test_zero_disables_the_bound(self, monkeypatch):
        monkeypatch.setenv("DS_TERM_DRAIN_DEADLINE_SECS", "0")
        assert sres.drain_deadline_secs() == 0.0


class _FakeEngine:
    """Stdlib stand-in for the duck-typed drain contract."""

    def __init__(self):
        self.closed_with = []

    def close(self, reason="?"):
        self.closed_with.append(reason)


class TestServingPreemption:
    def test_sigterm_drains_then_exits_respawnable(self):
        fake = _FakeEngine()
        codes = []
        old = signal.getsignal(signal.SIGTERM)
        try:
            sres.arm_serving_preemption(fake, exit_fn=codes.append)
            signal.raise_signal(signal.SIGTERM)
        finally:
            signal.signal(signal.SIGTERM, old)
        assert fake.closed_with == ["preempt_drain"]
        assert codes == [128 + signal.SIGTERM]

    def test_drain_failure_still_exits_respawnable(self):
        class Exploding:
            def close(self, reason="?"):
                raise RuntimeError("drain blew up")

        codes = []
        old = signal.getsignal(signal.SIGTERM)
        try:
            sres.arm_serving_preemption(Exploding(), exit_fn=codes.append)
            signal.raise_signal(signal.SIGTERM)
        finally:
            signal.signal(signal.SIGTERM, old)
        assert codes == [128 + signal.SIGTERM]


class TestEngineDrainClose:
    def test_drain_finishes_inflight_and_stops_admission(
            self, model_and_params):
        engine = _mk_engine(model_and_params)
        prompts = seeded_prompts(3, seed=21)
        for i, p in enumerate(prompts):
            engine.submit(p, max_new_tokens=4, request_id=f"r{i}")
        engine.step()                      # admit + first decode
        drained = engine.drain()
        assert engine.scheduler.active_count == 0
        assert {r.request_id for r in drained} == {"r0", "r1", "r2"}
        assert all(len(r.generated) == 4 for r in drained)
        with pytest.raises(RuntimeError, match="draining"):
            engine.submit(prompts[0], max_new_tokens=2)
        engine.close()

    def test_drain_deadline_abandons_rather_than_hangs(
            self, model_and_params, monkeypatch):
        engine = _mk_engine(model_and_params)
        engine.submit(seeded_prompts(1, seed=22)[0], max_new_tokens=8)
        engine.step()
        # a deadline already in the past: drain must give up instantly
        # (the router re-serves), not loop the remaining decodes
        monkeypatch.setattr(
            "deepspeed_tpu.inference.resilience.drain_deadline_secs",
            lambda grace=None: 1e-9)
        before = engine.decode_iterations
        engine.drain(deadline_secs=1e-9)
        assert engine.decode_iterations <= before + 1
        assert engine.scheduler.active_count == 1   # abandoned, not lost
        engine.close()

    def test_close_is_idempotent_and_emits_run_end(self, model_and_params,
                                                   tmp_path):
        engine = _mk_engine(model_and_params, tmp_path=tmp_path)
        rid = engine.submit(seeded_prompts(1, seed=23)[0],
                            max_new_tokens=3)
        engine.step()      # admit: the request now holds KV state
        engine.close(reason="preempt_drain")
        engine.close(reason="preempt_drain")    # second call: no-op
        results = {r: req.result() for r, req in engine._results.items()}
        assert len(results[rid]["tokens"]) == 3
        events = [json.loads(line) for line in
                  open(tmp_path / "events-rank0.jsonl")]
        ends = [e for e in events if e["type"] == "run_end"]
        assert len(ends) == 1
        assert ends[0]["data"]["reason"] == "preempt_drain"
        assert any(e["type"] == "serving"
                   and e["data"].get("kind") == "drain" for e in events)


# ---------------------------------------------------------------------------
# zero added syncs with the FULL resilience plane armed
# ---------------------------------------------------------------------------

def test_zero_added_host_syncs_with_health_armed(model_and_params,
                                                 tmp_path, monkeypatch):
    """Heartbeats every decode iteration + the weight fingerprint on
    every print cadence (steps_per_print=1: EVERY iteration) must add
    ZERO jax.device_get calls over the bare serve loop — the
    fingerprint scalar rides the next-token fetch."""
    model, params = model_and_params
    prompts = seeded_prompts(6, seed=31)

    def count_gets(health_run_dir):
        config = serve_config(max_batch_slots=2)
        config["steps_per_print"] = 1
        engine = InferenceEngine(model, params, config=config)
        if health_run_dir is not None:
            engine.attach_health(sres.ServingHealth(
                engine, health_run_dir, 0, 1, peer_timeout_secs=60.0))
        # no step() syncs twice, admissions and the fingerprint included
        return count_device_gets(engine, prompts, monkeypatch,
                                 max_new_tokens=4)

    base = count_gets(None)
    armed = count_gets(tmp_path)
    assert base > 0
    assert armed == base, (
        f"the serving health plane added host syncs: {armed} device_get "
        f"calls vs {base} baseline")
    # and it genuinely ran: the fingerprint was published to the run dir
    fleet = sres.read_fleet_weight_fingerprints(tmp_path, 1)
    assert 0 in fleet and sres.SERVING_FINGERPRINT_STEP in fleet[0]


# ---------------------------------------------------------------------------
# launcher integration: SIGTERM drain in a real child process
# ---------------------------------------------------------------------------

def test_sigterm_drain_through_real_launcher(tmp_path, monkeypatch):
    """The launcher SIGTERMs its children on shutdown; an armed serving
    replica must drain (close(reason="preempt_drain") runs, marker
    lands) and die by the re-raised signal — the launcher reads an
    ordinary preemption death (128+15), not a tangle."""
    from .test_launcher import _launch_main

    monkeypatch.setenv("DS_MONITOR_POLL_SECS", "0.1")
    repo = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "..", "..")
    marker = tmp_path / "drained.json"
    child = f"""
import json, os, signal, sys, time
sys.path.insert(0, {repo!r})
from deepspeed_tpu.inference.resilience import arm_serving_preemption

class Engine:                       # duck-typed drain target
    def close(self, reason="?"):
        json.dump({{"reason": reason, "pid": os.getpid()}},
                  open({str(marker)!r}, "w"))

arm_serving_preemption(Engine())
os.kill(os.getppid(), signal.SIGTERM)   # preempt the launcher
for _ in range(600):
    time.sleep(0.1)
"""
    code = _launch_main(tmp_path, child)
    assert code == 128 + signal.SIGTERM
    payload = json.loads(marker.read_text())
    assert payload["reason"] == "preempt_drain"


def test_report_serving_resilience_summary_counts_and_details():
    """The report CLI's serving-resilience block: resilience kinds are
    counted (deadline/degrade counted only; shed/requeue/evict/drain
    itemized with their detail lines), decode-plane kinds and other
    event types are ignored, and a run with no resilience events skips
    the section entirely (empty list)."""
    from deepspeed_tpu.telemetry.report import serving_resilience_summary

    def ev_(kind, ts, **data):
        return {"type": "serving", "rank": 0, "ts": ts, "_stream": "r0",
                "data": dict(data, kind=kind)}

    records = [
        ev_("admit", 1.0, request="req-0"),            # decode plane
        ev_("shed", 2.0, queue_depth=4, max_queue_depth=4),
        ev_("degrade", 2.5, queue_depth=3, capped_to=2),
        ev_("deadline", 3.0, request="req-1"),
        ev_("requeue", 4.0, request="req-2", replica=1, requeues=1,
            backoff_secs=0.5),
        ev_("evict", 5.0, suspect=1, reason="hang_quorum"),
        ev_("drain", 6.0, active=2, queued=1, deadline_secs=9.0),
        {"type": "integrity", "rank": 0, "ts": 7.0, "_stream": "r0",
         "data": {"kind": "evict"}},                   # wrong type
    ]
    lines = serving_resilience_summary(records)
    assert lines[0].split() == ["deadline=1", "shed=1", "degrade=1",
                                "requeue=1", "evict=1", "drain=1"]
    body = "\n".join(lines[1:])
    assert "requeue: request req-2 off dead replica 1" in body
    assert "shed: queue depth 4 at max_queue_depth 4" in body
    assert "evict: replica 1 convicted (hang_quorum)" in body
    assert "drain: 2 active + 1 queued" in body
    # deadline/degrade events are counted, never itemized: nothing in
    # the body names their requests or caps
    assert "req-1" not in body and "capped_to" not in body

    assert serving_resilience_summary(
        [ev_("admit", 1.0), ev_("finish", 2.0)]) == []
