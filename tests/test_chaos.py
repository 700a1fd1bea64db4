"""Chaos-schedule / invariant-auditor / quarantine suite (tier-1;
markers ``chaos`` + ``invariants``; ``run-tests.sh --chaos`` runs both
lanes standalone).

Proves the composed-robustness contract:

- seeded chaos schedules (``resilience/chaos.py``): the decision for a
  site's n-th consult is a pure hash of ``(seed, site, n)`` — same
  seed, same firings, exactly; spec parsing rejects typos instead of
  arming vacuous drills; firings arm the SAME one-shot budgets as
  scripted faults (site-correct classifier shaping included) and are
  flight-recorded for replay;
- the fault-site table (``faults.sites()``): every armed-able site is
  driven here, arming an unknown site warns loudly, and the
  conformance meta-tests keep the docs + test-coverage in sync with
  the table;
- cross-cutting invariant auditors (``resilience/invariants.py``):
  always-on counts + flight-records, strict raises a classified
  ``InvariantViolation``; per-query row-conservation ledger,
  checkpoint cursor checks, exchange conservation (raises in EVERY
  mode), auditor crashes are violations too;
- poison-query quarantine (``serve/quarantine.py``): a streak of
  permanent failures fast-rejects the fingerprint with a classified
  ``QueryQuarantined``; TTL expiry admits ONE probe; success resets;
  ``tft.unquarantine()`` lifts; surfaced in health()/doctor()/
  serve_report();
- persist artifact checksums (``memory/persist.py``): bit-rot that
  still unpickles goes COLD (``memory.persist_corrupt``), never wrong;
  both shapes of the ``disk`` fault site;
- the bounded acceptance drill (``tools/chaos_soak.py``): a mixed
  workload under a seeded multi-site schedule is bit-identical to the
  fault-free run, leaks nothing, classifies every surfaced failure,
  and replays on its seed.
"""

import os
import pickle
import sys
import time

import numpy as np
import pytest

import tensorframes_tpu as tft
from tensorframes_tpu import observability as obs
from tensorframes_tpu import serve
from tensorframes_tpu.engine import pipeline as engine_pipeline
from tensorframes_tpu.memory import persist as _persist
from tensorframes_tpu.memory.checkpoint import QueryCheckpoint
from tensorframes_tpu.observability import flight as obs_flight
from tensorframes_tpu.resilience import chaos, error_kind, faults, invariants
from tensorframes_tpu.resilience.classify import (InvariantViolation,
                                                  QueryQuarantined,
                                                  is_transient)
from tensorframes_tpu.resilience.faults import InjectedFault
from tensorframes_tpu.serve import QueryScheduler, TenantQuota
from tensorframes_tpu.serve import quarantine
from tensorframes_tpu.utils import tracing

pytestmark = pytest.mark.chaos

# tools/ is not a package; the soak driver is imported by path so the
# tier-1 drill and the standalone soak run the exact same code
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import chaos_soak  # noqa: E402

# the literal twin of faults.sites().keys() — kept literal ON PURPOSE:
# the conformance meta-test greps test sources for quoted site names,
# so every site must appear as a string in at least one test file, and
# test_site_table_matches_literals pins this list to the real table
ALL_SITES = ("batch", "cluster_init", "compile", "device", "disk",
             "dispatch", "dmap", "drain", "oom", "pad_compile",
             "perf", "preempt", "worker")


@pytest.fixture(autouse=True)
def _clean_chaos():
    chaos.stop()
    faults.reset()
    quarantine.reset()
    tracing.counters.reset()
    obs.clear_ring()
    yield
    serve.shutdown_default_scheduler()
    chaos.stop()
    faults.reset()
    quarantine.reset()
    tracing.counters.reset()
    obs.clear_ring()
    assert engine_pipeline.current_slot_pool() is None


# -- chaos schedules -------------------------------------------------------

class TestChaosSchedule:
    def test_same_seed_fires_identically(self):
        a = chaos.ChaosSchedule(5, 0.2, ["compile"])
        b = chaos.ChaosSchedule(5, 0.2, ["compile"])
        c = chaos.ChaosSchedule(6, 0.2, ["compile"])
        for _ in range(300):
            a.consult("compile")
            b.consult("compile")
            c.consult("compile")
        faults.reset()  # consult() arms one-shot budgets as it fires
        assert a.firings() == b.firings()
        assert a.firings(), "rate 0.2 over 300 consults never fired"
        assert a.firings() != c.firings()
        assert a.fingerprint() == b.fingerprint()

    def test_check_integration_replays(self):
        spec = "seed:5,rate:0.2,sites:compile"
        raised = []
        for _ in range(2):
            hits = []
            with chaos.inject(spec) as sched:
                for i in range(200):
                    try:
                        faults.check("compile")
                    except InjectedFault:
                        hits.append(i)
                assert len(sched.firings()) == len(hits)
            raised.append(hits)
        assert raised[0], "chaos schedule never fired through check()"
        assert raised[0] == raised[1]

    def test_parse_spec(self):
        s = chaos.parse("seed:42,rate:0.5,sites:device|worker|disk")
        assert (s.seed, s.rate) == (42, 0.5)
        assert s.sites == ("device", "worker", "disk")
        # defaults: seed 0, rate 0.05
        d = chaos.parse("sites:compile")
        assert (d.seed, d.rate) == (0, 0.05)
        with pytest.raises(ValueError, match="malformed"):
            chaos.parse("seed=42,sites:compile")
        with pytest.raises(ValueError, match="unknown TFT_CHAOS key"):
            chaos.parse("sede:42,sites:compile")
        with pytest.raises(ValueError, match="unknown fault site"):
            chaos.parse("sites:compile|tyop")
        with pytest.raises(ValueError, match="at least one site"):
            chaos.parse("seed:1,rate:0.5")
        with pytest.raises(ValueError, match="rate"):
            chaos.ChaosSchedule(1, 1.5, ["compile"])
        with pytest.raises(ValueError, match="rate"):
            chaos.ChaosSchedule(1, 0.0, ["compile"])

    def test_firings_shaped_for_classifiers(self):
        # a chaos fault must be indistinguishable from a scripted one:
        # the firing arms the site's shaped message, so the downstream
        # classifier sees the kind the site's recovery path keys on
        for site, kind in (("oom", "oom"), ("device", "device_lost"),
                           ("worker", "worker_lost")):
            with chaos.inject(chaos.ChaosSchedule(1, 1.0, [site])):
                with pytest.raises(InjectedFault) as ei:
                    faults.check(site)
            assert error_kind(ei.value) == kind, site

    def test_stop_disarms_pending_firings(self):
        sched = chaos.start(chaos.ChaosSchedule(1, 1.0, ["dispatch"]))
        assert sched.consult("dispatch")  # fires: arms a one-shot budget
        assert faults.active("dispatch") == 1
        chaos.stop()
        assert faults.active("dispatch") == 0, (
            "stop() must disarm fired-but-unconsumed budgets")
        assert chaos.active() is None

    def test_env_knob(self, monkeypatch):
        monkeypatch.setattr(chaos, "_env_armed", False)
        monkeypatch.setenv("TFT_CHAOS", "seed:9,rate:0.5,sites:compile")
        chaos.maybe_start_from_env()
        try:
            sched = chaos.active()
            assert sched is not None
            assert (sched.seed, sched.rate) == (9, 0.5)
            assert sched.sites == ("compile",)
        finally:
            chaos.stop()
        # memoized: a second call with the schedule stopped stays off
        chaos.maybe_start_from_env()
        assert chaos.active() is None

    def test_firings_flight_recorded(self):
        with chaos.inject(chaos.ChaosSchedule(1, 1.0, ["compile"])):
            with pytest.raises(InjectedFault):
                faults.check("compile")
        recs = obs_flight.recent(kind="chaos.fire")
        assert recs, "chaos firing was not flight-recorded"
        assert recs[-1]["site"] == "compile"
        assert recs[-1]["seed"] == 1
        assert recs[-1]["step"] >= 1
        assert tracing.counters.get("chaos.fired") >= 1
        assert tracing.counters.get("chaos.compile.fired") >= 1

    def test_may_fire(self):
        assert not faults.may_fire("compile")
        faults.arm("compile", 1)
        assert faults.may_fire("compile")
        faults.reset("compile")
        assert not faults.may_fire("compile")
        with chaos.inject(chaos.ChaosSchedule(1, 0.01, ["compile"])):
            # named by the schedule: COULD fire, even at a tiny rate
            assert faults.may_fire("compile")
            assert not faults.may_fire("dispatch")
        assert not faults.may_fire("compile")


# -- the fault-site table --------------------------------------------------

class TestFaultSites:
    def test_site_table_matches_literals(self):
        assert tuple(sorted(faults.sites())) == ALL_SITES

    def test_sites_returns_copy(self):
        got = faults.sites()
        got["bogus"] = "nope"
        assert "bogus" not in faults.sites()

    def test_unknown_site_warns_loudly(self):
        before = tracing.counters.get("faults.unknown_sites")
        faults.arm("tyop", 1)
        try:
            assert tracing.counters.get("faults.unknown_sites") == before + 1
        finally:
            faults.reset("tyop")

    @pytest.mark.parametrize("site", [s for s in ALL_SITES if s != "perf"])
    def test_every_site_arms_and_raises(self, site):
        with faults.inject(site):
            with pytest.raises(InjectedFault) as ei:
                faults.check(site)
        kind = error_kind(ei.value)
        expect = {"oom": "oom", "device": "device_lost",
                  "worker": "worker_lost", "disk": "permanent"}
        assert kind == expect.get(site, "transient"), site
        # non-transient sites must never reach the retry loop
        if site in ("oom", "device", "worker", "disk"):
            assert not is_transient(ei.value)
        assert faults.active(site) == 0

    def test_perf_site_sleeps_never_raises(self, monkeypatch):
        monkeypatch.setenv("TFT_FAULT_PERF_S", "0.001")
        with faults.inject("perf"):
            assert faults.slowdown("perf") >= 0.001
            assert faults.slowdown("perf") == 0.0  # budget spent


# -- invariant auditors ----------------------------------------------------

class TestInvariants:
    pytestmark = pytest.mark.invariants

    def test_custom_auditor_always_on_counts(self):
        invariants.register("testaud", lambda point: ["book unbalanced"])
        try:
            found = invariants.audit("test.point")
        finally:
            invariants.unregister("testaud")
        assert found == ["[testaud] book unbalanced"]
        assert tracing.counters.get("invariants.violations") == 1
        assert tracing.counters.get("invariants.testaud.violations") == 1
        recs = obs_flight.recent(kind="invariant.violation")
        assert recs and recs[-1]["auditor"] == "testaud"
        assert recs[-1]["point"] == "test.point"

    def test_strict_mode_raises_classified(self):
        invariants.register("testaud", lambda point: ["book unbalanced"])
        try:
            with invariants.strict():
                assert invariants.strict_mode()
                with pytest.raises(InvariantViolation) as ei:
                    invariants.audit("test.point")
        finally:
            invariants.unregister("testaud")
        assert error_kind(ei.value) == "invariant"
        assert "testaud" in str(ei.value)
        assert not invariants.strict_mode()

    def test_chaos_schedule_implies_strict(self):
        assert not invariants.strict_mode()
        with chaos.inject(chaos.ChaosSchedule(1, 0.01, ["compile"])):
            assert invariants.strict_mode()
        assert not invariants.strict_mode()

    def test_auditor_crash_is_a_violation(self):
        def broken(point):
            raise RuntimeError("auditor bug")
        invariants.register("broken", broken)
        try:
            found = invariants.audit("test.point")
        finally:
            invariants.unregister("broken")
        assert len(found) == 1 and "auditor crashed" in found[0]
        assert tracing.counters.get("invariants.broken.violations") == 1

    def test_disabled_bypass(self, monkeypatch):
        monkeypatch.setenv("TFT_INVARIANTS", "0")
        assert not invariants.enabled()
        invariants.register("testaud", lambda point: ["unbalanced"])
        try:
            assert invariants.audit("test.point") == []
        finally:
            invariants.unregister("testaud")
        assert tracing.counters.get("invariants.violations") == 0
        # check() cold-paths without counting when disabled
        assert invariants.check(False, "testaud", "nope") is False
        assert tracing.counters.get("invariants.violations") == 0

    def test_env_strict_knob(self, monkeypatch):
        monkeypatch.setenv("TFT_INVARIANTS_STRICT", "1")
        assert invariants.strict_mode()
        with pytest.raises(InvariantViolation):
            invariants.violate("testaud", "unbalanced")

    def test_conserve_raises_in_every_mode(self):
        assert not invariants.strict_mode()  # even always-on raises
        with pytest.raises(InvariantViolation) as ei:
            invariants.conserve(10, 8, "test.exchange")
        assert error_kind(ei.value) == "invariant"
        assert tracing.counters.get("invariants.rows.violations") == 1
        invariants.conserve(10, 10, "test.exchange")  # balanced: quiet

    def test_row_ledger_balanced(self):
        with invariants.row_ledger(10, "test.query"):
            invariants.note_filtered(4)
            invariants.note_emitted(6)
        assert tracing.counters.get("invariants.violations") == 0

    def test_row_ledger_unbalanced_counts(self):
        with invariants.row_ledger(10, "test.query"):
            invariants.note_filtered(4)
            invariants.note_emitted(5)  # 10 != 5 + 4
        assert tracing.counters.get("invariants.rows.violations") == 1

    def test_row_ledger_unbalanced_strict_raises(self):
        with pytest.raises(InvariantViolation):
            with invariants.strict():
                with invariants.row_ledger(10, "test.query"):
                    invariants.note_emitted(5)
                    invariants.note_filtered(4)

    def test_row_ledger_taint_skips_check(self):
        with invariants.strict():
            with invariants.row_ledger(10, "test.query"):
                invariants.note_emitted(5)
                invariants.taint_rows("resume restored a prior prefix")
        assert tracing.counters.get("invariants.rows.tainted") == 1
        assert tracing.counters.get("invariants.violations") == 0

    def test_real_filter_query_balances(self):
        # the production row ledger: plan/execute opens it around a
        # row-local fused chain (atom-proven filter + map_rows), filter
        # stages note their masked-out rows, the close balances
        df = tft.frame({"x": np.arange(30.0)}, num_partitions=3)
        with invariants.strict():
            out = df.map_rows(lambda x: {"z": x * 2.0}).filter(
                lambda z: z > 10.0)
            blocks = out.blocks()  # forces the fused chain
        vals = np.concatenate(
            [np.asarray(b.columns["z"]) for b in blocks])
        np.testing.assert_allclose(np.sort(vals),
                                   np.arange(12.0, 60.0, 2.0))
        assert tracing.counters.get("invariants.rows.violations") == 0
        assert tracing.counters.get("invariants.audits") >= 1

    def test_checkpoint_park_cursor_check(self):
        cp = QueryCheckpoint("q-cursor")
        cp.park_stream([np.arange(3.0), np.arange(3.0)], total=1,
                       tag="stream-a")
        assert tracing.counters.get(
            "invariants.checkpoint.violations") == 1

    def test_checkpoint_resume_cursor_cold_paths(self):
        cp = QueryCheckpoint("q-cursor2")
        # an inconsistent cursor (more parked blocks than the stream
        # has) must discard to a cold re-run, never resume dup rows
        cp._parked = ([("junk",), ("junk",)], 1, "stream-a")
        before = tracing.counters.get("serve.checkpoint_discards")
        assert cp.resume_stream(total=1, tag="stream-a") is None
        assert tracing.counters.get(
            "serve.checkpoint_discards") == before + 1
        assert tracing.counters.get(
            "invariants.checkpoint.violations") == 1

    def test_scheduler_quiesce_audit_clean(self):
        with QueryScheduler(workers=1, name="inv-clean") as sched:
            df = tft.frame({"x": np.arange(16.0)}, num_partitions=2)
            fut = sched.submit(df, lambda x: {"z": x * 2.0}, tenant="t")
            fut.result(timeout=60)
            with invariants.strict():
                assert invariants.audit("test.quiesce") == []
        with invariants.strict():
            assert invariants.audit("test.close") == []
        assert tracing.counters.get("invariants.violations") == 0


# -- poison-query quarantine -----------------------------------------------

class TestQuarantine:
    def test_streak_quarantines_and_classifies(self):
        fp = "fp-poison-1"
        boom = ValueError("deterministic plan bug")
        quarantine.note_failure(fp, boom)
        quarantine.note_failure(fp, boom)
        quarantine.check(fp)  # below threshold: admitted
        quarantine.note_failure(fp, boom)  # 3rd: quarantined
        assert tracing.counters.get("serve.quarantines") == 1
        with pytest.raises(QueryQuarantined) as ei:
            quarantine.check(fp)
        assert error_kind(ei.value) == "quarantined"
        assert not is_transient(ei.value)
        assert "unquarantine" in str(ei.value)
        assert tracing.counters.get("serve.quarantined") == 1
        st = quarantine.status()
        assert fp in st["active"]
        assert st["active"][fp]["failures"] == 3
        recs = obs_flight.recent(kind="serve.quarantine")
        assert recs and recs[-1]["fingerprint"] == fp

    def test_success_resets_streak(self):
        fp = "fp-flaky"
        boom = ValueError("boom")
        quarantine.note_failure(fp, boom)
        quarantine.note_failure(fp, boom)
        quarantine.note_success(fp)
        quarantine.note_failure(fp, boom)
        quarantine.note_failure(fp, boom)
        quarantine.check(fp)  # never hit 3 consecutive: still admitted
        assert quarantine.status()["active"] == {}

    def test_unquarantine_lifts(self):
        boom = ValueError("boom")
        for fp in ("fp-a", "fp-b"):
            for _ in range(3):
                quarantine.note_failure(fp, boom)
        assert len(quarantine.status()["active"]) == 2
        assert tft.unquarantine("fp-a") == 1
        quarantine.check("fp-a")  # admitted again
        with pytest.raises(QueryQuarantined):
            quarantine.check("fp-b")
        assert tft.unquarantine() == 1  # lift everything
        quarantine.check("fp-b")
        assert tracing.counters.get("serve.unquarantined") == 2
        assert tft.quarantine_status()["active"] == {}

    def test_ttl_expires_into_one_probe(self, monkeypatch):
        monkeypatch.setenv("TFT_QUARANTINE_TTL_S", "0.05")
        fp = "fp-ttl"
        boom = ValueError("boom")
        for _ in range(3):
            quarantine.note_failure(fp, boom)
        with pytest.raises(QueryQuarantined):
            quarantine.check(fp)
        time.sleep(0.08)
        quarantine.check(fp)  # the TTL expired: ONE probe admission
        assert tracing.counters.get("serve.quarantine_expired") == 1
        # a still-poisonous plan re-quarantines on the probe's failure
        quarantine.note_failure(fp, boom)
        with pytest.raises(QueryQuarantined):
            quarantine.check(fp)

    def test_disabled_by_knob(self, monkeypatch):
        monkeypatch.setenv("TFT_QUARANTINE_AFTER", "0")
        fp = "fp-off"
        for _ in range(10):
            quarantine.note_failure(fp, ValueError("boom"))
        quarantine.check(fp)
        assert quarantine.status()["active"] == {}

    def test_scheduler_end_to_end(self):
        # a deterministically-failing plan: each run hits a PERMANENT
        # (non-transient) fault at dispatch — fail_n=2 covers the async
        # dispatch AND the pipeline's synchronous re-run of the block,
        # so the classified permanent error surfaces out of the query
        df = tft.frame({"x": np.arange(16.0)}, num_partitions=2)
        with QueryScheduler(quotas={"t": TenantQuota()}, workers=1,
                            name="quar-e2e") as sched:
            for _ in range(3):
                faults.arm("dispatch", 2,
                           message="injected permanent plan bug",
                           transient=False)
                fut = sched.submit(df, _benign_fetches, tenant="t")
                with pytest.raises(InjectedFault):
                    fut.result(timeout=60)
                faults.reset("dispatch")
            # the 4th submission fast-rejects before touching a queue
            with pytest.raises(QueryQuarantined) as ei:
                sched.submit(df, _benign_fetches, tenant="t")
            assert error_kind(ei.value) == "quarantined"
            assert sched.snapshot()["t"]["quarantined"] == 1
            # surfaced in the operator reports
            report = serve.serve_report(scheduler=sched)
            assert "QUARANTINE" in report
            snap = tft.health()
            assert snap["quarantine"]["active"]
            assert any("quarantine" in w for w in snap["warnings"])
            assert "quarantine:" in tft.doctor()
            # lifting re-admits — and with the fault gone the same
            # plan completes on its own merits
            assert tft.unquarantine() == 1
            fut = sched.submit(df, _benign_fetches, tenant="t")
            out = fut.result(timeout=60)
            vals = np.concatenate([np.asarray(b.columns["z"])
                                   for b in out.blocks()])
            np.testing.assert_allclose(np.sort(vals),
                                       np.arange(16.0) * 2.0)

    def test_none_fingerprint_never_quarantined(self):
        for _ in range(10):
            quarantine.note_failure(None, ValueError("boom"))
        quarantine.check(None)
        assert quarantine.status()["active"] == {}


def _benign_fetches(x):
    # module-level so the plan fingerprint is stable across submissions
    return {"z": x * 2.0}


# -- persist artifact checksums --------------------------------------------

class TestPersistChecksums:
    @pytest.fixture(autouse=True)
    def _tier(self, tmp_path):
        prev = _persist.configure(str(tmp_path))
        yield
        _persist.configure(prev)

    def _result_path(self, fp):
        d = os.path.join(_persist.root(), "results")
        names = os.listdir(d)
        assert len(names) == 1
        return os.path.join(d, names[0])

    def test_roundtrip_bit_identical(self):
        blocks = [{"x": np.arange(16.0)}]
        assert _persist.save_result("fp-rt", blocks)
        got = _persist.load_result("fp-rt")
        assert got is not None
        np.testing.assert_array_equal(np.asarray(got[0]["x"]),
                                      blocks[0]["x"])
        assert tracing.counters.get("memory.persist_corrupt") == 0

    def test_bit_rot_detected_and_cold(self):
        # single-bit rot inside a numpy buffer still unpickles — the
        # checksum is the ONLY thing standing between the serving tier
        # and a silently-wrong warm hit
        arr = np.arange(16.0)
        _persist.save_result("fp-rot", [{"x": arr}])
        path = self._result_path("fp-rot")
        with open(path, "rb") as f:
            data = bytearray(f.read())
        # flip one bit INSIDE the serialized float buffer: the file
        # still unpickles cleanly, just to wrong values
        off = data.find(arr.tobytes())
        assert off > 0, "float buffer not found in the artifact"
        data[off + 40] ^= 0x01
        with open(path, "wb") as f:
            f.write(bytes(data))
        # the rotten payload must still be loadable by pickle alone,
        # or this test would only prove what unpickling already catches
        payload = bytes(data[len(_persist._MAGIC)
                             + _persist._DIGEST_LEN:])
        assert pickle.loads(payload) is not None
        assert _persist.load_result("fp-rot") is None
        assert tracing.counters.get("memory.persist_corrupt") == 1
        assert not os.path.exists(path), "corrupt artifact not removed"
        recs = obs_flight.recent(kind="memory.persist_corrupt")
        assert recs and "checksum" in recs[-1]["why"]

    def test_missing_header_cold(self):
        _persist.save_result("fp-hdr", [{"x": np.arange(4.0)}])
        path = self._result_path("fp-hdr")
        with open(path, "wb") as f:
            f.write(b"not a framed artifact")
        assert _persist.load_result("fp-hdr") is None
        assert tracing.counters.get("memory.persist_corrupt") == 1
        assert not os.path.exists(path)

    def test_checksum_ok_unpickle_fails_is_skew_not_rot(self):
        # a valid checksum over an unloadable payload means version/
        # environment skew, not rot: the read_errors path, NOT corrupt
        _persist.save_result("fp-skew", [{"x": np.arange(4.0)}])
        path = self._result_path("fp-skew")
        with open(path, "wb") as f:
            f.write(_persist._pack(b"not-a-pickle"))
        assert _persist.load_result("fp-skew") is None
        assert tracing.counters.get("memory.persist_corrupt") == 0
        assert tracing.counters.get("persist.read_errors") == 1

    def test_disk_fault_read_failure_mode(self):
        _persist.save_result("fp-io", [{"x": np.arange(4.0)}])
        with faults.inject("disk"):
            assert _persist.load_result("fp-io") is None
        assert tracing.counters.get("persist.read_errors") == 1
        assert tracing.counters.get("memory.persist_corrupt") == 0

    def test_disk_fault_corruption_mode(self):
        _persist.save_result("fp-crpt", [{"x": np.arange(4.0)}])
        with faults.inject("disk", message="injected corrupt artifact"):
            assert _persist.load_result("fp-crpt") is None
        assert tracing.counters.get("memory.persist_corrupt") == 1
        assert tracing.counters.get("persist.read_errors") == 0

    def test_checkpoint_artifacts_framed_too(self):
        cp = QueryCheckpoint("q-framed")
        cp.park_stream([np.arange(8.0)], total=2, tag="s")
        loaded = _persist.load_checkpoint("q-framed")
        assert loaded is not None
        d = os.path.join(_persist.root(), "checkpoints")
        path = os.path.join(d, os.listdir(d)[0])
        with open(path, "rb") as f:
            assert f.read(len(_persist._MAGIC)) == _persist._MAGIC


# -- conformance meta-tests ------------------------------------------------

def _classified_kinds():
    from tensorframes_tpu.resilience import classify
    kinds = {"device_lost", "worker_lost", "oom", "transient",
             "permanent"}
    for obj in vars(classify).values():
        if isinstance(obj, type) and issubclass(obj, BaseException):
            kind = getattr(obj, "kind", None)
            if kind:
                kinds.add(kind)
    return kinds


class TestConformance:
    DOCS = os.path.join(os.path.dirname(__file__), "..", "docs",
                        "resilience.md")

    def test_every_error_kind_documented(self):
        with open(self.DOCS) as f:
            text = f.read()
        missing = [k for k in sorted(_classified_kinds())
                   if f"`{k}`" not in text]
        assert not missing, (
            f"classified error kind(s) {missing} have no "
            f"docs/resilience.md entry — every kind the classifier "
            f"can emit needs a documented degradation row")

    def test_every_site_documented(self):
        with open(self.DOCS) as f:
            text = f.read()
        missing = [s for s in sorted(faults.sites()) if s not in text]
        assert not missing, (
            f"fault site(s) {missing} missing from docs/resilience.md "
            f"— the site table and the docs must not drift")

    def test_every_site_driven_by_a_test(self):
        tests_dir = os.path.dirname(__file__)
        corpus = ""
        for name in os.listdir(tests_dir):
            if name.endswith(".py"):
                with open(os.path.join(tests_dir, name)) as f:
                    corpus += f.read()
        undriven = [s for s in sorted(faults.sites())
                    if f'"{s}"' not in corpus and f"'{s}'" not in corpus]
        assert not undriven, (
            f"fault site(s) {undriven} never appear in any test — "
            f"every armed-able site must be driven by >=1 tier-1 test")


# -- the bounded acceptance drill ------------------------------------------

@pytest.mark.invariants
def test_chaos_acceptance_drill(tmp_path):
    """The mixed workload under a seeded >=3-site schedule: bit-identity
    vs the fault-free run, zero leaks, every failure classified, exact
    per-site seed replay. seed=11/rate=0.3 is chosen because it fires
    all four default sites within two rounds (the drill itself asserts
    the rest of the contract and raises on any breach)."""
    report = chaos_soak.run_drill(seed=11, rate=0.3, rounds=2,
                                  persist_dir=str(tmp_path))
    fired_sites = {site for site, _ in report["firings"]}
    assert {"device", "worker", "disk"} <= fired_sites, (
        f"drill must fire the device+worker+disk minimum; "
        f"got {sorted(fired_sites)}")
    assert report["fired"] >= 3
    assert tracing.counters.get("chaos.fired") >= report["fired"]


@pytest.mark.slow
@pytest.mark.invariants
def test_chaos_soak_slow(tmp_path):
    """More rounds of the same drill (the standalone soak's code path),
    at a different seed so the suite covers two schedules."""
    report = chaos_soak.run_drill(seed=7, rate=0.25, rounds=6,
                                  persist_dir=str(tmp_path))
    assert report["fired"] >= 3
