"""Closed-loop SLO observatory (ISSUE 18 tentpole).

Pins: sketch quantile accuracy vs numpy under fuzzed distributions and
merge equivalence (the DDSketch contract); burn-rate window goldens on
an injected clock — alerts fire during an induced storm and CLEAR once
it passes; the idle economy's fairness invariants (weighted time split,
greedy cannot starve the meek, the starvation bound guarantees
liveness); exactly-one SLO accounting per scheduler entry including
errors, sheds and caller-held (http) samples.
"""

import math

import numpy as np
import pytest

from greptimedb_tpu.serving.idle import IdleEconomy
from greptimedb_tpu.serving.slo import (
    LatencySketch, SloEngine, _MIN_S, sketch_params,
)

ALPHA = 0.01
PARAMS = sketch_params(ALPHA)


def _rank_quantile(vals, q):
    """The rank-based sample quantile the sketch estimates (DDSketch
    guarantees relative error alpha against THIS, not interpolation)."""
    s = np.sort(vals)
    return float(s[max(1, math.ceil(q * len(s))) - 1])


class TestSketchAccuracy:
    DISTS = (
        ("lognormal", lambda r, n: r.lognormal(-3.0, 1.0, n)),
        ("uniform", lambda r, n: r.uniform(0.001, 2.0, n)),
        ("exponential", lambda r, n: r.exponential(0.05, n)),
    )

    def test_quantiles_within_relative_error_fuzzed(self):
        for seed in (7, 21, 99):
            rng = np.random.default_rng(seed)
            for name, gen in self.DISTS:
                vals = np.clip(gen(rng, 5000), 2e-4, 5e3)
                sk = LatencySketch(PARAMS)
                for v in vals:
                    sk.observe(float(v))
                assert sk.n == 5000
                for q in (0.50, 0.90, 0.99, 0.999):
                    est = sk.quantile(q)
                    true = _rank_quantile(vals, q)
                    rel = abs(est - true) / true
                    assert rel <= 2 * ALPHA, (name, seed, q, est, true)

    def test_merge_equals_observing_everything(self):
        rng = np.random.default_rng(13)
        vals = np.clip(rng.lognormal(-2.5, 1.2, 3000), 2e-4, 5e3)
        whole = LatencySketch(PARAMS)
        parts = [LatencySketch(PARAMS) for _ in range(3)]
        for i, v in enumerate(vals):
            whole.observe(float(v))
            parts[i % 3].observe(float(v))
        merged = LatencySketch(PARAMS)
        for p in parts:
            merged.merge(p)
        assert merged.counts == whole.counts
        assert merged.n == whole.n
        assert merged.sum == pytest.approx(whole.sum)
        for q in (0.5, 0.99):
            assert merged.quantile(q) == whole.quantile(q)

    def test_range_clamps_never_raise(self):
        sk = LatencySketch(PARAMS)
        sk.observe(0.0)        # sub-minimum → bucket 0
        sk.observe(1e-9)
        sk.observe(1e9)        # absurd → top bucket, no index error
        assert sk.n == 3
        assert sk.quantile(0.0) == _MIN_S
        assert sk.quantile(1.0) >= 1e3

    def test_empty_sketch_has_no_quantile(self):
        assert LatencySketch(PARAMS).quantile(0.5) is None


def _engine(monkeypatch, **env):
    """SloEngine on an injected, manually-advanced clock."""
    defaults = {
        "GREPTIME_SLO_MIN_SAMPLES": "10",
        "GREPTIME_SLO_OBJECTIVE": "0.999",
        "GREPTIME_SLO_THRESHOLD_MS": "500",
    }
    defaults.update(env)
    for k, v in defaults.items():
        monkeypatch.setenv(k, str(v))
    t = [10_000.0]
    eng = SloEngine(clock=lambda: t[0])
    return eng, t


class TestBurnWindows:
    KEY = ("default", "interactive", "http")

    def _record(self, eng, n, bad=0, seconds=0.01):
        for _ in range(n - bad):
            eng.record(*self.KEY, seconds)
        for _ in range(bad):
            eng.record(*self.KEY, 10.0)  # >> threshold: breach

    def test_goldens(self, monkeypatch):
        eng, t = _engine(monkeypatch)
        # no traffic: burn 0, full budget
        assert eng.burn_rate(self.KEY, "5m") == 0.0
        assert eng.budget_remaining(self.KEY) == 1.0
        # 1000 clean: still no burn
        self._record(eng, 1000)
        assert eng.burn_rate(self.KEY, "5m") == 0.0
        assert eng.budget_remaining(self.KEY) == 1.0
        # 5 breaches in 1005: ratio .004975 over budget .001 → burn ~4.98
        self._record(eng, 5, bad=5)
        for w in ("5m", "30m", "1h", "6h"):
            assert eng.burn_rate(self.KEY, w) == pytest.approx(
                (5 / 1005) / 0.001, rel=1e-6), w
        assert eng.budget_remaining(self.KEY) == pytest.approx(
            max(0.0, 1.0 - (5 / 1005) / 0.001))

    def test_short_window_forgets_the_storm(self, monkeypatch):
        eng, t = _engine(monkeypatch)
        self._record(eng, 100, bad=50)
        assert eng.burn_rate(self.KEY, "5m") > 0
        t[0] += 6 * 60.0  # 6 slots later: outside 5m, inside 1h
        assert eng.burn_rate(self.KEY, "5m") == 0.0
        assert eng.burn_rate(self.KEY, "1h") > 0
        t[0] += 60 * 60.0  # and eventually outside 1h, inside 6h
        assert eng.burn_rate(self.KEY, "1h") == 0.0
        assert eng.burn_rate(self.KEY, "6h") > 0

    def test_alert_fires_during_storm_and_clears(self, monkeypatch):
        eng, t = _engine(monkeypatch)
        # storm: 5% breaches → burn 50 >> fast threshold 14.4 on BOTH
        # fast-pair windows, with ample samples
        self._record(eng, 600, bad=30)
        alerts = eng.alerts()
        severities = {a["severity"] for a in alerts}
        assert "fast" in severities
        assert eng.fast_burn_active()
        # storm passes: clean traffic refills the short window; the fast
        # pair needs the short window STILL burning, so it clears even
        # though the 1h window remembers the storm
        t[0] += 6 * 60.0
        self._record(eng, 600)
        t[0] += 2.0  # invalidate the 1s alert cache
        assert eng.burn_rate(self.KEY, "1h") > 14.4
        assert not eng.fast_burn_active()

    def test_min_samples_gates_thin_traffic(self, monkeypatch):
        eng, t = _engine(monkeypatch)
        # 5 queries, ALL breaches — a 3am test database, not a storm
        self._record(eng, 5, bad=5)
        assert eng.burn_rate(self.KEY, "5m") > 900  # ratio says burning
        assert eng.alerts() == []                    # evidence says no
        assert not eng.fast_burn_active()

    def test_tenant_overrides_and_class_factors(self, monkeypatch):
        eng, _t = _engine(
            monkeypatch, GREPTIME_SLO_OVERRIDES="acme=250:0.99, bad==,x")
        assert eng.objective_for("acme", "interactive") == (0.25, 0.99)
        assert eng.objective_for("acme", "background") == (
            pytest.approx(5.0), 0.99)
        assert eng.objective_for("other", "interactive") == (0.5, 0.999)
        # runtime override (the soak's induced storm)
        eng.set_objective("other", 1.0)
        thr, obj = eng.objective_for("other", "interactive")
        assert thr == pytest.approx(0.001) and obj == 0.999

    def test_adaptive_timeout_needs_evidence(self, monkeypatch):
        eng, _t = _engine(monkeypatch)
        assert eng.adaptive_timeout_s("interactive") is None
        for _ in range(300):
            eng.record("default", "interactive", "http", 0.05)
        # p99 ~50ms × 8 « floor → the generous floor wins
        assert eng.adaptive_timeout_s("interactive") == 30.0
        for _ in range(300):
            eng.record("default", "normal", "http", 10.0)
        # p99 ~10s × 8 = 80s > floor
        assert eng.adaptive_timeout_s("normal") == pytest.approx(
            80.0, rel=0.05)

    def test_admit_background_scales_with_budget(self, monkeypatch):
        eng, _t = _engine(monkeypatch, GREPTIME_SLO_ADMIT_MS="60000")
        # full budget: the whole allowance
        ok, allowance = eng.admit_background(50_000)
        assert ok and allowance == 60_000
        # burned-out interactive budget: allowance collapses; unknown
        # (0-cost) work is still admitted
        self._record(eng, 100, bad=50)
        ok, allowance = eng.admit_background(50_000)
        assert not ok and allowance == 0.0
        assert eng.admit_background(0)[0]

    def test_status_rows_render_every_key(self, monkeypatch):
        eng, _t = _engine(monkeypatch)
        eng.record("a", "interactive", "http", 0.01)
        eng.record("b", "background", "sql", 2.0)
        rows = eng.status_rows()
        assert [(r["tenant"], r["class"]) for r in rows] == [
            ("a", "interactive"), ("b", "background")]
        assert rows[0]["total"] == 1 and rows[0]["breached"] == 0
        assert rows[1]["p50_ms"] == pytest.approx(2000.0, rel=2 * ALPHA)
        assert eng.total_recorded() == 2


class TestIdleEconomy:
    def _eco(self, monkeypatch, t, **env):
        defaults = {"GREPTIME_IDLE_QUANTUM_MS": "20",
                    "GREPTIME_IDLE_STARVE_TICKS": "64"}
        defaults.update(env)
        for k, v in defaults.items():
            monkeypatch.setenv(k, str(v))
        return IdleEconomy(clock=lambda: t[0])

    def test_weighted_time_split_deterministic(self, monkeypatch):
        t = [0.0]
        eco = self._eco(monkeypatch, t)
        ledger = {"a": 0.040, "b": 0.020}  # simulated tick durations

        def consumer(name):
            def fn():
                t[0] += ledger[name]
                return True
            return fn

        eco.register(consumer("a"), name="a", weight=2.0)
        eco.register(consumer("b"), name="b", weight=1.0)
        for _ in range(60):
            assert eco.tick() is True
        by = {c["name"]: c for c in eco.consumers()}
        # deterministic DRR schedule (a,a,b repeating): grants follow
        # the 2:1 weights exactly because each grant of a costs its
        # weight in quanta (40 ms / 20 ms quantum = 2)
        assert by["a"]["granted"] == 40 and by["b"]["granted"] == 20
        assert by["a"]["elapsed_ms"] == pytest.approx(4 * by["b"]["elapsed_ms"])
        assert by["a"]["starved"] == 0 and by["b"]["starved"] == 0

    def test_greedy_cannot_starve_the_meek(self, monkeypatch):
        t = [0.0]
        eco = self._eco(monkeypatch, t)

        def greedy():
            t[0] += 1.0  # 50 quanta per tick
            return True

        def meek():
            t[0] += 0.001
            return True

        eco.register(greedy, name="greedy", weight=1.0)
        eco.register(meek, name="meek", weight=1.0)
        for _ in range(80):
            eco.tick()
        by = {c["name"]: c for c in eco.consumers()}
        # the deficit debit makes every greedy grant cost ~50 future
        # grants: the meek consumer runs far more often, no starvation
        # bound needed
        assert by["meek"]["granted"] > 5 * by["greedy"]["granted"]
        assert by["meek"]["starved"] == 0

    def test_starvation_bound_guarantees_liveness(self, monkeypatch):
        from greptimedb_tpu.utils.telemetry import REGISTRY

        t = [0.0]
        eco = self._eco(monkeypatch, t, GREPTIME_IDLE_STARVE_TICKS="5")

        def fn():
            return True

        eco.register(fn, name="rich", weight=1.0)
        eco.register(lambda: True, name="zero", weight=0.0)
        for _ in range(20):
            eco.tick()
        by = {c["name"]: c for c in eco.consumers()}
        # weight 0 accrues nothing — only the bound ever grants it
        assert by["zero"]["granted"] >= 2
        assert by["zero"]["starved"] == by["zero"]["granted"]
        assert (REGISTRY.value("greptime_idle_starved_total",
                               ("zero",)) or 0) >= 2

    def test_drain_unhook_and_resurrect(self, monkeypatch):
        t = [0.0]
        eco = self._eco(monkeypatch, t)
        calls = []

        def once():
            calls.append(1)
            return False  # drained after one grant

        name = eco.register(once, name="once")
        assert eco.tick() is False  # all drained → unhook contract
        assert len(calls) == 1
        # re-registering the SAME callable revives the ledger entry
        assert eco.register(once) == name
        assert [c["name"] for c in eco.consumers()] == [name]
        assert eco.tick() is False
        assert len(calls) == 2

    def test_fast_burn_throttles_every_consumer(self, monkeypatch):
        t = [0.0]

        class FakeSlo:
            burning = True

            def fast_burn_active(self):
                return self.burning

        slo = FakeSlo()
        for k, v in (("GREPTIME_IDLE_QUANTUM_MS", "20"),
                     ("GREPTIME_IDLE_STARVE_TICKS", "64")):
            monkeypatch.setenv(k, v)
        eco = IdleEconomy(slo=slo, clock=lambda: t[0])
        granted = []
        eco.register(lambda: granted.append(1) or True, name="w")
        for _ in range(5):
            assert eco.tick() is True  # stays hooked, grants NOTHING
        assert granted == [] and eco.throttled == 5
        slo.burning = False
        eco.tick()
        assert granted == [1]

    def test_exceptions_drain_not_kill(self, monkeypatch):
        t = [0.0]
        eco = self._eco(monkeypatch, t)

        def boom():
            raise RuntimeError("consumer bug")

        eco.register(boom, name="boom")
        eco.register(lambda: True, name="ok")
        assert eco.tick() in (True, False)
        assert eco.tick() is True  # 'ok' still lives
        by = {c["name"]: c for c in eco.consumers()}
        assert by["boom"]["drained"]


class TestSchedulerAccounting:
    """Exactly-one sketch sample per scheduler entry — success, error,
    shed and caller-held paths."""

    @pytest.fixture()
    def db(self):
        from greptimedb_tpu.standalone import GreptimeDB

        d = GreptimeDB()
        d.sql("CREATE TABLE cpu (h STRING, ts TIMESTAMP TIME INDEX, "
              "v DOUBLE, PRIMARY KEY(h))")
        d.sql("INSERT INTO cpu VALUES ('a', 1000, 1.0), ('a', 2000, 2.0)")
        yield d
        d.close()

    def test_every_submit_lands_in_exactly_one_sketch(self, db):
        base = db.slo.total_recorded()
        n_ok, n_err = 12, 3
        for i in range(n_ok):
            db.scheduler.submit(f"SELECT count(v) FROM cpu WHERE v > {i}")
        for _ in range(n_err):
            with pytest.raises(Exception):
                db.scheduler.submit("SELECT definitely_no_such_col "
                                    "FROM cpu")
        assert db.slo.total_recorded() == base + n_ok + n_err

    def test_held_sample_defers_to_the_caller(self, db):
        base = db.slo.total_recorded()
        hold = []
        db.scheduler.submit("SELECT count(v) FROM cpu", slo_hold=hold)
        # not yet recorded: serialization is still ahead
        assert db.slo.total_recorded() == base
        assert len(hold) == 1
        db.scheduler.record_held(hold)
        assert db.slo.total_recorded() == base + 1
        assert hold == []  # drained: double-record impossible

    def test_error_with_hold_records_immediately(self, db):
        base = db.slo.total_recorded()
        hold = []
        with pytest.raises(Exception):
            db.scheduler.submit("SELECT nope FROM cpu", slo_hold=hold)
        # errored entries never defer (there is no response to time)
        assert db.slo.total_recorded() == base + 1
        db.scheduler.record_held(hold)  # empty: no double count
        assert db.slo.total_recorded() == base + 1

    def test_fast_burn_rejects_background_admission(self, db):
        from greptimedb_tpu.errors import ResourcesExhausted
        from greptimedb_tpu.utils.telemetry import REGISTRY

        db.slo.fast_burn_active = lambda: True
        try:
            with pytest.raises(ResourcesExhausted):
                db.scheduler.submit("SELECT count(v) FROM cpu",
                                    priority="background")
            assert (REGISTRY.value("greptime_scheduler_rejected_total",
                                   ("default", "slo_budget")) or 0) >= 1
        finally:
            del db.slo.fast_burn_active

    def test_slo_status_information_schema(self, db):
        db.scheduler.submit("SELECT count(v) FROM cpu")
        res = db.sql("SELECT tenant, class, protocol, total "
                     "FROM information_schema.slo_status")
        assert res.rows, "slo_status must render recorded keys"
        cols = dict(zip(res.column_names, zip(*res.rows)))
        assert "default" in cols["tenant"]
