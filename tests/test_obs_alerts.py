"""Alerting-plane tests: rule registry contracts, the alert state machine,
burn-rate slow-window gating, threshold monotonicity, and the end-to-end
determinism contract (smoke stays incident-free; fault-storm opens
incidents; incidents.jsonl is byte-identical across same-seed runs and
across tick engines; the report/v5 "incidents" section validates)."""
import json

import pytest

from repro.cluster.control import check_schema, run_scenario
from repro.cluster.scenario import scenario_by_name
from repro.obs import (ALERTS_SCHEMA, AlertEngine, AlertRule, JsonlWriter,
                       ObsConfig, alert_rules_available, default_alert_rules,
                       incidents_open_at, read_incidents,
                       register_alert_rule, resolve_alert_rules)


def _engine(rules, window_s=600.0):
    return AlertEngine(JsonlWriter(None), rules, window_s=window_s)


def _fleet(series, rule, eng):
    for i, v in enumerate(series):
        eng.on_window(600.0 * (i + 1), {"fleet": {rule.signal: v}})


# ---------------------------------------------------------------- registry
def test_rule_validation_rejects_bad_fields():
    with pytest.raises(ValueError):
        AlertRule("x", signal="s", scope="galaxy", threshold=1.0)
    with pytest.raises(ValueError):
        AlertRule("x", signal="s", scope="fleet", threshold=1.0,
                  severity="whisper")
    with pytest.raises(ValueError):
        AlertRule("x", signal="s", scope="fleet", threshold=1.0,
                  kind="vibes")
    with pytest.raises(ValueError):
        AlertRule("x", signal="s", scope="fleet", threshold=1.0,
                  for_windows=0)


def test_registry_rejects_duplicates_and_unknown_names():
    assert "error-rate" in alert_rules_available()
    with pytest.raises(ValueError, match="already registered"):
        register_alert_rule(AlertRule(
            "error-rate", signal="s", scope="fleet", threshold=1.0))
    with pytest.raises(ValueError, match="unknown alert rule"):
        resolve_alert_rules(["no-such-rule"])
    sub = resolve_alert_rules(["online-slowdown", "error-rate"])
    assert [r.name for r in sub] == ["error-rate", "online-slowdown"]


def test_default_catalog_sorted_and_engine_rejects_dup_rules():
    names = [r.name for r in default_alert_rules()]
    assert names == sorted(names)
    r = AlertRule("dup", signal="s", scope="fleet", threshold=1.0)
    with pytest.raises(ValueError, match="duplicate"):
        _engine((r, r))


# ----------------------------------------------------------- state machine
def test_lifecycle_pending_firing_resolved():
    rule = AlertRule("r", signal="s", scope="fleet", threshold=10.0,
                     for_windows=2, clear_windows=2)
    eng = _engine((rule,))
    _fleet([5, 20, 30, 20, 5, 5, 5], rule, eng)
    assert len(eng.incidents) == 1
    inc = eng.incidents[0]
    # pending at the first breach, firing (incident opens) at the second
    assert inc.opened_t == 600.0 * 3
    # two clean windows resolve it
    assert inc.resolved_t == 600.0 * 6
    assert inc.windows == 3 and inc.peak == 30.0
    assert inc.target == "fleet" and eng.open_count() == 0
    # transitions: pending, firing, resolved
    assert eng.transitions == 3 and eng.breach_windows == 3


def test_single_clean_window_does_not_resolve_with_clear_2():
    rule = AlertRule("r", signal="s", scope="fleet", threshold=10.0,
                     clear_windows=2)
    eng = _engine((rule,))
    _fleet([20, 5, 20, 5, 5], rule, eng)
    # the lone clean window between breaches never resolves the incident
    assert len(eng.incidents) == 1
    assert eng.incidents[0].resolved_t == 600.0 * 5


def test_pending_run_shorter_than_for_windows_never_fires():
    rule = AlertRule("r", signal="s", scope="fleet", threshold=10.0,
                     for_windows=3)
    eng = _engine((rule,))
    _fleet([20, 20, 5, 20, 20, 5], rule, eng)
    assert eng.incidents == [] and eng.breach_windows == 4


def test_burn_rate_requires_slow_window_mean():
    rule = AlertRule("r", signal="burn", scope="service", threshold=10.0,
                     kind="burn_rate", slow_windows=3, slow_threshold=5.0)
    eng = _engine((rule,))
    # spike with a cold trailing mean: (0 + 0 + 15)/3 = 5.0, not > 5.0
    for i, v in enumerate([0.0, 0.0, 15.0]):
        eng.on_window(600.0 * (i + 1), {"service": {"svc": {"burn": v}}})
    assert eng.incidents == [] and eng.breach_windows == 0
    # sustained burn pushes the mean over the gate -> fires
    eng.on_window(600.0 * 4, {"service": {"svc": {"burn": 15.0}}})
    assert len(eng.incidents) == 1
    assert eng.incidents[0].target == "svc"


def test_targets_discovered_per_pool_and_sorted():
    rule = AlertRule("r", signal="s", scope="pool", threshold=10.0)
    eng = _engine((rule,))
    eng.on_window(600.0, {"pool": {"b": {"s": 20.0}, "a": {"s": 30.0}}})
    assert [i.target for i in eng.incidents] == ["a", "b"]


def test_incident_open_at_half_open_interval():
    rule = AlertRule("r", signal="s", scope="fleet", threshold=10.0)
    eng = _engine((rule,))
    _fleet([20, 5], rule, eng)
    inc = eng.incidents[0]
    assert inc.open_at(600.0) and inc.open_at(900.0)
    assert not inc.open_at(599.0) and not inc.open_at(1200.0)
    assert incidents_open_at([inc], 700.0) == [inc]


# ------------------------------------------------------------ monotonicity
def test_breach_windows_monotone_in_threshold():
    """Strict `>` breaching: raising the threshold can only shrink the set
    of breaching windows (the incident *count* is not monotone — a higher
    threshold can split one long incident into two — so the property pins
    breach_windows)."""
    series = [0.0, 3.0, 7.0, 7.0, 2.0, 9.0, 9.0, 9.0, 1.0, 5.0, 8.0, 0.0]
    prev = None
    for threshold in (0.0, 2.0, 4.0, 6.0, 8.0, 10.0):
        rule = AlertRule("r", signal="s", scope="fleet",
                         threshold=threshold, for_windows=2)
        eng = _engine((rule,))
        _fleet(series, rule, eng)
        if prev is not None:
            assert eng.breach_windows <= prev
        prev = eng.breach_windows
    assert prev == 0  # threshold above the series -> no breaches at all


# ------------------------------------------------------------- end to end
def _run(tmp_path, tag, scenario, *, engine=None, rules=(), **overrides):
    out = tmp_path / f"incidents{tag}.jsonl"
    report = run_scenario(
        scenario_by_name(scenario), engine=engine,
        obs=ObsConfig(alerts_out=str(out), alert_rules=rules,
                      metrics_every_s=600.0),
        **overrides)
    return report, out.read_bytes()


def test_smoke_seed0_is_incident_free(tmp_path):
    """The quiet CI scenario pages nobody: no error propagates and the
    online-slowdown, SLO-burn and chaos rules stay quiet.

    The one rule that may open here is the ticket-level
    device-disable-spike, and only on the 16-device a10 pool.  A 600 s
    window of that pool is 2.67 device-hours, so each SysMonitor
    healthy -> unhealthy/overlimit transition counts 375 per 1k
    device-hours and two in one window cross the 700 threshold.  How many
    occur follows which devices host offline jobs, which follows the
    predictor's jax.random initialization.  That stream changed when JAX
    made ``jax_threefry_partitionable`` the default: seed 0 now co-locates
    four a10 devices where it co-located three, and their protective
    evictions put one window at 2250.  The rule is working as specified;
    the scenario is too small for its rate to be quiet by construction."""
    report, _ = _run(tmp_path, "s", "smoke", seed=0)
    inc = report["incidents"]
    assert "page" not in inc["by_severity"]
    assert set(inc["by_rule"]) <= {"device-disable-spike"}
    assert {e["target"] for e in inc["timeline"]} <= {"a10"}
    assert inc["total"] <= 1
    assert inc["windows"] > 0


def test_fault_storm_opens_incidents_and_is_byte_identical(tmp_path):
    report1, raw1 = _run(tmp_path, "1", "fault-storm", seed=0, hours=3.0)
    _report2, raw2 = _run(tmp_path, "2", "fault-storm", seed=0, hours=3.0)
    assert raw1 == raw2
    inc = report1["incidents"]
    assert inc["total"] >= 1
    assert inc["by_rule"]  # attributed to at least one named rule
    # the stream digest in the report matches the file bytes
    import hashlib
    assert hashlib.sha256(raw1).hexdigest() == inc["digest"]
    # the persisted timeline reads back (canonical rounding on both sides)
    from repro.obs import canonical_json
    timeline = read_incidents(str(tmp_path / "incidents1.jsonl"))
    assert (canonical_json([i.row() for i in timeline])
            == canonical_json(inc["timeline"]))


def test_incidents_byte_identical_across_engines(tmp_path):
    _, raw_np = _run(tmp_path, "n", "fault-storm", seed=0, hours=2.0,
                     engine="numpy")
    _, raw_xla = _run(tmp_path, "x", "fault-storm", seed=0, hours=2.0,
                      engine="xla")
    assert raw_np == raw_xla


def test_report_v5_schema_with_and_without_alerts(tmp_path):
    report, _ = _run(tmp_path, "v", "smoke", seed=0)
    assert report["schema"].endswith("/v5")
    assert report["incidents"]["schema"] == ALERTS_SCHEMA
    assert check_schema(report) == []
    plain = run_scenario(scenario_by_name("smoke"), seed=0)
    assert plain["incidents"] is None
    assert check_schema(plain) == []


def test_rule_subset_only_evaluates_named_rules(tmp_path):
    report, raw = _run(tmp_path, "sub", "fault-storm", seed=0, hours=3.0,
                       rules=("error-rate",))
    inc = report["incidents"]
    assert inc["rules"] == ["error-rate"]
    assert set(inc["by_rule"]) <= {"error-rate"}
    header = json.loads(raw.splitlines()[0])
    assert header["rules"] == ["error-rate"]


def test_alerting_never_changes_metrics_bytes(tmp_path):
    """Signal extraction rides the accumulators: metrics output is
    byte-identical whether or not the alert engine is attached."""
    sc = scenario_by_name("smoke")
    for tag, alerts in (("off", None), ("on", str(tmp_path / "inc.jsonl"))):
        run_scenario(sc, seed=0, obs=ObsConfig(
            metrics_out=str(tmp_path / f"m{tag}.jsonl"), alerts_out=alerts,
            metrics_every_s=600.0))
    assert ((tmp_path / "moff.jsonl").read_bytes()
            == (tmp_path / "mon.jsonl").read_bytes())


def test_window_delta_gauges_sum_to_cumulative_totals(tmp_path):
    """The per-window delta gauges (satellite fix: counters were
    run-cumulative only) must sum back to the run totals."""
    out = tmp_path / "metrics.jsonl"
    report = run_scenario(
        scenario_by_name("fault-storm"), seed=0, hours=2.0,
        obs=ObsConfig(metrics_out=str(out), metrics_every_s=600.0))
    sums = {}
    finals = {}
    for line in out.read_text().splitlines():
        row = json.loads(line)
        if row.get("kind") != "sample":
            continue
        name = row["name"]
        if name.endswith("_window") and not name.startswith("serving"):
            sums[name] = sums.get(name, 0.0) + row["value"]
        elif name.endswith("_total"):
            finals[name] = row["value"]  # last sample = cumulative end
    for win_name, total_name in (
            ("errors_injected_window", "errors_injected_total"),
            ("jobs_started_window", "jobs_started_total"),
            ("jobs_finished_window", "jobs_finished_total"),
            ("jobs_evicted_window", "jobs_evicted_total"),
            ("online_incidents_window", "online_incidents_total")):
        assert sums.get(win_name, 0.0) == finals.get(total_name, 0.0), \
            win_name
    assert sums["errors_injected_window"] == report["sim"]["errors_injected"]
