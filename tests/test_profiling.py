"""repro.profiling subsystem: executed catalog, harness determinism, the
speed-matrix artifact contract, measured calibration, and the predictor
feature-contract property tests (satellite of ISSUE 4)."""
import dataclasses
import json

import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core.interference import (OFFLINE_MODEL_PROFILES, WorkloadProfile,
                                     online_profile, online_profile_arrays)
from repro.core.predictor import FEATURE_RANGES, N_FEATURES, pair_features
from repro.core.traces import SERVICES
from repro.profiling import (MeasuredInterferenceProvider, SpeedMatrix,
                             build_catalog, build_measured_predictor,
                             build_speed_matrix, catalog_by_role,
                             check_schema, default_matrix, execute,
                             make_measured_dataset, predict_share_curve,
                             workload_profile)
from repro.profiling.run import main as profiling_main


@pytest.fixture(scope="module")
def matrix():
    return default_matrix("smoke")


@pytest.fixture(scope="module")
def measured_predictor(matrix):
    # A100 included: the calibrated scenario's heterogeneous fleet needs it
    return build_measured_predictor(matrix, gpu_types=("T4", "A10", "A100"),
                                    n=150, epochs=5, seed=0)


# ------------------------------------------------------------------ catalog
def test_catalog_roles_and_costs():
    cat = build_catalog()
    onlines, offlines = catalog_by_role(cat)
    assert {w.name for w in onlines} == {"flash-prefill", "decode-serve"}
    assert {w.name for w in offlines} == {"ssm-scan", "lm-train-step"}
    for w in cat.values():
        assert w.cost_s() > 0
        p = w.profile()
        assert 0 < p.sm_activity <= 1 and 0 < p.mem_bw <= 1
        assert 0 <= p.mem_bytes_frac <= 1


def test_execute_runs_real_steps():
    cat = build_catalog()
    rec = execute(cat["ssm-scan"], interpret=True)
    assert rec.steps_executed == cat["ssm-scan"].steps
    assert np.isfinite(rec.checksum) and rec.checksum != 0.0
    assert rec.wall_ms_per_step > 0
    # execution is deterministic: same seed, same checksum
    assert execute(cat["ssm-scan"], interpret=True).checksum == rec.checksum


# ------------------------------------------------------------------ harness
def test_matrix_bit_reproducible(matrix):
    again = build_speed_matrix("smoke", seed=0)
    assert again.to_json() == matrix.to_json()


def test_matrix_schema_valid(matrix):
    assert check_schema(matrix.data) == []


def test_matrix_covers_full_pair_grid(matrix):
    onlines, offlines = catalog_by_role()
    for on in onlines:
        for off in offlines:
            pair = matrix.pair(on.name, off.name)
            assert pair["shares"] == sorted(pair["shares"])
            assert all(s >= 1.0 for s in pair["online_slowdown"])
            assert all(0.0 <= t <= 1.0 for t in pair["offline_tput"])
            # more SM share never slows the offline partner down
            assert pair["offline_tput"] == sorted(pair["offline_tput"])


def test_matrix_artifact_excludes_wall_time(matrix):
    assert "wall" not in matrix.to_json()


def test_schema_catches_corruption(matrix):
    data = json.loads(matrix.to_json())
    bad = dict(data, schema="nope/v0")
    assert any("schema" in p for p in check_schema(bad))
    bad = json.loads(matrix.to_json())
    bad["pairs"][0]["offline_tput"][0] = 1.7
    assert any("offline_tput" in p for p in check_schema(bad))
    bad = json.loads(matrix.to_json())
    del bad["workloads"][bad["pairs"][0]["online"]]
    assert check_schema(bad)


def test_matrix_save_load_roundtrip(matrix, tmp_path):
    path = tmp_path / "m.json"
    matrix.save(str(path))
    loaded = SpeedMatrix.load(str(path))
    assert loaded.data == json.loads(matrix.to_json())
    assert profiling_main(["--check-schema", str(path)]) == 0


def test_cli_list():
    assert profiling_main(["--list"]) == 0


# ------------------------------------------------------------- calibration
def test_provider_is_drop_in_for_array_provider(matrix):
    """Same call shape as interference.shared_performance_arrays, sane
    output contract for a whole simulated fleet."""
    provider = MeasuredInterferenceProvider(matrix)
    n = 64
    rng = np.random.default_rng(0)
    service_idx = np.arange(n) % len(SERVICES)
    on = online_profile_arrays(service_idx, rng.uniform(5, 150, n),
                               tuple(SERVICES))
    models = tuple(OFFLINE_MODEL_PROFILES)
    prof = [OFFLINE_MODEL_PROFILES[m] for m in models]
    idx = rng.integers(len(models), size=n)
    off = {k: np.array([getattr(p, k) for p in prof])[idx]
           for k in ("gpu_util", "sm_activity", "sm_occupancy", "mem_bw",
                     "exec_time_ms", "mem_bytes_frac")}
    shares = rng.uniform(0, 1, n)
    slow, tput = provider(on, off, shares)
    assert slow.shape == tput.shape == (n,)
    assert (slow >= 1.0).all()
    assert ((tput >= 0.0) & (tput <= 1.0)).all()
    # the alias used at drop-in call sites is the same function
    s2, t2 = provider.shared_performance_arrays(on, off, shares)
    np.testing.assert_array_equal(slow, s2)
    np.testing.assert_array_equal(tput, t2)


def test_provider_exact_on_measured_points(matrix):
    """Feeding a measured pair's own profiles at a measured share returns
    the matrix cell exactly."""
    provider = MeasuredInterferenceProvider(matrix)
    pair = matrix.pair("decode-serve", "lm-train-step")
    on_p = workload_profile(matrix, "decode-serve")
    off_p = workload_profile(matrix, "lm-train-step")
    keys = ("gpu_util", "sm_activity", "sm_occupancy", "mem_bw",
            "exec_time_ms", "mem_bytes_frac")
    on = {k: np.array([getattr(on_p, k)]) for k in keys}
    off = {k: np.array([getattr(off_p, k)]) for k in keys}
    for i, s in enumerate(pair["shares"]):
        slow, tput = provider(on, off, np.array([s]))
        assert slow[0] == pytest.approx(pair["online_slowdown"][i])
        assert tput[0] == pytest.approx(pair["offline_tput"][i])


def test_measured_dataset_shapes_and_ranges(matrix):
    feats, targets = make_measured_dataset(
        matrix, np.random.default_rng(3), n=64)
    assert feats.shape == (64, N_FEATURES)
    assert targets.shape == (64,)
    assert ((targets >= 0) & (targets <= 1)).all()
    lo, hi = FEATURE_RANGES[:, 0], FEATURE_RANGES[:, 1]
    assert (feats >= lo - 1e-6).all() and (feats <= hi + 1e-6).all()


def test_measured_policy_end_to_end(matrix, measured_predictor):
    from repro.core.simulator import run_policy
    from repro.policies import resolve
    pol = resolve("muxflow-measured")
    assert pol is resolve("calibrated-muxflow")
    assert pol.needs_predictor
    res = run_policy("muxflow-measured", predictor=measured_predictor,
                     n_devices=32, horizon_s=1800.0, trace="C", seed=3)
    assert res.policy == "muxflow-measured"
    assert res.avg_slowdown >= 1.0
    assert 0.0 <= res.avg_norm_tput <= 1.0


def test_calibrated_scenario_report(measured_predictor):
    from repro.cluster import run_scenario
    from repro.cluster.run import check_schema as report_schema
    rep = run_scenario("calibrated", predictor=measured_predictor,
                       n_devices=24, hours=0.5, seed=1)
    assert report_schema(rep) == []
    assert rep["sim"]["policy"] == "muxflow-measured"


def test_policy_build_predictor_seam(matrix):
    """SharingPolicy.build_predictor: the measured policy trains on
    measurements; the base default trains on the synthetic model."""
    from repro.policies import resolve
    pred = resolve("muxflow-measured").build_predictor(
        ("T4",), samples=80, epochs=2, seed=0)
    assert set(pred.params_by_type) == {"T4"}
    pred = resolve("time-sharing").build_predictor(
        ("T4",), samples=80, epochs=2, seed=0)
    assert set(pred.params_by_type) == {"T4"}


def test_measured_policy_tracks_env_var_matrix(matrix, tmp_path,
                                               monkeypatch):
    """The registry singleton must not pin a stale matrix: setting or
    clearing REPRO_SPEED_MATRIX between runs swaps the calibration source."""
    from repro.policies import resolve
    pol = resolve("muxflow-measured")
    monkeypatch.delenv("REPRO_SPEED_MATRIX", raising=False)
    assert pol.matrix.data == matrix.data
    provider_default = pol.provider
    path = tmp_path / "alt.json"
    alt = json.loads(matrix.to_json())
    alt["seed"] = 999
    path.write_text(json.dumps(alt, sort_keys=True))
    monkeypatch.setenv("REPRO_SPEED_MATRIX", str(path))
    assert pol.matrix.data["seed"] == 999
    assert pol.provider is not provider_default
    monkeypatch.delenv("REPRO_SPEED_MATRIX")
    assert pol.matrix.data == matrix.data
    # an explicitly supplied matrix is pinned — env var does not override
    pinned = type(pol)(matrix=matrix)
    monkeypatch.setenv("REPRO_SPEED_MATRIX", str(path))
    assert pinned.matrix.data == matrix.data


def test_cluster_cli_policy_override(tmp_path):
    """--policy swaps any registered policy into any scenario (CLI path)."""
    from repro.cluster.run import main as cluster_main
    out = tmp_path / "r.json"
    rc = cluster_main(["--scenario", "smoke", "--policy", "time-sharing",
                       "--devices", "16", "--hours", "0.5", "--seed", "0",
                       "--out", str(out)])
    assert rc == 0
    rep = json.loads(out.read_text())
    assert rep["sim"]["policy"] == "time-sharing"
    assert rep["scenario"]["policy"] == "time-sharing"


# --------------------------------------------- predictor feature contract
_PROFILE_FIELDS = st.tuples(
    st.floats(0.0, 1.0), st.floats(0.05, 1.0), st.floats(0.0, 1.0),
    st.floats(0.05, 1.0), st.floats(0.01, 10_000.0), st.floats(0.0, 1.0))


def _profile(name, fields):
    util, act, occ, bw, ms, mem = fields
    return WorkloadProfile(name=name, gpu_util=util, sm_activity=act,
                           sm_occupancy=occ, mem_bw=bw, exec_time_ms=ms,
                           mem_bytes_frac=mem)


@settings(max_examples=60, deadline=None)
@given(_PROFILE_FIELDS, _PROFILE_FIELDS, st.floats(0.0, 1.0))
def test_pair_features_within_documented_ranges(on_f, off_f, share):
    feats = pair_features(_profile("on", on_f), _profile("off", off_f), share)
    assert feats.shape == (N_FEATURES,)
    assert np.isfinite(feats).all()
    lo, hi = FEATURE_RANGES[:, 0], FEATURE_RANGES[:, 1]
    assert (feats >= lo - 1e-6).all() and (feats <= hi + 1e-6).all()


@settings(max_examples=25, deadline=None)
@given(st.lists(st.floats(0.0, 1.0), min_size=2, max_size=12),
       st.sampled_from(["recommend", "translate", "vision"]),
       st.floats(10.0, 180.0))
def test_predicted_tput_monotone_in_share(shares, svc, qps):
    """After training on measured data, predicted offline throughput along
    any share sweep is monotone non-decreasing (isotonic contract)."""
    pred = _MONO["pred"]
    on = online_profile(svc, qps)
    off = _MONO["off"]
    curve = predict_share_curve(pred, "T4", on, off, np.array(shares))
    order = np.argsort(shares)
    assert (np.diff(curve[order]) >= -1e-12).all()
    assert ((curve >= 0.0) & (curve <= 1.0)).all()


_MONO: dict = {}


@pytest.fixture(autouse=True, scope="module")
def _mono_setup(matrix, measured_predictor):
    _MONO["pred"] = measured_predictor
    _MONO["off"] = workload_profile(matrix, "lm-train-step")
    yield
    _MONO.clear()


# --------------------------------------------------------- profiler home
def test_core_profiler_shim_is_gone():
    """The PR-4 deprecation shim has been removed: the profiler's single
    home is repro.profiling.workloads, and the old import path now fails
    loudly instead of warning."""
    import importlib
    import sys
    sys.modules.pop("repro.core.profiler", None)
    with pytest.raises(ModuleNotFoundError):
        importlib.import_module("repro.core.profiler")
    from repro.profiling.workloads import profile_from_trace
    assert profile_from_trace("VGG16").name == "VGG16"
