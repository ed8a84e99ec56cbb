"""Marker / lane coverage audit: the test-tree <-> pytest.ini <->
scripts/run_tests.sh triangle stays closed.

Three claims, each of which has silently rotted in other projects:

  * every marker used anywhere under tests/ is REGISTERED in pytest.ini
    (an unregistered marker is a typo that silently deselects nothing);
  * every registered suite marker has a scripts/run_tests.sh lane, so
    each suite can be run in isolation (exemptions are pinned
    explicitly, with the reason);
  * the per-module marker inventory matches a pinned table — adding a
    test module or changing its family markers forces this audit to be
    updated in the same PR, which is the point.
"""
import configparser
import pathlib
import re

ROOT = pathlib.Path(__file__).resolve().parent.parent
TESTS = ROOT / "tests"

# Markers that deliberately have no run_tests.sh -m lane, and why.
LANE_EXEMPT = {
    "slow",      # the exclusion filter itself; included via --all
    "serving",   # spans most of tier-1 — the default lane covers it
}

# Pinned inventory: test module -> the pytest.ini markers it applies at
# module level or per-test. Modules absent from markers entirely map to
# the empty set (they run only in the default tier-1 lane).
EXPECTED_MODULE_MARKERS = {
    "test_admission.py": {"serving", "chunked", "paged", "sched"},
    "test_archs_smoke.py": set(),
    "test_bert_scoring.py": {"serving", "bert"},
    "test_distributed_steps.py": set(),
    "test_docs.py": set(),
    "test_encdec_serving.py": {"serving", "encdec"},
    "test_exactness_envelope.py": {"serving", "sharded"},
    "test_fused_integration.py": set(),
    "test_hlo_cost.py": set(),
    "test_kernels.py": {"kernels"},
    "test_markers.py": set(),
    "test_metrics_and_launchers.py": set(),
    "test_models.py": set(),
    "test_optimizers.py": set(),
    "test_paged_cache.py": {"serving", "paged"},
    "test_precision.py": set(),
    "test_properties.py": set(),
    "test_router.py": {"serving"},
    "test_sampling.py": {"serving"},
    "test_schedules_and_data.py": set(),
    "test_scheduling.py": {"serving", "sched", "paged", "slow"},
    "test_serving_engine.py": {"serving", "paged", "slow"},
    "test_serving_properties.py": {"paged", "sched", "spec"},
    "test_sharded_serving.py": {"serving", "sharded", "paged",
                                "chunked", "spec"},
    "test_sharding_rules.py": set(),
    "test_speculative.py": {"serving", "spec"},
    "test_system.py": set(),
    "test_tpu_compile.py": {"kernels"},
    "test_trace_spans.py": set(),
}

_MARK_RE = re.compile(r"pytest\.mark\.([A-Za-z_][A-Za-z0-9_]*)")
# pytest builtins / structural marks that need no pytest.ini entry
_BUILTIN = {"parametrize", "skipif", "skip", "xfail", "usefixtures",
            "filterwarnings"}


def registered_markers():
    cp = configparser.ConfigParser()
    cp.read(ROOT / "pytest.ini")
    lines = cp.get("pytest", "markers").strip().splitlines()
    return {line.split(":", 1)[0].strip() for line in lines if line.strip()}


def module_markers(path):
    used = set(_MARK_RE.findall(path.read_text()))
    return used - _BUILTIN


def lane_markers():
    """Markers run_tests.sh exposes as `-m \"<marker>\"` lanes."""
    text = (ROOT / "scripts" / "run_tests.sh").read_text()
    return set(re.findall(r'-m "([a-z_]+)"', text))


def test_all_used_markers_are_registered():
    registered = registered_markers()
    for path in sorted(TESTS.glob("test_*.py")):
        unknown = module_markers(path) - registered
        assert not unknown, (
            f"{path.name} uses unregistered markers {sorted(unknown)}: "
            f"register them in pytest.ini")


def test_every_suite_marker_has_a_lane():
    lanes = lane_markers()
    missing = registered_markers() - lanes - LANE_EXEMPT
    assert not missing, (
        f"registered markers without a scripts/run_tests.sh lane: "
        f"{sorted(missing)} — add a --<marker> lane or pin an "
        f"exemption with its reason")
    stale = lanes - registered_markers()
    assert not stale, (
        f"run_tests.sh lanes for unregistered markers: {sorted(stale)}")


def test_module_marker_inventory_is_pinned():
    actual = {p.name: module_markers(p)
              for p in sorted(TESTS.glob("test_*.py"))}
    assert set(actual) == set(EXPECTED_MODULE_MARKERS), (
        "test modules added/removed: update EXPECTED_MODULE_MARKERS",
        sorted(set(actual) ^ set(EXPECTED_MODULE_MARKERS)))
    for name, markers in actual.items():
        assert markers == EXPECTED_MODULE_MARKERS[name], (
            f"{name} marker set changed: expected "
            f"{sorted(EXPECTED_MODULE_MARKERS[name])}, found "
            f"{sorted(markers)} — update the pinned inventory")


def test_every_family_module_carries_its_family_marker():
    """The two workload-family suites must stay runnable via their
    dedicated lanes (--bert / --encdec)."""
    assert "bert" in module_markers(TESTS / "test_bert_scoring.py")
    assert "encdec" in module_markers(TESTS / "test_encdec_serving.py")
