"""The benchmark harness's own tests (``bench_harness/tests``), run once
in a subprocess and reported here as one case per harness test, so that
every run of the repo's tests guards the yardstick too.

The harness's tests rehearse every cell on the CPU (about a minute and
a half in all) and start subprocesses of their own; their conftest asks
for the same eight virtual CPU devices as this directory's.
"""

import os
import subprocess
import sys
import xml.etree.ElementTree as ET

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HARNESS_TESTS = os.path.join(ROOT, "bench_harness", "tests")

_CELLS = ["higgs_level", "epsilon_tree_allreduce_4chip", "higgs_deep_nodes",
          "epsilon_wide_build"]
# every harness test by its id; one added later runs under
# test_unlisted_harness_tests_pass until it is listed here
LISTED = (
    [f"test_harness::test_rehearse_cell[{t}-{w}]"
     for t in "01" for w in _CELLS]
    + [f"test_harness::test_control_fails[{w}]" for w in _CELLS]
    + ["test_harness::test_refuses_cpu",
       "test_harness::test_refuses_without_the_program",
       "test_harness::test_new_cell_is_files_and_entries_only"]
    + [f"test_harness::test_dense_cell_rehearses[{c}]"
       for c in ("sound", "control", "_hist_drop_half", "_hist_alter")]
    + ["test_harness::test_dense_layout_is_entries_reshaped"]
    + [f"test_harness::test_entries_layout_inputs_unchanged[{w}]"
       for w in ("higgs_deep_nodes", "higgs_level")]
    + [f"test_harness::test_recorded_bytes[{lay}]"
       for lay in ("entries", "dense")]
    + ["test_harness::test_unknown_layout_refused"]
    + [f"test_harness::test_fault_fails_check[{w}-{f}]"
       for w in ("higgs_level", "higgs_deep_nodes")
       for f in ("_hist_drop_half", "_hist_alter")]
    + [f"test_harness::test_fault_fails_check[epsilon_tree_allreduce_4chip-"
       f"{f}]" for f in ("_ar_no_exchange", "_ar_drop_half", "_ar_alter")]
    + ["test_harness::test_hist_bytes_hand_checked",
       "test_harness::test_allreduce_bus_bytes_hand_checked",
       "test_harness::test_tree_nodes",
       "test_harness::test_peaks_refuse_unknown_kind",
       "test_harness::test_reference_histogram_and_rel_err",
       "test_harness::test_merge_union"]
    + [f"test_harness::test_recorded_trace[trace_{c}chip.xplane.pb]"
       for c in (1, 4)]
    + ["test_harness::test_benchmark_json_names_and_keys"]
    + [f"test_program_spans::test_traced_rehearsal_reports_phases[{w}]"
       for w in ("epsilon_tree_allreduce_4chip", "higgs_deep_nodes")])


@pytest.fixture(scope="module")
def outcomes(tmp_path_factory):
    """{test id: (outcome, message)} of one run of the harness's tests."""
    xml = tmp_path_factory.mktemp("harness") / "junit.xml"
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", HARNESS_TESTS, "-q",
         "-p", "no:cacheprovider", "-p", "no:randomly",
         f"--junitxml={xml}"],
        capture_output=True, text=True, timeout=1200, cwd=ROOT)
    if not xml.exists():
        pytest.fail(f"the harness's tests did not run (exit "
                    f"{proc.returncode}):\n{proc.stdout[-3000:]}"
                    f"{proc.stderr[-3000:]}")
    out = {}
    for case in ET.parse(xml).getroot().iter("testcase"):
        key = case.get("classname").split(".")[-1] + "::" + case.get("name")
        bad = next((e for e in case if e.tag in ("failure", "error")), None)
        skipped = case.find("skipped")
        out[key] = (("failed", bad.get("message", "") + (bad.text or ""))
                    if bad is not None else
                    ("skipped", skipped.get("message", ""))
                    if skipped is not None else ("passed", ""))
    return out


@pytest.mark.parametrize("test_id", LISTED)
def test_harness_test_passes(outcomes, test_id):
    assert test_id in outcomes, f"{test_id} did not run"
    outcome, message = outcomes[test_id]
    assert outcome == "passed", message[-3000:]


def test_unlisted_harness_tests_pass(outcomes):
    failed = {k: v[1][-500:] for k, v in outcomes.items()
              if k not in LISTED and v[0] != "passed"}
    assert not failed, failed
