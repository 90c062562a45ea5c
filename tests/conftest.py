"""Test configuration: force JAX onto a virtual 8-device CPU mesh, and
wire the tpusan runtime sanitizer into the suite.

Multi-chip hardware is not available in CI; sharding correctness is validated
on 8 virtual CPU devices (the driver separately dry-runs the multi-chip path
via __graft_entry__.dryrun_multichip).

tpusan (``tritonclient_tpu/sanitize``) integration:

* ``TPUSAN=1`` (or ``strict``) enables the sanitizer for the whole
  session — the CI tpusan lane runs the tier-1 subset this way — and the
  session FAILS if any runtime finding (including leaked shm handles at
  session end) survives; ``TPUSAN_REPORT=<path>`` additionally writes the
  findings (SARIF for ``.sarif`` paths, JSON otherwise) for
  ``scripts/tpusan_report.py``.
* The stress tier (``test_*_stress.py``) always runs under the sanitizer:
  an autouse fixture enables it per-test and fails the test on any new
  finding, so races only reachable under load are witnessed even in
  plain tier-1 runs.
"""

import os

import pytest

# Must be set before the backend initializes.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402,F401

from tritonclient_tpu import sanitize  # noqa: E402

_TPUSAN_ENV = os.environ.get("TPUSAN", "").strip().lower() not in (
    "", "0", "false", "off",
)
if _TPUSAN_ENV:
    # Enable BEFORE any test module imports the server/shm/engine code so
    # every named lock is constructed instrumented (jax is imported above,
    # so the device_put patch lands too).
    sanitize.enable()


@pytest.fixture(autouse=True)
def _tpusan_stress_tier(request):
    """Auto-load the sanitizer for the stress tier.

    Stress tests are where lock-order and lifecycle races actually get
    exercised; they run witnessed even without ``TPUSAN=1``, and fail on
    any finding seeded by their own execution. Findings are isolated with
    ``sanitize.capture`` so a session-wide ``TPUSAN=1`` report is not
    double-counted.
    """
    fspath = str(getattr(request.node, "path", None) or request.node.fspath)
    if "stress" not in os.path.basename(fspath):
        yield
        return
    sanitize.enable()
    try:
        with sanitize.capture() as cap:
            yield
    finally:
        sanitize.disable()
    if cap.findings:
        lines = "\n".join(f.text() for f in cap.findings)
        pytest.fail(
            f"tpusan: {len(cap.findings)} runtime sanitizer finding(s) "
            f"during stress test:\n{lines}"
        )


def pytest_sessionfinish(session, exitstatus):
    """TPUSAN sessions fail on surviving findings and write the report."""
    if not _TPUSAN_ENV:
        return
    sanitize.check_leaks()
    report = os.environ.get("TPUSAN_REPORT", "")
    if report:
        sanitize.write_report(report)
    found = sanitize.findings()
    if found:
        rep = session.config.pluginmanager.get_plugin("terminalreporter")
        lines = [f.text() for f in found]
        if rep is not None:
            rep.write_line("")
            for line in lines:
                rep.write_line(f"tpusan: {line}", red=True)
            rep.write_line(
                f"tpusan: {len(found)} runtime sanitizer finding(s) — "
                "failing the session", red=True,
            )
        session.exitstatus = 1


# One hand-over to the next `benchmark` PR. `tests/benchmark/
# test_benchmark_spec.py` is parametrised over every configuration and cell
# of BENCHMARK.json and holds each to the GPT family's key names (`n_embd`,
# `n_positions`, ...) and to an EMPTY `reduced`. The MLA / routed-expert
# configuration keeps its own published key names and lists the three keys
# it cut, so those two cases cannot pass, and a PR that changes the program
# may not edit a file the benchmark already has. The same things are held
# for this configuration by `tests/benchmark/test_benchmark_mla_moe.py`
# (every published number kept, `reduced` exact, the longest request inside
# the served positions, limits set). Not strict: the day the spec test asks
# the configuration's adapter for its keys, these pass and this goes.
_GPT_KEYED_SPEC_CASES = (
    "test_benchmark_spec.py::test_configuration_files[joyai-llm-flash]",
    "test_benchmark_spec.py::test_the_longest_request_fits_the_configuration"
    "[joyai-llm-flash.chat_half]",
    # PR 31: the window/global routed configuration, for the same reason
    # (its own published keys, five keys under `reduced`); the same things
    # are held for it by `tests/benchmark/test_benchmark_swa_moe.py`.
    "test_benchmark_spec.py::test_configuration_files[k-exaone-236b-a23b]",
    "test_benchmark_spec.py::test_the_longest_request_fits_the_configuration"
    "[k-exaone-236b-a23b.long_mixed]",
    # PR 33: the multi-stream residual configuration of the MLA family, for
    # the same reason (its own published keys, three keys under `reduced`);
    # the same things are held for it by
    # `tests/benchmark/test_benchmark_mhc_mla_moe.py`.
    "test_benchmark_spec.py::test_configuration_files[xing4.0-29b-a4b]",
    "test_benchmark_spec.py::test_the_longest_request_fits_the_configuration"
    "[xing4.0-29b-a4b.code]",
)


# A second hand-over to the next `benchmark` PR (PR 36). Eight tests of the
# benchmark's own files hold `BENCHMARK.json`'s per-layer entries to a count
# or to an exact set: four hold a traced tiny run's line to exactly the
# names the families shared at the time (three of them by `len(shared) ==
# 15`), three hold a cell to `15 + its family's own`, and one holds the
# list's last three entries to the `mhc_*` names. PR 36 appended five shared
# readers of the host's time (egress split, the loop off the CPU, the
# collector), a PR that changes the program may not edit a file the
# benchmark already has, and new entries go at the end of their list. The
# same runs, cells and list are held to the enlarged sets, nothing left out,
# by `tests/benchmark/test_benchmark_host_time.py`, one test for each of the
# eight. Strict: the day one passes again (a `benchmark` PR made it read
# `shared | the family's own` from BENCHMARK.json in place of a count) this
# hook fails the run and goes, with PR 25's `tests/benchmark/conftest.py`.
_STALE_COUNTS = (
    "test_benchmark_spans.py::"
    "test_the_traced_line_holds_exactly_the_old_and_the_new_metrics",
    "test_benchmark_mla_moe.py::"
    "test_traced_run_reports_the_shared_layers_and_the_routers_counters",
    "test_benchmark_swa_moe.py::"
    "test_traced_run_reports_the_shared_layers_and_the_new_counters",
    "test_benchmark_mhc_mla_moe.py::"
    "test_traced_run_reports_the_shared_layers_and_the_new_counter",
    "test_benchmark_mla_moe.py::"
    "test_the_cell_resolves_and_its_longest_request_fits",
    "test_benchmark_swa_moe.py::"
    "test_the_cell_resolves_and_its_longest_request_fits",
    "test_benchmark_mhc_mla_moe.py::"
    "test_the_cell_resolves_and_its_longest_request_fits",
    "test_benchmark_mhc_mla_moe.py::"
    "test_benchmark_json_gained_one_configuration_one_cell_three_metrics",
)


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(_GPT_KEYED_SPEC_CASES):
            item.add_marker(pytest.mark.xfail(
                strict=False, raises=(AssertionError, KeyError),
                reason="holds every configuration to the GPT family's key "
                       "names and an empty `reduced`; see tests/conftest.py"))
        elif item.nodeid.endswith(_STALE_COUNTS):
            # (no `raises`: two of the traced tests share the checkout's
            # `.bench_scratch/trace` with another file's, and a run that
            # loses its trace to the other is no news about this hand-over)
            item.add_marker(pytest.mark.xfail(
                strict=True,
                reason="holds the per-layer entries to a count from before "
                       "PR 36's five shared readers; see tests/conftest.py"))
