"""Test configuration: force an 8-device CPU mesh before JAX backends initialize.

Sharding/collective tests (DP/TP/FSDP/ring attention, psum gradient sync) run
on virtual CPU devices so CI needs no TPU (SURVEY §4).
"""

import atexit
import os
import shutil
import sys
import tempfile

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# Hermetic autotune: engines consult the plan DB at construction
# (distrl_llm_tpu/autotune), and a developer's populated
# ~/.cache/distrl_llm_tpu/plan_db.json — or an exported DISTRL_PLAN_DB —
# would silently change engine defaults under the suite. Force the default
# DB to a fresh empty tempdir path (plain assignment, not setdefault);
# tests that exercise the DB pass explicit paths or monkeypatch this.
os.environ["DISTRL_PLAN_DB"] = os.path.join(
    tempfile.mkdtemp(prefix="distrl_test_"), "plan_db.json"
)

# WHAT IS KEPT (PR 62; ROADMAP D18, D21). One compilation cache for the run,
# shared by its workers and by the subprocesses its cases start: the process that
# starts the run (xdist's controller, or the one process of a run without it)
# makes the directory and removes it at exit; a worker finds it in the
# environment it inherits. Plain assignment, never a directory that outlives
# the run: a test must not pass on a program another run compiled, and with the
# variable unset `perfbench/run.py` and `utils/devices.py` would turn to
# `<checkout>/.jax_cache`, which does outlive it. WHICH programs are kept is
# JAX's own default (a compile of a second or more): PR 47 kept every program
# however small (`JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0`,
# `JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES=-1`), thousands of one-op
# executables a run. Measured on one machine, whole runs of the driver's command
# (PR 62; CHANGES.md has the table): the default 1,297 s; a threshold of 0.1 s
# 1,279 s, no better than the noise; NO cache cut by the 1,470 s limit at 99%, so
# the cache is worth an eighth of the wall and stays. None of the three cures
# D18: a worker died inside XLA:CPU's COMPILE (`backend_compile_and_load`) with
# the default and with no cache at all, and inside the cache's write with 0.1 s
# as it did with PR 47's: the fault is the compiler's under six busy workers, not
# the cache's, and a lost worker costs the one case it held.
if "PYTEST_XDIST_WORKER" not in os.environ:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
        prefix="distrl_test_jaxcache_"
    )
    atexit.register(
        shutil.rmtree, os.environ["JAX_COMPILATION_CACHE_DIR"], ignore_errors=True
    )

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# WITH WHAT (PR 62): XLA's own optimisation, as before. `jax_disable_most_optimizations`
# was MEASURED AND REFUSED (CHANGES.md's PR 62 entry, ROADMAP D21): it takes a
# third off a family file's CPU, and it moves the arithmetic. On the parent's
# tests it failed four cases of three files that hold bits or two orders of one
# sum to float32 rounding (`tests/perfbench/test_perfbench_second_family.py`'s
# seeded weights bit for bit, `tests/test_decode_view.py`'s `power-lora`, the
# delta-rule family's fan-out at 2e-6); with those exempted it failed two OTHER
# cases of two other files and lost a worker inside XLA's compile. The flag is no
# part of `jax.jit`'s key, so an exemption either reuses what was compiled before
# it or clears every cache of the process, and what passes then depends on which
# cases a worker was dealt before: more than a handful of files, and no way to
# say which. Issue 62's rule for that outcome is to leave the flag out.
# THE STEP BELOW IT WAS MEASURED AND REFUSED TOO (PR 65):
# `--xla_backend_optimization_level=1` in `XLA_FLAGS`, for the whole run and every
# process of it alike, so no case compiles with another's. A family's conformance
# cases take 284 CPU-seconds where they take 333 (level 2: 314), the cases the
# flag above failed pass, and the chip's compiler answers with the same programs
# (thirteen of `tests/test_tpu_compile.py`'s: the same optimised HLO to the byte);
# but `tests/test_learn_obs.py::TestDeviceBundle::test_armed_is_byte_identical_to_off`
# fails in its first byte, every time: two programs that round alike at level 3 do
# not at level 1. The suite holds bits, so it stays at the default.

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import faulthandler  # noqa: E402
import signal  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402

#: seconds of wall a case may take, set-up and tear-down included (the slowest
#: case of the default run takes under a minute, of `slow` under three). A case
#: that waits on a worker, a socket or a `jax.distributed` round fails BY NAME
#: with the stack it was in, where before it ate the run's limit and showed as
#: rc 124 with no name. `pytest-timeout` is not installed: this is its
#: `signal` method in ten lines.
CASE_LIMIT_S = 300.0


@pytest.fixture(autouse=True)
def case_limit(request):
    """THE LIMIT ON EVERY CASE: ``CASE_LIMIT_S`` of wall by the main thread's
    ``SIGALRM``; the handler dumps every thread's stack and fails the case."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def out_of_time(signum, frame):
        faulthandler.dump_traceback(file=sys.stderr)
        pytest.fail(f"{request.node.nodeid} was still running after {CASE_LIMIT_S:.0f} s "
                    "(tests/conftest.py::CASE_LIMIT_S)")

    was = signal.signal(signal.SIGALRM, out_of_time)
    signal.setitimer(signal.ITIMER_REAL, CASE_LIMIT_S)
    yield
    signal.setitimer(signal.ITIMER_REAL, 0)
    signal.signal(signal.SIGALRM, was)


@pytest.hookimpl(trylast=True)
def pytest_collection_modifyitems(items):
    """THE LONGEST UNIT FIRST (PR 62, PR 69): ``tests/test_tpu_compile.py`` is
    one unit of ``unit_of`` below, 65 compiles for the TPU that no cache serves,
    two to five minutes of one worker; where it sorts by name, among the last
    files, the run would end with that worker inside it while five idled. Then
    the families' conformance module A FAMILY AT A TIME: a family is one unit of
    ``unit_of`` below, its cases go to one worker and share the engines it built
    (``tests/family_suite.py::engine``). One stable sort on the file's name and
    the ``family`` parameter, the same in every worker, as xdist requires, and
    after pytest's own reordering (``trylast``)."""
    def order(item):
        if item.path.name == "test_tpu_compile.py":
            return 0, ""
        if item.path.name == "test_family_conformance.py":
            family = getattr(item, "callspec", None) and item.callspec.params.get("family")
            return 1, getattr(family, "name", "")
        return 2, ""

    items.sort(key=order)


def unit_of(nodeid):
    """WHAT ONE WORKER RUNS WHOLE: the cases that share what a process builds
    once. The conformance module's unit is a family (its ``small_pieces``, its
    weights, the engines of ``family_suite.engine``), named by the first word
    of the case's id, which is the family's (``cca``, ``swa``: the controller
    imports no family); every other file is one
    unit (its module fixtures, ``shared_cell``'s runs of a tiny cell,
    ``jax.jit``'s own cache of a file's programs). ``tests/test_tpu_compile.py``
    too, though its compiles share nothing: one process may load the TPU's
    library, and its compiler runs on every core it finds, so six of them at
    once fight for the cores (PR 65: 1,489 worker-seconds where one after
    another took 737)."""
    file, _, case = nodeid.partition("::")
    if file.endswith("test_family_conformance.py") and "[" in case:
        return f"{file}[{case.partition('[')[2].partition('-')[0]}]"
    return file


def unit_seconds(reports, heaviest=10):
    """WHERE A RUN'S TIME WENT, as lines for its log: the ``heaviest`` units of
    ``unit_of`` by the seconds their cases held a worker (set-up, call and
    tear-down, as the junit file sums them), heaviest first, under the total.
    Every line starts with a word, so the driver's count of a log's dots
    reads none of them."""
    seconds = {}
    for report in reports:
        unit = unit_of(report.nodeid)
        seconds[unit] = seconds.get(unit, 0.0) + report.duration
    ranked = sorted(seconds.items(), key=lambda item: (-item[1], item[0]))[:heaviest]
    return [f"worker-seconds {sum(seconds.values()):.0f} in {len(seconds)} units "
            f"(tests/conftest.py::unit_of), the {len(ranked)} heaviest:",
            *(f"unit {took:7.1f} s  {unit}" for unit, took in ranked)]


def pytest_terminal_summary(terminalreporter):
    """The table above at the end of every run, from the reports the process
    that prints the summary was sent anyway (xdist's controller: its workers'):
    a run that reaches its end leaves it in the driver's ``/tmp/_t1.log``."""
    reports = [report for found in terminalreporter.stats.values() for report in found
               if isinstance(report, pytest.TestReport)]
    if reports:
        terminalreporter.write_line("\n".join(unit_seconds(reports)))


@pytest.hookimpl(optionalhook=True)
def pytest_xdist_make_scheduler(config, log):
    """WHO RUNS WHAT (PR 65). ``--dist load`` deals single cases to whichever
    worker is free, so what a file builds once a PROCESS (an engine, a tiny
    cell's run, a module's weights, every program ``jax.jit`` holds) was built
    again in each worker a case of the file fell to (9,733 worker-seconds a
    whole run, 7,722 with units, the same tree an hour apart; PERF.md has the
    walls). Where ``--dist load`` is asked for (the driver's command, the
    README's; any other mode is xdist's as it stands), xdist's own scope scheduler
    with ``unit_of`` as the scope: a unit goes to one worker whole, units are
    dealt in the collection's order (the longest first, as ordered above, and
    not in the scheduler's own, the units of most cases first)."""
    from xdist.scheduler import LoadScopeScheduling

    if config.getvalue("dist") != "load":
        return None
    config.option.loadscopereorder = False

    class Units(LoadScopeScheduling):
        def _split_scope(self, nodeid):
            return unit_of(nodeid)

    return Units(config, log)
