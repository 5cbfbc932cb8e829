"""Who runs what in a whole run of the suite: ``tests/conftest.py::unit_of``
names the cases one xdist worker runs whole (``pytest_xdist_make_scheduler``
there hands it to xdist's scope scheduler). A unit wrongly cut costs time and
no verdict, so this holds only what the rule says: a file is a unit, the
conformance module's unit is a family, by the first word of the family's
name as the case ids carry it."""

import os
import pathlib
import re
from types import SimpleNamespace

import pytest

import family_suite as fs

CONFORMANCE = "tests/test_family_conformance.py"


@pytest.fixture(scope="module")
def suite(request):
    here = os.path.join(os.path.dirname(os.path.abspath(__file__)), "conftest.py")
    (plugin,) = [p for p in request.config.pluginmanager.get_plugins()
                 if getattr(p, "__file__", None) == here]
    return plugin


@pytest.mark.parametrize("nodeid, unit", [
    ("tests/test_tpu_compile.py::test_paged_decode[native-hd64-bf16]",
     "tests/test_tpu_compile.py"),
    ("tests/test_engine.py::TestEosStop::test_row_stops_at_eos_and_pads", "tests/test_engine.py"),
    ("tests/perfbench/test_perfbench_spec.py::test_reduced[depth-alone]",
     "tests/perfbench/test_perfbench_spec.py"),
    # the one case of the module that belongs to no family
    (f"{CONFORMANCE}::test_every_hybrid_preset_has_a_family_record_and_a_benchmark_file",
     CONFORMANCE),
], ids=["tpu_compiles", "a_class", "a_directory", "no_family"])
def test_a_file_is_one_unit(suite, nodeid, unit):
    assert suite.unit_of(nodeid) == unit


@pytest.mark.parametrize("name", [fam.name for fam in fs.families()])
def test_the_conformance_modules_unit_is_a_family(suite, name):
    """The id of a case is ``<family>-<case>`` (``per_family``) and a family's
    name may hold hyphens of its own (``swa-sink-moe``): the unit is named by
    the id's FIRST word, which no two families share, so a family's cases fall
    into one unit and no two families into the same."""
    first = name.partition("-")[0]
    assert [fam.name for fam in fs.families() if fam.name.partition("-")[0] == first] == [name]
    for case in ("refill-4", "pg", "paged_verify"):
        nodeid = f"{CONFORMANCE}::test_generate_equals_the_reference[{name}-{case}]"
        assert suite.unit_of(nodeid) == f"{CONFORMANCE}[{first}]"


def test_the_longest_unit_is_collected_first_then_a_family_at_a_time(suite):
    """The order the units are dealt in (``pytest_collection_modifyitems``):
    ``tests/test_tpu_compile.py``, one unit of minutes, then the conformance
    module with each family's cases together, then every other file where
    pytest collected it."""
    def item(file, family=None):
        spec = family and SimpleNamespace(params={"family": SimpleNamespace(name=family)})
        return SimpleNamespace(path=pathlib.Path(file), callspec=spec, family=family)

    items = [item("tests/perfbench/test_perfbench_spec.py"), item(CONFORMANCE, "swa-sink-moe"),
             item("tests/test_engine.py"), item(CONFORMANCE, "cca-moe"),
             item("tests/test_tpu_compile.py"), item(CONFORMANCE, "swa-sink-moe"),
             item("tests/test_weight_bus.py"), item("tests/test_tpu_compile.py")]
    suite.pytest_collection_modifyitems(items)
    assert [(i.path.name, i.family) for i in items] == [
        ("test_tpu_compile.py", None), ("test_tpu_compile.py", None),
        ("test_family_conformance.py", "cca-moe"),
        ("test_family_conformance.py", "swa-sink-moe"),
        ("test_family_conformance.py", "swa-sink-moe"),
        ("test_perfbench_spec.py", None), ("test_engine.py", None), ("test_weight_bus.py", None)]


def test_the_scheduler_is_xdists_own_with_this_rule_and_the_collections_order(suite):
    from xdist.scheduler import LoadScopeScheduling

    option = SimpleNamespace(tx=["popen"], numprocesses=1, loadscopereorder=True, maxprocesses=None,
                             px=[], dist="load")
    config = SimpleNamespace(option=option, getoption=lambda name, default=None: getattr(
        option, name, default), getvalue=lambda name: getattr(option, name))
    made = suite.pytest_xdist_make_scheduler(config, None)
    assert isinstance(made, LoadScopeScheduling) and option.loadscopereorder is False
    assert made._split_scope(f"{CONFORMANCE}::test_x[dsa-pg]") == f"{CONFORMANCE}[dsa]"
    option.dist = "each"  # any other mode is xdist's as it stands
    assert suite.pytest_xdist_make_scheduler(config, None) is None


def test_a_run_says_where_its_time_went(suite):
    """The table a run ends with (``pytest_terminal_summary``): worker-seconds
    by unit, every phase of a case summed, the heaviest first under the total,
    and no line the driver's count of dots could read as cases (a line of
    nothing but ``.FEsx`` and a percentage)."""
    report = lambda nodeid, duration: SimpleNamespace(nodeid=nodeid, duration=duration)
    reports = [report("tests/test_engine.py::TestEosStop::test_a", 2.0),
               report("tests/test_engine.py::test_b", 0.5),  # its set-up ...
               report("tests/test_engine.py::test_b", 1.5),  # ... and its call
               report(f"{CONFORMANCE}::test_x[dsa-pg]", 7.5),
               report(f"{CONFORMANCE}::test_x[cca-pg]", 3.0),
               report("tests/test_paged.py::test_c", 0.25)]
    assert suite.unit_seconds(reports, heaviest=3) == [
        "worker-seconds 15 in 4 units (tests/conftest.py::unit_of), the 3 heaviest:",
        f"unit     7.5 s  {CONFORMANCE}[dsa]",
        "unit     4.0 s  tests/test_engine.py",
        f"unit     3.0 s  {CONFORMANCE}[cca]"]
    assert not [line for line in suite.unit_seconds(reports)
                if re.match(r"^[.FEsx]+( *\[ *[0-9]+%\])?$", line)]
