"""Who runs what in a whole run of the suite: ``tests/conftest.py::unit_of``
names the cases one xdist worker runs whole (``pytest_xdist_make_scheduler``
there hands it to xdist's scope scheduler). A unit wrongly cut costs time and
no verdict, so this holds only what the rule says: a file is a unit, the
conformance module's unit is a family, by the first word of the family's
name as the case ids carry it."""

import os

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
], ids=["real_size_compiles", "a_class", "a_directory", "no_family"])
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


def test_the_scheduler_is_xdists_own_with_this_rule_and_the_collections_order(suite):
    from types import SimpleNamespace

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
