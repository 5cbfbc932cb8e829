"""One update of the measured train step over a model with sparse and
lightning layers (loss, remat over two stacks, adapter gradient, 8-bit Adam's
first step) against ``reference_sala.pg_loss_and_lora_grad``, through the
``learner`` driver on the CPU. Rows of 56 tokens over a ``dense_len`` of 16:
every answer token's block choice is live, and carries no gradient."""

import pytest

from rehearsal_helpers import assert_contract, shared_cell
from sala_spec import write_sala_benchmark


@pytest.fixture(scope="module")
def sala_file(tmp_path_factory):
    return write_sala_benchmark(tmp_path_factory.mktemp("sala"))


def test_the_learners_update_is_the_references(sala_file):
    line, notes = shared_cell(sala_file, "sala-tiny.learner", 0)
    assert_contract(line, 0)
    check = notes["check"]
    assert check["ok"] is True and check["elements_moved"] > 0
    assert check["loss_scaled_err"] <= 1e-5 and check["grad_sign_mass"] >= 0.9999
    assert check["tol_loss_scaled"] == 1e-5  # the cell's own, from its traffic file
    assert notes["compiles"]["window"]["programs"] == 0
