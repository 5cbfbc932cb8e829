"""``Trainer.train()`` with ``--engine_impl paged`` over a model with sparse
and lightning layers, through the ``rl_step`` driver on the CPU: rollout
(segmented prefill, state hand-off, decode through the cache), rewards, the
update, the adapter pushed back to the engine, and the engine's
log-probabilities under the TRAINED adapter against the reference."""

import pytest

from rehearsal_helpers import assert_contract, shared_cell
from sala_spec import write_sala_benchmark


@pytest.fixture(scope="module")
def sala_file(tmp_path_factory):
    return write_sala_benchmark(tmp_path_factory.mktemp("sala"))


def test_trainer_train_steps_with_the_paged_engine(sala_file):
    line, notes = shared_cell(sala_file, "sala-tiny.rl-paged", 0)
    assert_contract(line, 0)
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert notes["check"]["ok"] is True and notes["check"]["tokens"] > 0
    assert notes["compiles"]["window"]["programs"] == 0
