"""The ``trainer_first_client`` generator on the DeepSeek-V2 cell's tiny twin:
a sound run is correct (the program's loss, first gradient and adapters
after the first client's steps against ``bench/reference/mla_moe.py``, and
the last close against ``fold.py``); a run with a fault planted under the
timed path, and the control and faults ``bench/control_first_client.py``
reads, are refused by the cell's limits."""

import pytest

from conftest import tiny_run
from harness import spec
from test_faults import _close_fault, _correct, _half_batch, _step_fault

CELL = "deepseek-v2.ep8.train.silo3.s4k"
SEED = 2**33 + 5


def _limits(name):
    import json
    import os
    with open(os.path.join(spec.BENCH, "limits", name + ".json")) as f:
        return json.load(f)["limits"]


def _fails(readings, limits):
    return any(readings[k] > v for k, v in limits.items())


def test_sound_run_is_correct_at_two_local_steps():
    r, out = tiny_run(CELL, seed=SEED)
    assert r.cell.traffic["local_steps"] == 2
    assert spec.generator(r.cell).compared_steps(r.cell.traffic) == 2
    assert len(out.context["replay"]["calls"]) == 2
    assert _correct(out), [(c.name, c.value, c.limit) for c in out.checks]


@pytest.mark.parametrize("plant", [_step_fault("unchanged"),
                                   _step_fault("altered"), _half_batch,
                                   _close_fault("unchanged"),
                                   _close_fault("half")],
                         ids=["step-unchanged", "step-altered", "half-batch",
                              "close-unchanged", "close-half"])
def test_faulty_run_is_refused(plant):
    _, out = tiny_run(CELL, seed=SEED, seconds=0.5, plant=plant)
    assert not _correct(out), [(c.name, c.value) for c in out.checks]


def test_control_reader_refuses_every_fault():
    r, out = tiny_run(CELL, seed=SEED, seconds=0.5)
    got = spec.generator(r.cell).control_readings(r.cell, SEED,
                                                  out.context["replay"])
    limits = _limits(CELL)
    for fault, readings in got["faults"].items():
        assert _fails(readings, limits), (fault, readings)
    assert set(got["control"]) >= set(limits)
