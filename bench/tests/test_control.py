"""The control comes out not correct: the plain reference put in the
program's place one precision step below what the configuration states is
refused by the cell's limits (``bench/limits/<cell>.json``).

At this size, on the CPU; ``bench/control.py`` reads the same numbers on
the chip at the cells' own sizes.
"""

import pytest

from conftest import tiny_run
from harness import spec

SEEDS = [2**33 + 11, 2**31 + 12, 13]

# The fp8 control moves the trainer's numbers less at this size than at the
# cell's (its loss gap reads 2.1e-4..5.6e-4 here against 1.0e-3..1.4e-3 on
# the chip), so the trainer's test holds it against limits set by the same
# rule from readings at this size (six seeds on the CPU): program
# loss 4.9e-5 / grad 2.1e-3 / change 1.9e-3 at most, control 2.1e-4 /
# 1.27e-2 / 1.16e-2 at least. The served controls fail the cells' own limits.
TINY_TRAIN_LIMITS = {"loss_gap": 1.2e-4, "grad_gap": 6e-3, "change_gap": 5e-3}
# At this size, on the CPU, the close's 3-pass control reads 3.0e-4..4.3e-4
# at the last close and the program 0.0 (four seeds), so the limit here is
# 1e-4.
TINY_CLOSE_LIMIT = {"close_gap": 1e-4}


def _limits(name):
    import json
    import os
    with open(os.path.join(spec.BENCH, "limits", name + ".json")) as f:
        return json.load(f)["limits"]


def _fails(readings, limits):
    return any(readings[k] > v for k, v in limits.items())


@pytest.mark.parametrize("seed", SEEDS)
def test_trainer_control_fails(seed):
    name = "qwen2.5-3b.train.silo3"
    r, out = tiny_run(name, seed=seed, seconds=0.5)
    drv = spec.generator(r.cell)
    ref = spec.reference(r.cell.config["reference"])
    calls = out.context["replay"]["calls"]
    got = drv.readings(r.cell.config, r.cell.traffic, seed, calls,
                       mm=ref.mm_fp8)
    assert _fails(got, TINY_TRAIN_LIMITS), got
    got = drv.close_readings(r.cell.config, out.context["replay"]["last"],
                             mode="3pass")
    assert _fails(got, TINY_CLOSE_LIMIT), got
    sound = {c.name: c.value for c in out.checks}
    assert not _fails(sound, dict(TINY_TRAIN_LIMITS, **TINY_CLOSE_LIMIT)), \
        sound


@pytest.mark.parametrize("name", ["granite-8b.l8.serve.c16",
                                  "granite-8b.l8.serve.c4"])
def test_served_control_fails(name):
    seed = SEEDS[0]
    r, out = tiny_run(name, seed=seed, seconds=0.5)
    drv = spec.generator(r.cell)
    rep = out.context["replay"]
    args = (r.cell.config, r.cell.traffic, seed, rep["n_ex"], rep["rounds"],
            rep["lshape"])
    got = drv.gaps(drv.reference_outputs(*args, mode="3pass"),
                   drv.reference_outputs(*args))
    assert _fails(got, _limits(name)), got
