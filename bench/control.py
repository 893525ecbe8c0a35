#!/usr/bin/env python3
"""Readings that set a cell's limits: the program on many seeds, the control
and the planted faults on a few, in one process on the chip.

    python3 bench/control.py --workload <name> --seeds 1,2,3 \\
        --controls 1,2,3 --seconds 5 --out <name>.readings.jsonl

For every seed it runs the cell as ``bench/run.py`` does (with the window
``--seconds`` gives) and records the compared numbers. For the seeds in
``--controls`` it also records:

* ``control``: the plain reference put in the program's place, one precision
  step below what the configuration states (float8 operands for the
  bfloat16 trainer, three bfloat16 passes for the float32 close), compared
  by the same numbers;
* ``faults``: a fault planted in the reference put in the program's place
  (``half_batch``: the mean over the first half of the batch, and closes
  over the first half of the clients; ``unchanged``: closes that leave W0
  as it was; ``altered``: one served adapter value changed), and for the
  trainer also in the program's own captured steps (``unchanged``: a step
  that returns its state; ``altered``: one adapter value changed after
  step 3).

For a served cell, ``--rounds N`` reads only the control and the faults,
from the reference alone at N closes (no server run): the seed fixes the
deltas and example counts, and N is the rounds a run closes.

The benchmark's own runs never run this. One JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.time()
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import device, spec, weights  # noqa: E402
from harness.runner import Run, enable_cache  # noqa: E402


def _unchanged(calls):
    """The program's captured steps as a step that returns its state
    unchanged would have left them: no moments, no change."""
    import numpy as np
    zero = {k: np.zeros_like(v) for k, v in weights.flatten(
        calls[0]["mu"]).items()}
    return [dict(calls[0], mu=weights.unflatten(zero))] + calls[1:3] + [
        dict(calls[3], lora_in=calls[0]["lora_in"])]


def _altered(calls):
    """The program's captured steps with one adapter value altered where
    the third step produced it (+1.0 on the first element of the first
    leaf)."""
    flat = {k: v.copy() for k, v in weights.flatten(
        calls[3]["lora_in"]).items()}
    first = sorted(flat)[0]
    flat[first].flat[0] += 1.0
    return calls[:3] + [dict(calls[3], lora_in=weights.unflatten(flat))]


def control_readings(cell, seed: int, rep) -> dict:
    drv = spec.generator(cell)
    if cell.traffic["generator"] == "trainer":
        ref = spec.reference(cell.config["reference"])
        cfg, tr = cell.config, cell.traffic
        calls = rep["calls"]

        def close(**kw):
            return drv.close_readings(cfg, rep["last"], **kw)
        return {"control": dict(drv.readings(cfg, tr, seed, calls,
                                             mm=ref.mm_fp8),
                                **close(mode="3pass")),
                "faults": {
                    "half_batch": dict(drv.readings(cfg, tr, seed, calls,
                                                    mm=ref.mm_f32,
                                                    fault="half_batch"),
                                       **close(fault="half_batch")),
                    "unchanged": dict(drv.readings(cfg, tr, seed,
                                                   _unchanged(calls)),
                                      **close(fault="unchanged")),
                    "altered": dict(drv.readings(cfg, tr, seed,
                                                 _altered(calls)),
                                    **close(fault="altered"))}}
    cfg, tr = cell.config, cell.traffic
    args = (cfg, tr, seed, rep["n_ex"], rep["rounds"], rep["lshape"])
    want = drv.reference_outputs(*args)
    out = {"control": drv.gaps(drv.reference_outputs(*args, mode="3pass"),
                               want), "faults": {}}
    for fault in ("half_batch", "unchanged", "altered"):
        out["faults"][fault] = drv.gaps(
            drv.reference_outputs(*args, fault=fault), want)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rounds", type=int, default=0,
                    help="served cells: control and faults only, at this "
                         "many closes, from the reference alone")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    cell = spec.load_cell(args.workload)
    devices = device.require_chips(cell.chips)
    enable_cache()
    controls = {int(s) for s in args.controls.split(",") if s}
    sink = open(args.out, "a") if args.out else None
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.time()
        if args.rounds:
            drv = spec.generator(cell)
            rep = {"n_ex": drv.examples(cell.traffic, seed),
                   "rounds": args.rounds,
                   "lshape": drv.seeded_state(cell.config)[2]}
            row = {"seed": seed, "rounds": args.rounds,
                   **control_readings(cell, seed, rep),
                   "seconds": time.time() - t}
            print(json.dumps(row), flush=True)
            if sink:
                sink.write(json.dumps(row) + "\n")
                sink.flush()
            continue
        with device.CompileLog() as compiles:
            r = Run(cell=cell, seed=seed, seconds=args.seconds, trace=False,
                    devices=devices, t_start=time.time(), compiles=compiles)
            out = spec.generator(cell).run(r)
        row = {"seed": seed, "program": {c.name: c.value for c in out.checks},
               "failed": out.failed, "attempted": out.attempted,
               "memory_peak_bytes": out.device["memory_peak_bytes"]}
        if seed in controls:
            row.update(control_readings(cell, seed, out.context["replay"]))
        row["seconds"] = time.time() - t
        print(json.dumps(row), flush=True)
        if sink:
            sink.write(json.dumps(row) + "\n")
            sink.flush()
        del out
    return 0


if __name__ == "__main__":
    sys.exit(main())
