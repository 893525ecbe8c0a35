"""Traffic generator: the ``trainer`` generator's rounds, with the reference
following the first client's own local steps, however few a round holds.

``trainer.py`` takes the first three captured local-step calls as three steps
of one client and the fourth call's input as the state after them, so it
compares only mixes of four or more local steps a round: at two, the third
call is the second client's first step, which starts again from the global
adapter. This generator builds, warms up, measures, traces and compares the
last close exactly as ``trainer.py`` does (it imports those parts), and
compares the first client's first ``n = min(3, local_steps)`` steps with the
plain reference: their losses, the first step's gradient and the adapters
the n-th step returned.

Traffic parameters: those of ``trainer.py``.
"""

from __future__ import annotations

import gc
import math
import time
from typing import Any, Dict, List

import numpy as np

from harness import flops, weights
from harness.device import device_stamp, log
from harness.runner import Check, Outcome, Run
from program import seeded_state
from trainer import (_fault_batches, _leaf_gap, _norm, _traced_round, build,
                     close_readings, last_close)


def compared_steps(tr: Dict[str, Any]) -> int:
    """How many of the first client's local steps the reference follows."""
    return min(3, int(tr["local_steps"]))


def run(r: Run) -> Outcome:
    import jax
    cfg, tr = r.cell.config, r.cell.traffic
    trainer, cap, closer = build(r)
    trainer.run(until=1)                       # round 0: compiles, captured
    trainer.local_step = r.hook("local_step", cap.step)
    t0 = time.time()
    log(f"set-up {t0 - r.t_start:.1f}s (engine {trainer.engine.backend})")

    rounds, rnd, ends = 0, 1, [t0]
    while True:
        trainer.run(until=rnd + 1)
        rnd += 1
        rounds += 1
        t1 = time.time()
        ends.append(t1)
        if t1 - t0 >= r.seconds:
            break
    tokens = rounds * tr["clients"] * tr["local_steps"] * tr["batch"] * \
        tr["seq_len"]
    e2e = {"setup_s": t0 - r.t_start, "train_tokens_per_s": tokens / (t1 - t0)}
    log(f"window: {rounds} rounds, {tokens} tokens in {t1 - t0:.3f}s")
    log("round seconds: " + " ".join(f"{b - a:.3f}"
                                     for a, b in zip(ends, ends[1:])))
    failed = sum(1 for h in trainer.history[1:]
                 if not all(math.isfinite(l) for l in h.client_losses)
                 or not math.isfinite(h.eval_loss))

    reduced, ctx = None, {}
    if r.trace:
        reduced, ctx = _traced_round(r, trainer, rnd)
        if hasattr(trainer.recorder, "metrics"):
            snap = trainer.recorder.metrics.snapshot()
            log("program metrics: " + str({
                k: v for group in snap.values() for k, v in group.items()
                if k.startswith(("moe.", "step.saved"))}))
    dev = device_stamp(r.devices)

    # to the host: the last close's inputs and what it left, the first
    # client's captured steps; then the program's state is freed before the
    # reference runs
    last = last_close(trainer, closer)
    capt = [{k: jax.tree.map(np.asarray, v) for k, v in c.items()}
            for c in cap.calls[:compared_steps(tr)]]
    del trainer, cap, closer
    gc.collect()
    t_ref = time.time()
    want = reference_steps(cfg, tr, r.seed, capt)
    checks = compare(r, capt, want, close_readings(cfg, last))
    log(f"reference {time.time() - t_ref:.1f}s")
    ctx.update(peaks=r.peaks(),
               replay={"calls": capt, "last": last, "want": want},
               flops_per_token=flops.train_flops_per_token(cfg, tr["seq_len"]))
    return Outcome(metrics=e2e, attempted=rounds, failed=failed,
                   checks=checks, device=dev, window=(t0, t1), context=ctx,
                   reduced=reduced)


def reference_steps(cfg, tr, seed: int, calls, mm=None,
                    fault: str = "") -> Dict[str, Any]:
    """The plain reference's steps over the first client's batches from the
    seeded adapters, in ``mm`` (float32 at ``HIGHEST`` by default): losses,
    the first step's gradient and the adapters after the last step."""
    import jax.numpy as jnp
    from harness import spec

    n = compared_steps(tr)
    ref = spec.reference(cfg["reference"])
    _, wshape, lshape, _ = seeded_state(cfg)
    w = weights.make_tree(wshape, seed, 1)
    lora0 = weights.make_tree(lshape, seed, 2)
    batches = [{k: jnp.asarray(c["batch"][k]) for k in ("tokens", "targets")}
               for c in calls[:n]]
    rows = np.concatenate([np.asarray(b["tokens"]) for b in batches])
    if len({r.tobytes() for r in rows}) != len(rows):
        raise ValueError("the compared steps reused a row")
    recipe = dict(tr["recipe"], total_steps=10**6 * tr["local_steps"])
    out = ref.train_steps(cfg, recipe, w, dict(lora0),
                          _fault_batches(batches, fault),
                          mm=mm or ref.mm_f32,
                          scale=cfg["lora_alpha"] / cfg["lora_rank"])
    del w
    return {"losses": list(out["losses"]),
            "grad1": {k: np.asarray(v) for k, v in out["grad1"].items()},
            "lora": {k: np.asarray(v) for k, v in out["lora"].items()}}


def program_steps(tr, calls) -> Dict[str, Any]:
    """What the program's first client's steps gave, in the reference's
    terms: the first gradient is μ₁/(1−β₁)."""
    n, b1 = compared_steps(tr), tr["recipe"]["beta1"]
    return {"losses": [float(c["loss"]) for c in calls[:n]],
            "grad1": {k: v / (1 - b1) for k, v in
                      weights.flatten(calls[0]["mu"]).items()},
            "lora": weights.flatten(calls[n - 1]["lora_out"])}


def seeded_adapters(cfg, seed: int) -> Dict[str, np.ndarray]:
    _, _, lshape, _ = seeded_state(cfg)
    return {k: np.asarray(v) for k, v in
            weights.make_tree(lshape, seed, 2).items()}


def gaps(have, want, lora0) -> Dict[str, float]:
    """The three compared numbers of ``have`` (the program's steps, or the
    reference in its place) against ``want`` (the reference)."""
    g_norm = {k: _norm(v) for k, v in want["grad1"].items()}
    med = float(np.median(list(g_norm.values())))
    keep = sorted(k for k, v in g_norm.items() if v >= 1e-3 * med)
    d_ref = {k: want["lora"][k] - lora0[k] for k in keep}
    d_have = {k: np.asarray(have["lora"][k]) - lora0[k] for k in keep}
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(have["losses"], want["losses"])),
        "grad_gap": _leaf_gap({k: np.asarray(have["grad1"][k]) for k in keep},
                              want["grad1"], keep),
        "change_gap": _leaf_gap(d_have, d_ref, keep),
        "leaves_left_out": float(len(g_norm) - len(keep)),
    }


def compare(r: Run, calls, want, close: Dict[str, float]) -> List[Check]:
    n = compared_steps(r.cell.traffic)
    if len(calls) < n:
        raise RuntimeError(f"captured {len(calls)} local steps, need {n}")
    lora0 = seeded_adapters(r.cell.config, r.seed)
    start = weights.flatten(calls[0]["lora_in"])
    if any(not np.array_equal(start[k], lora0[k]) for k in lora0):
        raise ValueError("the trainer did not start from the seeded adapters")
    got = dict(gaps(program_steps(r.cell.traffic, calls), want, lora0),
               **close)
    lim = r.limits()
    log(f"readings: {got}")
    return [Check(k, got[k], lim[k]) for k in ("loss_gap", "grad_gap",
                                               "change_gap", "close_gap")]


def _unchanged(calls, n: int):
    """The first client's captured steps as a step that returns its state
    unchanged would have left them: no moments, no change."""
    zero = {k: np.zeros_like(v) for k, v in weights.flatten(
        calls[0]["mu"]).items()}
    out = [dict(calls[0], mu=weights.unflatten(zero))] + calls[1:n]
    out[n - 1] = dict(out[n - 1], lora_out=calls[0]["lora_in"])
    return out


def _altered(calls, n: int):
    """The first client's captured steps with one adapter value altered
    where the n-th step produced it (+1.0 on the first element of the first
    leaf)."""
    flat = {k: v.copy() for k, v in weights.flatten(
        calls[n - 1]["lora_out"]).items()}
    first = sorted(flat)[0]
    flat[first].flat[0] += 1.0
    return calls[:n - 1] + [dict(calls[n - 1],
                                 lora_out=weights.unflatten(flat))]


def control_readings(cell, seed: int, rep) -> Dict[str, Any]:
    """``bench/control.py``'s control and planted faults for this
    generator's cells, from one run's ``replay`` (which holds the run's own
    float32 reference, ``want``): the reference one precision step below
    the configuration's (fp8 operands; three bfloat16 passes for the close)
    and the faults ``half_batch``, ``unchanged`` and ``altered``, each in
    the reference and in the captured steps."""
    from harness import spec
    ref = spec.reference(cell.config["reference"])
    cfg, tr = cell.config, cell.traffic
    calls, want, n = rep["calls"], rep["want"], compared_steps(tr)
    lora0 = seeded_adapters(cfg, seed)

    def steps(**kw):
        return gaps(reference_steps(cfg, tr, seed, calls, **kw), want, lora0)

    def prog(c):
        return gaps(program_steps(tr, c), want, lora0)

    def close(**kw):
        return close_readings(cfg, rep["last"], **kw)
    return {"control": dict(steps(mm=ref.mm_fp8), **close(mode="3pass")),
            "faults": {
                "half_batch": dict(steps(fault="half_batch"),
                                   **close(fault="half_batch")),
                "unchanged": dict(prog(_unchanged(calls, n)),
                                  **close(fault="unchanged")),
                "altered": dict(prog(_altered(calls, n)),
                                **close(fault="altered"))}}
