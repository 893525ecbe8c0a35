"""Traffic generator: the host-mode federated trainer (``repro.core.FederatedTrainer``),
built as ``repro.launch.train`` builds it, measured over whole rounds.

Traffic parameters (``bench/traffic/<mix>.json``): clients, local_steps,
batch, seq_len, eval_batches and eval_batch, the Dirichlet concentration
and floor of the clients' example counts, the optimizer recipe, and
``close_sample_every`` (the close comparison reads one W0 row in this many).

Set-up builds ONE trainer with the benchmark's weights and adapters (drawn
from the seed; the program supplies only the tree structure), and runs round
0 through the trainer's own ``run`` call: that compiles every program the
window uses, and its first client's first three local steps are the ones the
reference follows. The window is whole ``run(until=r + 1)`` rounds, local
steps, uplinks, closes and evals included, until ``--seconds`` have passed.

Before every close the rows of each adapted W0 that the seed samples are
gathered on the device (the latest gather only is kept). After the window
the plain reference folds the last close from those rows and the adapters
the clients delivered to it (the trainer keeps the latest round's), and the
trainer's W0 rows and global adapter after that close are compared with it.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import time
from typing import Any, Dict, List

import numpy as np

from harness import flops, weights
from harness.weights import jnp_dtype
from harness.device import device_stamp, log
from harness.runner import Check, Outcome, Run, reduce_trace
from program import seeded_state, w0_keys, w0_leaf, w0_path

# host spans that can name an idle gap in a traced round, most specific
# first (benchmark spans around the program's calls, then repro.obs spans)
SPANS = ("bench.batch", "bench.local_step", "bench.close", "bench.eval",
         "codec.encode", "codec.decode", "ring.write", "close.dispatch",
         "divergence.resolve", "client.uplink", "client.train", "round.close",
         "round.eval", "round.collect")


class Loader:
    """One client's shard: ``n`` seeded token rows, served in shuffled
    batches without replacement (rows of one pass all differ)."""

    def __init__(self, rows: np.ndarray, batch: int, seed: int):
        self.sequences = rows
        self.batch = batch
        self.rng = np.random.default_rng(seed)
        self._order = self.rng.permutation(len(rows))
        self._cursor = 0

    def next_batch(self) -> Dict[str, Any]:
        import jax.numpy as jnp
        if self._cursor + self.batch > len(self._order):
            self._order = self.rng.permutation(len(self.sequences))
            self._cursor = 0
        idx = self._order[self._cursor:self._cursor + self.batch]
        self._cursor += self.batch
        rows = self.sequences[idx]
        return {"tokens": jnp.asarray(rows[:, :-1], jnp.int32),
                "targets": jnp.asarray(rows[:, 1:], jnp.int32),
                "loss_mask": jnp.ones(rows[:, 1:].shape, jnp.float32)}


def make_data(cfg: Dict[str, Any], tr: Dict[str, Any], seed: int):
    """Client loaders (Dirichlet example counts) and eval batches."""
    import jax.numpy as jnp
    rng = np.random.default_rng(weights.seed_words(seed, 3))
    k, vocab, s = tr["clients"], cfg["vocab_size"], tr["seq_len"]
    share = rng.dirichlet([tr["dirichlet_alpha"]] * k)
    counts = tr["min_rows"] + rng.multinomial(tr["extra_rows"], share)
    loaders = [Loader(rng.integers(0, vocab, (int(n), s + 1), dtype=np.int32),
                      tr["batch"], int(rng.integers(2**31)))
               for n in counts]
    evals = []
    for _ in range(tr["eval_batches"]):
        rows = rng.integers(0, vocab, (tr["eval_batch"], s + 1), dtype=np.int32)
        evals.append({"tokens": jnp.asarray(rows[:, :-1]),
                      "targets": jnp.asarray(rows[:, 1:]),
                      "loss_mask": jnp.ones((tr["eval_batch"], s), jnp.float32)})
    return loaders, evals


class StepCapture:
    """Wraps the trainer's jitted local step for its first four calls: the
    inputs and outputs the reference comparison needs. The same jitted
    function runs underneath; the wrapper is removed after set-up."""

    def __init__(self, step, n: int = 4):
        self.step, self.n = step, n
        self.calls: List[Dict[str, Any]] = []

    def __call__(self, params, lora, opt_state, batch, lr):
        out = self.step(params, lora, opt_state, batch, lr)
        if len(self.calls) < self.n:
            self.calls.append({"lora_in": lora, "batch": batch,
                               "lora_out": out[0], "mu": out[1].mu,
                               "loss": out[2]})
        return out


def _annotated(fn, name):
    import jax

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return wrapped


def build(r: Run):
    """The trainer with the benchmark's weights, and the captures of its
    steps and of its closes."""
    from repro.configs import FedConfig, TrainConfig
    from repro.core import FederatedTrainer
    cfg, tr = r.cell.config, r.cell.traffic
    model, wshape, lshape, lcfg = seeded_state(cfg)
    params = weights.unflatten(weights.make_tree(wshape, r.seed, 1))
    lora = weights.unflatten(weights.make_tree(lshape, r.seed, 2))
    model = r.hook("model", dataclasses.replace(model,
                                                init=lambda rng: params))
    loaders, evals = make_data(cfg, tr, r.seed)
    rounds = 10**6
    rec = tr["recipe"]
    fed = FedConfig(num_clients=tr["clients"], rounds=rounds,
                    local_steps=tr["local_steps"], method="fedex",
                    dirichlet_alpha=tr["dirichlet_alpha"],
                    seed=weights.program_seed(r.seed), weighting="examples",
                    engine="auto", obs="trace" if r.trace else "off")
    train = TrainConfig(learning_rate=rec["lr"], weight_decay=rec["weight_decay"],
                        beta1=rec["beta1"], beta2=rec["beta2"], eps=rec["eps"],
                        grad_clip=rec["grad_clip"],
                        warmup_ratio=rec["warmup_ratio"], schedule="constant",
                        total_steps=rounds * tr["local_steps"])
    trainer = FederatedTrainer(model=model, lora_cfg=lcfg, fed_cfg=fed,
                               train_cfg=train, client_loaders=loaders,
                               eval_batches=evals,
                               seed=weights.program_seed(r.seed))
    trainer.global_lora = lora
    del params
    cap = StepCapture(trainer.local_step)
    trainer.local_step = r.hook("local_step", cap)
    trainer = r.hook("trainer", trainer)
    closer = CloseCapture(trainer.engine.close, sample_rows(
        wshape, lshape, r.seed, tr["close_sample_every"]))
    trainer.engine.close = closer
    return trainer, cap, closer


def close_inputs(trainer) -> Dict[str, Any]:
    """The latest round's close inputs as the program delivered them: each
    client's decoded adapter (host arrays, flat) and its raw weight."""
    out = trainer.outcomes[-1]
    if out.degraded or not out.delivered:
        raise RuntimeError(f"round {out.round_id} closed no deliveries")
    n = len(out.delivered)
    return {"loras": [weights.flatten(d.lora) for d in out.delivered],
            "weights": [1.0] * n if out.weights is None else list(out.weights)}


def sample_rows(wshape, lshape, seed: int, every: int) -> Dict[str, Any]:
    """For each adapted W0, the sorted row indices (one in ``every``, at
    least one) that the close comparison reads, drawn from the seed."""
    rng = np.random.default_rng(weights.seed_words(seed, 5))
    rows = {}
    for key in w0_keys(lshape):
        m = wshape[w0_path(wshape, key)].shape[-2]
        rows[key] = np.sort(rng.choice(m, max(1, m // every), replace=False))
    return rows


def _take(w, rows):
    import jax.numpy as jnp
    return jnp.take(w, rows, axis=-2)


class CloseCapture:
    """Wraps the engine's close: before each close it gathers the sampled
    rows of every adapted W0 (one small program per leaf, kept only for the
    latest close), then runs the program's own close on the same call."""

    def __init__(self, close, rows: Dict[str, Any]):
        import jax
        import jax.numpy as jnp
        self.close = close
        self.sampled = rows
        self.take = jax.jit(_take)
        self.rows = {k: jnp.asarray(v, jnp.int32) for k, v in rows.items()}
        self.before = None

    def gather(self, params) -> Dict[str, Any]:
        return {k: self.take(w0_leaf(params, k), r)
                for k, r in self.rows.items()}

    def __call__(self, params, client_ids, weights=None, **kw):
        self.before = None
        self.before = self.gather(params)
        return self.close(params, client_ids, weights, **kw)


def last_close(trainer, closer: CloseCapture) -> Dict[str, Any]:
    """The last close as the comparison reads it, on the host: its inputs,
    the sampled W0 rows before and after it, and the global adapter it
    left."""
    def host(tree):
        return {k: np.asarray(v) for k, v in tree.items()}
    return dict(close_inputs(trainer), rows=closer.sampled,
                before=host(closer.before),
                after=host(closer.gather(trainer.params)),
                lora=host(weights.flatten(trainer.global_lora)))


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
    dev = device_stamp(r.devices)

    # to the host: the last close's inputs and what it left, the captured
    # steps; then the program's state is freed before the reference runs
    last = last_close(trainer, closer)
    capt = [{k: jax.tree.map(np.asarray, v) for k, v in c.items()}
            for c in cap.calls]
    del trainer, cap, closer
    gc.collect()
    t_ref = time.time()
    checks = compare(r, capt, close_readings(cfg, last))
    log(f"reference {time.time() - t_ref:.1f}s")
    ctx.update(peaks=r.peaks(), replay={"calls": capt, "last": last},
               flops_per_token=flops.train_flops_per_token(cfg, tr["seq_len"]))
    return Outcome(metrics=e2e, attempted=rounds, failed=failed,
                   checks=checks, device=dev, window=(t0, t1), context=ctx,
                   reduced=reduced)


def _traced_round(r: Run, trainer, rnd: int):
    """Profile one more whole round with the benchmark's host spans around
    the program's calls."""
    import jax
    from harness import xtrace

    cfg, tr = r.cell.config, r.cell.traffic
    step, ev, eng = trainer.local_step, trainer.eval_fn, trainer.engine
    close = eng.close
    trainer.local_step = _annotated(step, "bench.local_step")
    trainer.eval_fn = _annotated(ev, "bench.eval")
    eng.close = _annotated(close, "bench.close")
    for ld in trainer.client_loaders:
        ld.next_batch = _annotated(ld.next_batch, "bench.batch")
    d = r.trace_dir()
    jax.profiler.start_trace(d)
    try:
        with jax.profiler.TraceAnnotation(xtrace.WINDOW):
            trainer.run(until=rnd + 1)
    finally:
        jax.profiler.stop_trace()
    trainer.local_step, trainer.eval_fn, eng.close = step, ev, close
    reduced = reduce_trace(r, d, SPANS)
    w0 = int(jnp_dtype(cfg["base_dtype"]).itemsize)
    fl, nb = flops.close_work(cfg, tr["clients"], w0)
    return reduced, {"close_flops": fl, "close_bytes": nb,
                     "close_programs": ("_close",),
                     "step_program": "step"}


def _norm(x) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))


def _leaf_gap(prog: Dict[str, Any], ref: Dict[str, Any],
              keep: List[str]) -> float:
    """Worst leaf of | ‖prog‖ − ‖ref‖ | / max(‖ref‖, median leaf's ‖ref‖)."""
    norms = {k: _norm(ref[k]) for k in keep}
    med = float(np.median(list(norms.values())))
    return max(abs(_norm(prog[k]) - norms[k]) / max(norms[k], med)
               for k in keep)


def readings(cfg, tr, seed: int, calls, mm=None,
             fault: str = "") -> Dict[str, float]:
    """The three compared numbers of one run, from its captured calls (or,
    for the control, ``mm`` in the program's place)."""
    import jax.numpy as jnp
    from harness import spec

    ref = spec.reference(cfg["reference"])
    _, wshape, lshape, _ = seeded_state(cfg)
    w = weights.make_tree(wshape, seed, 1)
    lora0 = {k: np.asarray(v) for k, v in
             weights.make_tree(lshape, seed, 2).items()}
    batches = [{k: jnp.asarray(c["batch"][k]) for k in ("tokens", "targets")}
               for c in calls[:3]]
    rows = np.concatenate([np.asarray(b["tokens"]) for b in batches])
    if len({r.tobytes() for r in rows}) != len(rows):
        raise ValueError("the compared steps reused a row")
    recipe = dict(tr["recipe"], total_steps=10**6 * tr["local_steps"])
    scale = cfg["lora_alpha"] / cfg["lora_rank"]
    out = ref.train_steps(cfg, recipe, w, {k: jnp.asarray(v)
                                           for k, v in lora0.items()},
                          batches, scale=scale)
    flat = weights.flatten
    if mm is not None:             # control: the reference in lower precision
        low = ref.train_steps(cfg, recipe, w, {k: jnp.asarray(v)
                                               for k, v in lora0.items()},
                              _fault_batches(batches, fault), mm=mm,
                              scale=scale)
        p_loss, p_g1 = low["losses"], low["grad1"]
        p_lora3 = low["lora"]
    else:
        b1 = tr["recipe"]["beta1"]
        p_loss = [float(c["loss"]) for c in calls[:3]]
        p_g1 = {k: v / (1 - b1) for k, v in flat(calls[0]["mu"]).items()}
        p_lora3 = flat(calls[3]["lora_in"])
        start = flat(calls[0]["lora_in"])
        if any(not np.array_equal(start[k], lora0[k]) for k in lora0):
            raise ValueError("the trainer did not start from the seeded "
                             "adapters")
    del w
    g_ref = {k: np.asarray(v) for k, v in out["grad1"].items()}
    g_norm = {k: _norm(v) for k, v in g_ref.items()}
    med = float(np.median(list(g_norm.values())))
    keep = sorted(k for k, v in g_norm.items() if v >= 1e-3 * med)
    d_ref = {k: np.asarray(out["lora"][k]) - lora0[k] for k in keep}
    d_prog = {k: np.asarray(p_lora3[k]) - lora0[k] for k in keep}
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(p_loss, out["losses"])),
        "grad_gap": _leaf_gap({k: np.asarray(p_g1[k]) for k in keep}, g_ref,
                              keep),
        "change_gap": _leaf_gap(d_prog, d_ref, keep),
        "leaves_left_out": float(len(g_norm) - len(keep)),
    }


def _fault_batches(batches, fault):
    if fault != "half_batch":
        return batches
    return [{k: v[: v.shape[0] // 2] for k, v in b.items()} for b in batches]


def _close_weights(raw, fault: str = ""):
    """A close's weights as the engine normalizes them: float64 sums, then
    float32; ``half_batch`` keeps the first half of the clients only."""
    import jax.numpy as jnp
    n = np.asarray(raw, np.float64)
    if fault == "half_batch":
        n[max(1, len(n) // 2):] = 0.0
    return jnp.asarray((n / n.sum()).astype(np.float32))


def _fold_rows(ref, last, key, wc, mode):
    """The reference's sampled rows of W0 ``key`` after the last close:
    W0 + s·(Σ w_c a_c b_c − ā b̄) in float32 from the rows the program
    held before it, stored in W0's dtype."""
    import jax.numpy as jnp
    rows = last["rows"][key]
    a = np.take(np.stack([l[key + "/a"] for l in last["loras"]]), rows,
                axis=-2)
    b = np.stack([l[key + "/b"] for l in last["loras"]])
    res = ref.residual(jnp.asarray(a), jnp.asarray(b), wc, mode)
    return ref.fold(jnp.asarray(last["before"][key]), res,
                    jnp.float32(last["scale"]))


def reference_global(last, mode: str = "f32", fault: str = ""):
    """The global adapter the reference serves after the last close:
    (ā, b̄) = Σ w_c (a_c, b_c). ``altered`` changes one served value."""
    import jax.numpy as jnp
    from harness import spec

    ref = spec.reference("fold")
    wc = _close_weights(last["weights"], fault)
    out = {}
    for path in sorted(last["loras"][0]):
        if not path.endswith("/a"):
            continue
        key = path[:-2]
        a = jnp.asarray(np.stack([l[key + "/a"] for l in last["loras"]]))
        b = jnp.asarray(np.stack([l[key + "/b"] for l in last["loras"]]))
        abar, bbar = ref.means(a, b, wc, mode)
        out[key + "/a"], out[key + "/b"] = np.asarray(abar), np.asarray(bbar)
    if fault == "altered":
        k = sorted(out)[0]
        out[k] = out[k].copy()
        out[k].flat[0] += 1.0
    return out


def close_readings(cfg, last, mode: str = "", fault: str = ""
                   ) -> Dict[str, float]:
    """``close_gap`` of the last close: the program's sampled W0 rows and
    global adapter after it (``last["after"]``, ``last["lora"]``) against
    the plain reference folding the same close from the rows the program
    held before it. With ``mode`` or ``fault`` set, the reference is put in
    the program's place: ``mode="3pass"`` is the control; ``unchanged`` (the
    close leaves W0 as it was), ``half_batch`` (the first half of the
    clients only, weights renormalized) and ``altered`` (one served value
    changed) are planted faults.

    ``close_gap`` is the worse of ``w0_mismatch_share`` (share of the
    sampled W0 elements whose stored value differs from the reference's)
    and ``adapter_gap`` (worst leaf of max|ā − ā_ref| / max|ā_ref|): the
    control moves only the share, one altered served value only the gap."""
    import jax.numpy as jnp
    from harness import spec

    ref = spec.reference("fold")
    last = dict(last, scale=cfg["lora_alpha"] / cfg["lora_rank"])
    wc = _close_weights(last["weights"])
    planted = bool(mode or fault)
    low = _close_weights(last["weights"], fault)
    differ, total = 0, 0
    for key in sorted(last["rows"]):
        want = _fold_rows(ref, last, key, wc, "f32")
        if not planted:
            have = jnp.asarray(last["after"][key])
        elif fault == "unchanged":
            have = jnp.asarray(last["before"][key])
        else:
            have = _fold_rows(ref, last, key, low, mode or "f32")
        differ += int(jnp.count_nonzero(have != want))
        total += int(want.size)
    lora = reference_global(last, mode or "f32", fault) if planted \
        else last["lora"]
    gap = 0.0
    for key, w_ref in reference_global(last).items():
        w = np.asarray(lora[key], np.float32)
        gap = max(gap, float(np.max(np.abs(w - w_ref)) / np.max(np.abs(w_ref))))
    share = differ / total
    return {"close_gap": max(share, gap), "w0_mismatch_share": share,
            "adapter_gap": gap}


def compare(r: Run, calls, close: Dict[str, float]) -> List[Check]:
    if len(calls) < 4:
        raise RuntimeError(f"captured {len(calls)} local steps, need 4")
    got = dict(readings(r.cell.config, r.cell.traffic, r.seed, calls),
               **close)
    lim = r.limits()
    log(f"readings: {got}")
    return [Check(k, got[k], lim[k]) for k in ("loss_gap", "grad_gap",
                                               "change_gap", "close_gap")]
