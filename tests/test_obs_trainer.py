"""Spans and counters inside the host trainer's round.

Contracts under test:

* Nesting: per client, ``local_steps`` ``client.step`` spans inside its
  ``client.train``, each holding ``step.batch`` → ``step.lr`` →
  ``step.dispatch`` → ``step.sync``; ``client.opt_init`` inside
  ``client.train``; one ``round.lr`` and one ``ring.alloc`` per round;
  ``ring.scatter`` inside every ``ring.write``; ``eval.dispatch`` /
  ``eval.sync`` per eval batch inside ``round.eval``.
* ``host.syncs[<site>]`` counts every blocking device→host read, exactly as
  derived from the configuration, and each round record carries its total
  (``host_syncs``) and its summed uplink wall time (``uplink_ms``).
* Nothing new when off: ``obs="off"`` and ``obs="trace"`` runs give bitwise
  equal history and params, with the same number of traces and compiles.
* One clock with the device trace: a span recorded under a ``jax.profiler``
  capture lands on the xplane's host plane within 1 ms of
  ``origin_unix_ns + ts``.
"""

import dataclasses
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import FedConfig, LoRAConfig, TrainConfig, get_config
from repro.core import FederatedTrainer
from repro.data import ClientLoader, SyntheticLM
from repro.models import build_model
from repro.obs import Recorder, Tracer
from repro.util.tree import flatten_with_paths

CLIENTS, ROUNDS, STEPS, EVAL_BATCHES = 3, 2, 2, 2


def _trainer(obs: str) -> FederatedTrainer:
    cfg = dataclasses.replace(get_config("paper-tiny"), dtype="float32",
                              vocab_size=16)
    ds = SyntheticLM(vocab=16, num_tasks=CLIENTS, seed=0)
    loaders = [ClientLoader(ds.sample(task=t, num_sequences=8 + 4 * t,
                                      seq_len=16, seed=t),
                            batch_size=4, seed=t) for t in range(CLIENTS)]
    ev = ClientLoader(ds.sample(task=0, num_sequences=8, seq_len=16, seed=9),
                      batch_size=4, seed=9)
    return FederatedTrainer(
        model=build_model(cfg), lora_cfg=LoRAConfig(rank=4, alpha=8),
        fed_cfg=FedConfig(num_clients=CLIENTS, rounds=ROUNDS,
                          local_steps=STEPS, method="fedex",
                          weighting="examples", obs=obs),
        train_cfg=TrainConfig(learning_rate=1e-2, schedule="constant"),
        client_loaders=loaders,
        eval_batches=[ev.next_batch() for _ in range(EVAL_BATCHES)], seed=0)


@pytest.fixture(scope="module")
def traced():
    tr = _trainer("trace")
    tr.run()
    return tr


def _by_name(spans, name):
    return [s for s in spans if s["name"] == name]


def _inside(inner, outer) -> bool:
    return (inner["tid"] == outer["tid"] and outer["ts"] <= inner["ts"]
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"])


def _adapter_leaves(tr) -> int:
    return len(jax.tree.leaves(tr.global_lora))


class TestRoundSpans:
    def test_step_spans_nest_inside_each_clients_train(self, traced):
        spans = traced.recorder.tracer.spans
        trains = _by_name(spans, "client.train")
        assert len(trains) == ROUNDS * CLIENTS
        steps = _by_name(spans, "client.step")
        assert len(steps) == ROUNDS * CLIENTS * STEPS
        for t in trains:
            rnd, c = t["args"]["round"], t["args"]["client"]
            mine = [s for s in steps if _inside(s, t)]
            assert [(s["args"]["round"], s["args"]["client"], s["args"]["step"])
                    for s in mine] == [(rnd, c, i) for i in range(STEPS)]
            (opt,) = [s for s in _by_name(spans, "client.opt_init")
                      if _inside(s, t)]
            assert all(opt["ts"] + opt["dur"] <= s["ts"] for s in mine)
        order = ("step.batch", "step.lr", "step.dispatch", "step.sync")
        for st in steps:
            kids = sorted((s for n in order for s in _by_name(spans, n)
                           if _inside(s, st)), key=lambda s: s["ts"])
            assert tuple(s["name"] for s in kids) == order

    def test_round_level_and_ring_spans(self, traced):
        spans = traced.recorder.tracer.spans
        for name in ("round.lr", "ring.alloc"):
            assert sorted(s["args"]["round"] for s in _by_name(spans, name)) \
                == list(range(ROUNDS))
        writes = _by_name(spans, "ring.write")
        scatters = _by_name(spans, "ring.scatter")
        assert len(writes) == len(scatters) == ROUNDS * CLIENTS
        for w in writes:
            (sc,) = [s for s in scatters if _inside(s, w)]
            assert sc["args"]["round"] == w["args"]["round"]
            assert sc["args"]["open"] >= 1
        for ev in _by_name(spans, "round.eval"):
            for name in ("eval.dispatch", "eval.sync"):
                assert len([s for s in _by_name(spans, name)
                            if _inside(s, ev)]) == EVAL_BATCHES


class TestHostSyncs:
    def test_counters_match_the_configuration(self, traced):
        leaves = _adapter_leaves(traced)
        counters = traced.recorder.metrics.snapshot()["counters"]
        want = {"step": ROUNDS * CLIENTS * STEPS,
                "eval": ROUNDS * EVAL_BATCHES * 2,
                "schedule": ROUNDS,
                "uplink": ROUNDS * CLIENTS * leaves,
                "divergence": ROUNDS}
        got = {k[len("host.syncs["):-1]: v for k, v in counters.items()
               if k.startswith("host.syncs[")}
        assert got == want

    def test_round_records_carry_syncs_and_uplink_time(self, traced):
        per_round = (CLIENTS * STEPS + EVAL_BATCHES * 2 + 1
                     + CLIENTS * _adapter_leaves(traced) + 1)
        recs = {r["round"]: r for r in traced.recorder.round_records()}
        uplinks = _by_name(traced.recorder.tracer.spans, "client.uplink")
        for rnd in range(ROUNDS):
            assert recs[rnd]["host_syncs"] == per_round
            spent = sum(s["dur"] for s in uplinks
                        if s["args"]["round"] == rnd) / 1e6
            assert recs[rnd]["uplink_ms"] == pytest.approx(spent, rel=0.05,
                                                           abs=0.5)

    def test_basic_mode_counts_without_spans(self):
        tr = _trainer("basic")
        tr.run(until=1)
        counters = tr.recorder.metrics.snapshot()["counters"]
        assert counters["host.syncs[step]"] == CLIENTS * STEPS
        (r0,) = tr.recorder.round_records()
        assert r0["uplink_ms"] > 0 and r0["host_syncs"] > 0


    def test_mesh_trainer_counts_its_syncs(self):
        from repro.launch.mesh_train import MeshFederatedTrainer

        tr = _trainer("trace")
        mesh = MeshFederatedTrainer(
            model=tr.model, lora_cfg=tr.lora_cfg, fed_cfg=tr.fed_cfg,
            train_cfg=tr.train_cfg, client_loaders=tr.client_loaders,
            eval_batches=tr.eval_batches, seed=0)
        mesh.run()
        counters = mesh.recorder.metrics.snapshot()["counters"]
        got = {k[len("host.syncs["):-1]: v for k, v in counters.items()
               if k.startswith("host.syncs[")}
        # one lane-loss read and one schedule read per round: the mesh
        # round is one program over every lane
        assert got == {"step": ROUNDS, "eval": ROUNDS * EVAL_BATCHES * 2,
                       "schedule": ROUNDS, "divergence": ROUNDS}
        spans = mesh.recorder.tracer.spans
        for ev in _by_name(spans, "round.eval"):
            assert len([s for s in _by_name(spans, "eval.sync")
                        if _inside(s, ev)]) == EVAL_BATCHES


class TestNothingNewWhenOff:
    def test_off_and_trace_are_bitwise_equal_with_the_same_compiles(self):
        events = []

        def listen(name, _dur, **_kw):
            events.append(name)

        jax.monitoring.register_event_duration_secs_listener(listen)
        try:
            _trainer("off").run()          # warm every shared cache first
            seen = {}
            for mode in ("off", "trace"):
                events.clear()
                tr = _trainer(mode)
                tr.run()
                seen[mode] = (tr, sorted(events))
        finally:
            jax.monitoring.unregister_event_duration_listener(listen)
        (off, ev_off), (on, ev_on) = seen["off"], seen["trace"]
        assert ev_off == ev_on
        # each trainer jits its own step, eval and close: both runs lower them
        assert "/jax/core/compile/jaxpr_to_mlir_module_duration" in ev_off
        for a, b in zip(off.history, on.history):
            assert a.client_losses == b.client_losses
            assert (a.eval_loss, a.eval_acc, a.lr) == (b.eval_loss, b.eval_acc,
                                                       b.lr)
            assert float(a.divergence_scaled) == float(b.divergence_scaled)
        for tree_a, tree_b in ((off.params, on.params),
                               (off.global_lora, on.global_lora)):
            fa, fb = flatten_with_paths(tree_a), flatten_with_paths(tree_b)
            assert fa.keys() == fb.keys()
            for k in fa:
                np.testing.assert_array_equal(np.asarray(fa[k]),
                                              np.asarray(fb[k]))
        assert off.recorder.round_records() == []


class TestDeviceTraceClock:
    def test_span_lands_on_the_xplane_host_plane(self, tmp_path):
        from jax.profiler import ProfileData

        tracer = Tracer(device_annotations=True)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tracer.span("clock.probe"):
                jax.numpy.ones(4).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                            recursive=True)
        pd = ProfileData.from_file(path)
        env = dict(pd.find_plane_with_name("Task Environment").stats)
        starts = [ev.start_ns for p in pd.planes
                  if p.name.startswith("/host:") for line in p.lines
                  for ev in line.events if ev.name == "clock.probe"]
        assert len(starts) == 1
        (span,) = tracer.spans
        want = tracer.origin_unix_ns + span["ts"]
        assert abs(env["profile_start_time"] + starts[0] - want) < 1e6

    def test_exports_carry_the_origin(self, tmp_path):
        rec = Recorder("trace")
        with rec.span("x"):
            pass
        origin = rec.tracer.origin_unix_ns
        assert rec.tracer.to_chrome()["otherData"]["origin_unix_ns"] == origin
        assert rec.metrics_records()[0]["origin_unix_ns"] == origin
        assert "origin_unix_ns" not in Recorder("basic").metrics_records()[0]
