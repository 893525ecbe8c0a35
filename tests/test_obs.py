"""Observability subsystem: tracer, metrics, recorder + the traced contracts.

Contracts under test (src/repro/obs/, ISSUE 6):

* Tracer: perf_counter_ns spans nest by containment per thread, threads get
  distinct track ids, and the Chrome trace-event export is structurally what
  Perfetto expects (ph=M metadata, ph=X complete events in µs, ph=i instants).
* Metrics: typed counters/gauges/histograms behind a get-or-create registry
  that refuses to shadow a name with a different metric type.
* Recorder facade: ``off`` is the shared zero-alloc NULL no-op; ``basic``
  collects metrics but no spans (and refuses write_trace); ``trace`` adds
  spans; per-round records are keyed (run, round) so ``set_run`` namespacing
  keeps multi-run processes from merging rounds.
* MetricLogger CSV regression: heterogeneous records (a round that adds eval
  metrics mid-stream) rewrite the file under the union-of-keys header instead
  of crashing DictWriter (fieldnames used to freeze on the FIRST record).
* Comm reconciliation: on a quantized partial-participation run the measured
  BytesLedger agrees with core/comm.round_comm_params pinned to the observed
  delivered count — surfaced as per-round ``comm_match`` + the
  ``comm.reconcile_ok`` counter.
* Deferred-divergence resolution timing, now trace-proven: no host sync (and
  no ``divergence.resolve`` span) inside the close under
  ``jax.transfer_guard_device_to_host``; the resolve span lands AFTER the
  next round's ``ring.write`` spans, and ``scripts/obs_report.py``'s overlap
  check passes on the resulting stream.
* scripts/obs_report.py itself: stream loading, the overlap-invariant
  checker, trace-file validation, and the --check failure modes — exercised
  on synthetic span streams where the timestamps are chosen by hand.
"""

import csv
import importlib.util
import json
import pathlib
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import (NULL, Counter, Gauge, Histogram, MetricsRegistry,
                       NullRecorder, Recorder, Tracer, make_recorder)
from repro.util.logging import MetricLogger

_OBS_REPORT = (pathlib.Path(__file__).resolve().parents[1]
               / "scripts" / "obs_report.py")
_spec = importlib.util.spec_from_file_location("obs_report", _OBS_REPORT)
obs_report = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(obs_report)


# ---------------------------------------------------------------------------
# tracer


class TestTracer:
    def test_span_records_interval_and_args(self):
        tr = Tracer()
        with tr.span("outer", cat="test", round=3):
            with tr.span("inner", cat="test"):
                pass
        # recorded on exit: inner first
        assert [s["name"] for s in tr.spans] == ["inner", "outer"]
        inner, outer = tr.spans
        assert outer["args"] == {"round": 3}
        assert outer["ts"] >= 0 and outer["dur"] >= 0
        # nesting by containment: [inner] ⊆ [outer] on the same thread
        assert inner["tid"] == outer["tid"]
        assert inner["ts"] >= outer["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]

    def test_threads_get_distinct_track_ids(self):
        tr = Tracer()

        def record(name):
            with tr.span(name):
                pass

        t = threading.Thread(target=record, args=("worker",))
        record("main")
        t.start()
        t.join()
        tids = {s["name"]: s["tid"] for s in tr.spans}
        assert tids["main"] != tids["worker"]

    def test_instant_events(self):
        tr = Tracer()
        tr.instant("drop", cat="ring", client=7)
        (e,) = tr.events
        assert e["name"] == "drop" and e["args"] == {"client": 7}

    def test_chrome_export_structure(self, tmp_path):
        tr = Tracer()
        with tr.span("close.dispatch", cat="engine", round=0):
            pass
        tr.instant("ring.take", cat="ring", round=0)
        chrome = tr.to_chrome(process_name="proc")
        events = chrome["traceEvents"]
        metas = [e for e in events if e["ph"] == "M"]
        assert metas[0]["args"]["name"] == "proc"
        (x,) = [e for e in events if e["ph"] == "X"]
        assert x["name"] == "close.dispatch"
        # µs conversion from the ns record
        assert x["ts"] == pytest.approx(tr.spans[0]["ts"] / 1e3)
        assert x["dur"] == pytest.approx(tr.spans[0]["dur"] / 1e3)
        (i,) = [e for e in events if e["ph"] == "i"]
        assert i["name"] == "ring.take" and i["s"] == "t"
        path = tmp_path / "trace.json"
        tr.write_chrome_trace(str(path))
        assert json.load(open(path))["traceEvents"]
        assert obs_report.check_trace_file(str(path)) == []


# ---------------------------------------------------------------------------
# metrics


class TestMetrics:
    def test_counter_is_monotonic(self):
        c = Counter("n")
        c.inc()
        c.inc(4)
        assert c.value == 5
        with pytest.raises(ValueError, match="cannot decrease"):
            c.inc(-1)

    def test_gauge_last_write_wins(self):
        g = Gauge("occ")
        g.set(3)
        g.set(1)
        assert g.value == 1

    def test_histogram_summary(self):
        h = Histogram("lat")
        for v in (1, 2, 3):
            h.observe(v)
        s = h.summary()
        assert s["count"] == 3 and s["sum"] == 6.0
        assert s["min"] == 1.0 and s["max"] == 3.0
        assert s["mean"] == pytest.approx(2.0)
        assert s["std"] == pytest.approx(np.sqrt(2.0 / 3.0))
        assert Histogram("empty").summary() == {"count": 0}

    def test_registry_get_or_create_and_type_guard(self):
        reg = MetricsRegistry()
        assert reg.counter("x") is reg.counter("x")
        with pytest.raises(TypeError, match="is a Counter"):
            reg.gauge("x")
        snap = reg.snapshot()
        assert set(snap) == {"counters", "gauges", "histograms"}
        assert snap["counters"] == {"x": 0}


# ---------------------------------------------------------------------------
# recorder facade


class TestRecorderFacade:
    def test_off_is_the_shared_null_singleton(self):
        assert make_recorder("off") is NULL
        assert isinstance(NULL, NullRecorder)
        assert NULL.enabled is False and NULL.tracing is False

    def test_null_recorder_noop_contract(self, tmp_path):
        # callable unconditionally: spans usable, metrics inert, no files
        with NULL.span("anything", round=1):
            NULL.counter("c").inc(10)
            NULL.gauge("g").set(5)
            NULL.hist("h").observe(1.0)
        NULL.event("e", client=0)
        NULL.round_set(0, x=1)
        NULL.round_inc(0, "y")
        assert NULL.round_records() == []
        NULL.write_trace(str(tmp_path / "t.json"))
        NULL.write_metrics(str(tmp_path / "m.jsonl"))
        assert list(tmp_path.iterdir()) == []

    def test_mode_validation(self):
        with pytest.raises(ValueError, match="obs mode"):
            make_recorder("verbose")
        with pytest.raises(ValueError, match="basic|trace"):
            Recorder("off")

    def test_basic_mode_collects_metrics_but_no_spans(self, tmp_path):
        rec = make_recorder("basic")
        assert rec.enabled and not rec.tracing and rec.tracer is None
        with rec.span("close.dispatch", round=0):
            rec.counter("ring.evictions").inc()
        rec.event("ring.take", round=0)
        recs = rec.metrics_records()
        assert [r["type"] for r in recs[:2]] == ["meta", "counters"]
        assert not any(r["type"] in ("span", "event") for r in recs)
        assert recs[1]["counters"] == {"ring.evictions": 1}
        with pytest.raises(ValueError, match="write_trace"):
            rec.write_trace(str(tmp_path / "t.json"))

    def test_rounds_keyed_by_run_label(self):
        rec = Recorder("basic")
        rec.set_run("scenario-1")
        rec.round_set(0, delivered=3)
        rec.round_inc(0, "deadline_drops")
        rec.set_run("scenario-2")
        rec.round_set(0, delivered=2)
        recs = rec.round_records()
        assert len(recs) == 2  # round 0 of each run stays distinct
        assert recs[0] == {"run": "scenario-1", "round": 0, "delivered": 3,
                           "deadline_drops": 1}
        assert recs[1]["run"] == "scenario-2" and recs[1]["delivered"] == 2

    def test_trace_mode_stream_and_exports(self, tmp_path):
        rec = Recorder("trace")
        rec.set_run("r")
        with rec.span("ring.write", cat="ring", round=1, client=0):
            pass
        rec.event("ring.begin", cat="ring", round=1)
        mpath, tpath = tmp_path / "m.jsonl", tmp_path / "t.json"
        rec.write_metrics(str(mpath))
        rec.write_trace(str(tpath))
        recs = obs_report.load_stream(str(mpath))
        meta, counters, rounds, spans, events = obs_report.split_stream(recs)
        assert meta is not None and meta["backend"] == jax.default_backend()
        assert counters is not None
        (s,) = spans
        assert s["name"] == "ring.write" and s["run"] == "r"
        assert s["args"] == {"round": 1, "client": 0}
        assert isinstance(s["ts_us"], float) and s["dur_us"] >= 0
        (e,) = events
        assert e["name"] == "ring.begin"
        assert obs_report.check_trace_file(str(tpath)) == []
        assert any("obs mode=trace" in ln for ln in rec.summary_lines())


# ---------------------------------------------------------------------------
# satellite: MetricLogger CSV union-of-keys regression


class TestMetricLoggerCSV:
    def test_new_keys_mid_stream_rewrite_under_union_header(self, tmp_path):
        """A record introducing a new key (eval metrics on round boundaries)
        used to raise ValueError from DictWriter, whose fieldnames froze on
        the first record. Now: union header, old rows blank-filled."""
        path = tmp_path / "m.csv"
        ml = MetricLogger(csv_path=str(path))
        ml.log(0, {"loss": 1.0})
        ml.log(1, {"loss": 0.5, "eval_acc": 0.25})  # new key mid-stream
        ml.log(2, {"loss": 0.4})                    # back to the narrow shape
        ml.close()
        with open(path) as f:
            reader = csv.DictReader(f)
            assert reader.fieldnames == ["step", "wall_s", "loss", "eval_acc"]
            rows = list(reader)
        assert [r["loss"] for r in rows] == ["1.0", "0.5", "0.4"]
        assert rows[0]["eval_acc"] == ""      # predates the column
        assert rows[1]["eval_acc"] == "0.25"
        assert rows[2]["eval_acc"] == ""      # restval fills the gap
        assert len(ml.history) == 3

    def test_csvless_logger_still_accumulates(self):
        ml = MetricLogger(csv_path=None)
        ml.log(0, {"loss": 1.0})
        ml.log(1, {"loss": 0.5, "extra": 2})
        assert len(ml.history) == 2
        ml.close()


# ---------------------------------------------------------------------------
# satellite: deferred-divergence resolution timing, trace-proven


def _traced_engine(c=3, m=8, r=2, n=6, seed=0, **kw):
    from repro.core.engine import RoundCloseEngine

    rng = np.random.default_rng(seed)
    mk = lambda sh: jnp.asarray(rng.normal(size=sh), jnp.float32)
    params = {"blk": {"q_proj": {"kernel": mk((m, n))}}}
    template = {"blk": {"q_proj": {"a": mk((m, r)), "b": mk((r, n))}}}
    loras = [{"blk": {"q_proj": {"a": mk((m, r)), "b": mk((r, n))}}}
             for _ in range(c)]
    rec = Recorder("trace")
    eng = RoundCloseEngine(params, template, c_max=c, scale=2.0,
                           backend="jnp", recorder=rec, **kw)
    return eng, rec, params, loras


class TestDivergenceResolutionTiming:
    def test_resolve_span_lands_after_next_rounds_ring_writes(self):
        """The traced twin of the transfer-guard contract: the close emits
        close.dispatch but NO divergence.resolve span; round 1's uplinks
        stream into the ring; only then does resolve() stamp its span — so
        the resolve timestamp sits after every round-1 ring.write, and
        obs_report's overlap check proves round 0's close window intersects
        round 1's writes."""
        eng, rec, params, loras = _traced_engine(depth=2)
        eng.buffers.begin_round({i: i for i in range(3)}, round_id=0)
        for i, l in enumerate(loras):
            eng.buffers.write(i, l, round_id=0)
        with jax.transfer_guard_device_to_host("disallow"):
            _, params1, div0 = eng.close(params, [0, 1, 2], round_id=0)
        names = [s["name"] for s in rec.tracer.spans]
        assert "close.dispatch" in names
        assert "divergence.resolve" not in names, \
            "close resolved the divergence eagerly — host sync in the close"

        # round 1's uplinks stream in while round 0's close is in flight
        eng.buffers.begin_round({i: i for i in range(3)}, round_id=1)
        for i, l in enumerate(loras):
            eng.buffers.write(i, l, round_id=1)
        div0.resolve()  # the round boundary — the only host sync
        spans = rec.tracer.spans
        resolve0 = next(s for s in spans if s["name"] == "divergence.resolve")
        assert resolve0["args"]["round"] == 0
        r1_writes = [s for s in spans if s["name"] == "ring.write"
                     and s["args"]["round"] == 1]
        assert len(r1_writes) == 3
        for w in r1_writes:
            assert w["ts"] < resolve0["ts"], \
                "a round-1 uplink landed after round 0's resolve"

        # close round 1 too, then run the report's own invariant checker
        _, _, div1 = eng.close(params1, [0, 1, 2], round_id=1)
        div1.resolve()
        _, _, _, span_recs, _ = obs_report.split_stream(rec.metrics_records())
        proven, failures = obs_report.check_overlap(span_recs)
        assert failures == []
        assert len(proven) == 1 and "round=0→1" in proven[0]

    def test_round_records_carry_the_latency_split(self):
        eng, rec, params, loras = _traced_engine()
        for rnd in range(2):
            eng.buffers.begin_round({i: i for i in range(3)}, round_id=rnd)
            for i, l in enumerate(loras):
                eng.buffers.write(i, l, round_id=rnd)
            _, params, div = eng.close(params, [0, 1, 2], round_id=rnd)
            div.resolve()
        recs = {r["round"]: r for r in rec.round_records()}
        for rnd in range(2):
            r = recs[rnd]
            assert r["close_dispatch_us"] > 0
            assert r["close_block_us"] > 0
            assert r["divergence"] >= 0
        # one compile per signature: round 0 misses, round 1 hits
        counters = rec.metrics.snapshot()["counters"]
        (miss_key,) = [k for k in counters if k.startswith("engine.compile_miss")]
        assert counters[miss_key] == 1
        assert recs[0]["compile_miss"] == 1 and recs[1]["compile_miss"] == 0
        # the split's two halves are spans, one of each per round
        spans = rec.tracer.spans
        for name in ("close.dispatch", "divergence.resolve"):
            assert sorted(s["args"]["round"] for s in spans
                          if s["name"] == name) == [0, 1]
        hist = rec.metrics.snapshot()["histograms"]
        assert "engine.close_dispatch_us" not in hist
        assert "engine.close_block_us" not in hist


# ---------------------------------------------------------------------------
# satellite: ledger ↔ comm-table reconciliation on a quantized partial round


class TestCommReconciliation:
    def test_int8_partial_participation_rounds_reconcile(self):
        """The measured BytesLedger and core/comm.py's closed form are
        independent accountings of the same round; with int8 uplink AND
        partial participation they still agree on param counts (bytes are
        codec-dependent: int8 uplinks measure well under 4 B/param)."""
        import dataclasses

        from repro.configs import (FedConfig, LoRAConfig, TrainConfig,
                                   get_config)
        from repro.core import FederatedTrainer
        from repro.data import ClientLoader, SyntheticLM
        from repro.models import build_model

        cfg = dataclasses.replace(get_config("paper-tiny"), dtype="float32",
                                  vocab_size=16)
        ds = SyntheticLM(vocab=16, num_tasks=4, seed=0)
        loaders = [ClientLoader(ds.sample(task=t, num_sequences=12,
                                          seq_len=16, seed=t),
                                batch_size=4, seed=t) for t in range(4)]
        tr = FederatedTrainer(
            model=build_model(cfg), lora_cfg=LoRAConfig(rank=4, alpha=8),
            fed_cfg=FedConfig(num_clients=4, rounds=2, local_steps=2,
                              method="fedex", participation=0.5,
                              weighting="examples", quantize_uplink="int8",
                              obs="basic"),
            train_cfg=TrainConfig(learning_rate=1e-2, schedule="constant"),
            client_loaders=loaders, eval_batches=[], seed=0)
        tr.run()

        rec = tr.recorder
        assert rec.enabled and rec.mode == "basic"
        rounds = rec.round_records()
        matched = [r for r in rounds if "comm_match" in r]
        assert len(matched) == 2, f"expected 2 reconciled rounds: {rounds}"
        for r in matched:
            assert r["comm_match"] == 1, f"ledger ≠ comm table: {r}"
            assert r["delivered"] == 2  # ⌈0.5·4⌉ sampled, none dropped
            assert r["uplink_params"] > 0
            # int8 uplink: measured bytes well under fp32's 4 B/param
            assert r["uplink_bytes"] < 4 * r["uplink_params"]
            assert r["downlink_bytes"] > 0
        counters = rec.metrics.snapshot()["counters"]
        assert counters.get("comm.reconcile_ok") == 2
        assert "comm.reconcile_mismatch" not in counters

    def test_participants_pin_in_round_comm_params(self):
        """The reconciliation anchor: `participants` overrides the ceil
        estimate with the observed delivered count, and out-of-range pins
        are rejected."""
        from repro.core.comm import MatrixSpec, round_comm_params

        mats = [MatrixSpec("q", 8, 8)]
        # pinning to the count ⌈0.3·10⌉ would estimate gives the same table
        est = round_comm_params("fedex", mats, 2, 10,
                                participation_fraction=0.3)
        assert round_comm_params("fedex", mats, 2, 10, participants=3) == est
        # a realized count the estimate can't know (dropout) changes it
        dropped = round_comm_params("fedex", mats, 2, 10, participants=2)
        assert dropped["uplink"] < est["uplink"]
        with pytest.raises(ValueError, match="participants"):
            round_comm_params("fedex", mats, 2, 10, participants=0)
        with pytest.raises(ValueError, match="participants"):
            round_comm_params("fedex", mats, 2, 10, participants=11)


# ---------------------------------------------------------------------------
# scripts/obs_report.py on synthetic streams


def _span(name, ts, dur, rnd, run=None):
    return {"type": "span", "name": name, "cat": "t", "run": run, "tid": 0,
            "ts_us": float(ts), "dur_us": float(dur), "args": {"round": rnd}}


def _overlapping_spans(run=None):
    """Round 0 closes over [100, 500]us; round 1's writes land inside it."""
    return [
        _span("close.dispatch", 100, 50, 0, run),
        _span("ring.write", 200, 10, 1, run),
        _span("ring.write", 300, 10, 1, run),
        _span("divergence.resolve", 480, 20, 0, run),
        _span("close.dispatch", 600, 40, 1, run),
        _span("divergence.resolve", 700, 10, 1, run),
    ]


def _closed_round(rnd, run=None, **over):
    rec = {"type": "round", "run": run, "round": rnd, "sampled": 3,
           "delivered": 3, "close_dispatch_us": 50.0, "close_block_us": 20.0,
           "divergence": 0.1, "ring_evictions": 0, "stale_drops": 0,
           "uplink_bytes": 100, "downlink_bytes": 200, "comm_match": 1}
    rec.update(over)
    return rec


class TestObsReport:
    def test_load_stream_rejects_bad_json(self, tmp_path):
        path = tmp_path / "m.jsonl"
        path.write_text('{"type": "meta"}\n\nnot json\n')
        with pytest.raises(SystemExit, match="bad JSON"):
            obs_report.load_stream(str(path))

    def test_overlap_check_proves_the_good_stream(self):
        proven, failures = obs_report.check_overlap(_overlapping_spans())
        assert failures == []
        assert len(proven) == 1
        assert "2/2 ring.write" in proven[0]

    def test_overlap_check_fails_when_writes_miss_the_window(self):
        """A host sync inside the close pulls divergence.resolve before the
        next round's writes — the window shuts early and the check fails."""
        spans = _overlapping_spans()
        for s in spans:
            if s["name"] == "ring.write":
                s["ts_us"] = 550.0  # after the [100, 500] window shuts
        proven, failures = obs_report.check_overlap(spans)
        assert proven == []
        assert len(failures) == 1 and "did not overlap" in failures[0]

    def test_overlap_check_never_crosses_runs(self):
        """Round 0 of run A and round 1 of run B are NOT a consecutive pair."""
        spans = [_span("close.dispatch", 100, 50, 0, "A"),
                 _span("divergence.resolve", 480, 20, 0, "A"),
                 _span("ring.write", 200, 10, 1, "B"),
                 _span("close.dispatch", 600, 40, 1, "B"),
                 _span("divergence.resolve", 700, 10, 1, "B")]
        proven, failures = obs_report.check_overlap(spans)
        assert proven == [] and failures == []

    def test_run_checks_green_path(self):
        failures = obs_report.run_checks(
            {"type": "meta"}, {"type": "counters"},
            [_closed_round(0), _closed_round(1)],
            _overlapping_spans(), None)
        assert failures == []

    def test_run_checks_failure_modes(self):
        meta, counters = {"type": "meta"}, {"type": "counters"}
        rounds = [_closed_round(0), _closed_round(1)]
        spans = _overlapping_spans()

        assert any("no meta" in f for f in obs_report.run_checks(
            None, counters, rounds, spans, None))
        assert any("no round records" in f for f in obs_report.run_checks(
            meta, counters, [], [], None))

        incomplete = [_closed_round(0), _closed_round(1)]
        del incomplete[0]["close_block_us"]
        (f,) = obs_report.run_checks(meta, counters, incomplete, spans, None)
        assert "missing" in f and "close_block_us" in f

        mismatch = [_closed_round(0, comm_match=0), _closed_round(1)]
        (f,) = obs_report.run_checks(meta, counters, mismatch, spans, None)
        assert "closed form" in f

        # spans that prove nothing (no consecutive closed pair with writes)
        lonely = [_span("close.dispatch", 100, 50, 0),
                  _span("divergence.resolve", 480, 20, 0)]
        assert any("nothing proves" in f for f in obs_report.run_checks(
            meta, counters, rounds, lonely, None))

        # --trace against a span-free (obs=basic) stream
        assert any("no spans" in f for f in obs_report.run_checks(
            meta, counters, rounds, [], "trace.json"))

    def test_trace_file_validation(self, tmp_path):
        good = tmp_path / "good.json"
        good.write_text(json.dumps({"traceEvents": [
            {"name": "t", "ph": "M", "args": {}},
            {"name": "s", "ph": "X", "ts": 1.0, "dur": 2.0}]}))
        assert obs_report.check_trace_file(str(good)) == []

        empty = tmp_path / "empty.json"
        empty.write_text(json.dumps({"traceEvents": []}))
        assert any("no traceEvents" in p
                   for p in obs_report.check_trace_file(str(empty)))

        spanless = tmp_path / "spanless.json"
        spanless.write_text(json.dumps({"traceEvents": [
            {"name": "t", "ph": "M"}]}))
        assert any("no complete" in p
                   for p in obs_report.check_trace_file(str(spanless)))

        bad_x = tmp_path / "badx.json"
        bad_x.write_text(json.dumps({"traceEvents": [
            {"name": "s", "ph": "X", "ts": "soon"}]}))
        assert any("without numeric ts/dur" in p
                   for p in obs_report.check_trace_file(str(bad_x)))

        assert any("unreadable" in p for p in
                   obs_report.check_trace_file(str(tmp_path / "missing.json")))

    def test_main_on_a_real_recorder_stream(self, tmp_path, capsys):
        """End-to-end: a live traced engine run → write_metrics/write_trace →
        obs_report.main --check exits 0."""
        eng, rec, params, loras = _traced_engine()
        slots = {i: i for i in range(3)}
        # the trainers' interleaving: round 1's uplinks stream into the ring
        # BEFORE round 0's divergence resolves
        eng.buffers.begin_round(slots, round_id=0)
        for i, l in enumerate(loras):
            eng.buffers.write(i, l, round_id=0)
        _, params, div0 = eng.close(params, [0, 1, 2], round_id=0)
        eng.buffers.begin_round(slots, round_id=1)
        for i, l in enumerate(loras):
            eng.buffers.write(i, l, round_id=1)
        div0.resolve()
        _, _, div1 = eng.close(params, [0, 1, 2], round_id=1)
        div1.resolve()
        # the trainer's reconciliation fields, stamped here by hand (the
        # engine alone has no ledger)
        for rnd in range(2):
            rec.round_set(rnd, ring_evictions=0, stale_drops=0,
                          uplink_bytes=1, downlink_bytes=1, comm_match=1)
        mpath, tpath = tmp_path / "m.jsonl", tmp_path / "t.json"
        rec.write_metrics(str(mpath))
        rec.write_trace(str(tpath))
        code = obs_report.main([str(mpath), "--trace", str(tpath), "--check"])
        out = capsys.readouterr().out
        assert code == 0, out
        assert "CHECK OK" in out and "overlap invariant" in out
