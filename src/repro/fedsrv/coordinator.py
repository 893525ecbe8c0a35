"""Event-driven round coordinator: open → collect → close (weighted, exact).

Synchronous mode (``RoundCoordinator``): a round opens, sampled participants
are scheduled as (arrival_time, client) events from the straggler model, and
the round collects deliveries until the deadline passes WITH the min-quorum
met (deadline=0 → wait for everyone who didn't drop out). Close performs
*weighted* exact aggregation over the delivered subset: wᵢ = nᵢ/Σnⱼ (or
uniform), with the residual identity Σwᵢaᵢbᵢ = āb̄ + ΔW_res preserved exactly
— see core/aggregation.py.

Asynchronous mode (``AsyncBufferCoordinator``): FedBuff-style. Clients launch
against the *current* global adapter version and arrive after their simulated
latency; the server commits whenever ``buffer_size`` deliveries are buffered.
Stale deliveries (trained from an older version v) are discounted by
``(1 + staleness)^(−staleness_alpha)`` on top of their example weight, the
weights renormalized, and an exact residual for the committed subset is folded
at every commit — staleness changes the *weights*, never the exactness of the
weighted identity.

The coordinator is model-agnostic: training is injected as
``train_fn(client: ClientInfo, start_lora, round_id) → lora`` and every
adapter crosses the transport codec (so uplink quantization is part of what
gets aggregated). A BytesLedger entry is recorded per payload.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro.core import aggregation as agg
from repro.fedsrv.registry import (ClientInfo, ClientRegistry, SimClock,
                                   StragglerModel)
from repro.fedsrv.transport import (AdapterCodec, BytesLedger,
                                    StaleUplinkError, TransientTransportError,
                                    TransportError)
from repro.obs import NULL
from repro.util.logging import get_logger
from repro.util.tree import count_params

logger = get_logger("fedsrv")

TrainFn = Callable[[ClientInfo, Any, int], Any]


@dataclass(frozen=True)
class RoundPolicy:
    """Knobs for one round's collection behavior.

    participation — fraction of registered clients sampled per round.
    min_quorum   — deliveries required before the deadline may cut late
                   arrivals (0 → any single delivery suffices).
    deadline     — sim-seconds after round open at which late arrivals are
                   dropped, provided quorum is met (0 → no deadline).
    weighting    — "uniform" (legacy wᵢ=1/k path, bitwise-identical to the
                   seed trainer) or "examples" (wᵢ = nᵢ/Σnⱼ).
    """

    participation: float = 1.0
    min_quorum: int = 0
    deadline: float = 0.0
    weighting: str = "uniform"  # uniform | examples


@dataclass
class Delivery:
    client: ClientInfo
    lora: Any
    launched_at: float
    arrived_at: float
    staleness: int = 0  # async mode: commits elapsed since launch version


@dataclass
class RoundOutcome:
    round_id: int
    sampled: List[int]
    delivered: List[Delivery]
    dropped_out: List[int]          # never reported back
    dropped_deadline: List[int]     # arrived after deadline with quorum met
    weights: Optional[List[float]]  # None → uniform
    opened_at: float
    closed_at: float
    comm: Dict[str, int] = field(default_factory=dict)
    # --- fault outcomes (fedsrv/faults.py + the defended transport) ---
    # (client_id, reason) pairs whose uplink was quarantined (bad content)
    # or dropped (crash / replayed / duplicate address)
    quarantined: List[Tuple[int, str]] = field(default_factory=list)
    # quorum failed after quarantine: the trainer must carry forward the
    # previous global adapter (the round's set was evicted, never closed)
    degraded: bool = False
    retries: int = 0  # transient decode retries spent this round

    @property
    def client_ids(self) -> List[int]:
        return [d.client.client_id for d in self.delivered]


@dataclass
class UplinkResult:
    """What became of one client's uplink (see RoundCoordinator._uplink)."""

    ok: bool
    tree: Any = None        # decoded host tree when ok
    reason: str = ""        # quarantine/drop reason when not ok
    status: str = "delivered"  # delivered | quarantined | dropped
    retries: int = 0


def weighted_close(outcome: RoundOutcome, method: str = "fedex",
                   svd_rank: int = 0) -> Tuple[Any, Optional[Any]]:
    """Close a round: (new global adapter, residual-or-None) over the
    delivered subset with the outcome's weights. Exact for fedex/fedex_svd
    (modulo truncation for svd), inexact-by-design for fedit, exact by
    construction for ffa. ``svd_rank=0`` keeps the config-level "exact"
    meaning: the round closes through the plain (untruncated) fedex path."""
    loras = [d.lora for d in outcome.delivered]
    if not loras:
        raise ValueError(f"round {outcome.round_id} closed with no deliveries")
    w = outcome.weights
    if method == "fedex":
        return agg.fedex_aggregate(loras, w)
    if method == "fedex_svd":
        if svd_rank < 1:  # 0 → exact: never truncate
            return agg.fedex_aggregate(loras, w)
        return agg.fedex_svd_aggregate(loras, svd_rank, w)
    if method == "fedit":
        return agg.fedit_aggregate(loras, w), None
    if method == "ffa":
        return agg.ffa_aggregate(loras, w), None
    raise ValueError(f"unknown method {method!r}")


class RoundCoordinator:
    """Synchronous (per-round) coordinator with sampling/deadline/quorum.

    With the default policy (participation=1, no deadline, no dropout,
    uniform weighting, codec "none") this degenerates to the seed trainer's
    hard-coded loop: every client, client_id order, uniform mean.
    """

    def __init__(self, registry: ClientRegistry,
                 policy: Optional[RoundPolicy] = None,
                 stragglers: Optional[StragglerModel] = None,
                 codec: Optional[AdapterCodec] = None,
                 ledger: Optional[BytesLedger] = None,
                 clock: Optional[SimClock] = None,
                 sink: Optional[Any] = None,
                 recorder: Optional[Any] = None,
                 faults: Optional[Any] = None,
                 uplink_retries: int = 2,
                 retry_backoff: float = 0.05):
        self.registry = registry
        self.policy = policy or RoundPolicy()
        self.stragglers = stragglers or StragglerModel()
        self.codec = codec or AdapterCodec("none")
        self.ledger = ledger or BytesLedger()
        self.clock = clock or SimClock()
        # fault-injection layer (fedsrv/faults.FaultInjector) — None in
        # production paths; when set, every encoded uplink passes through
        # injector.corrupt() before delivery
        self.faults = faults
        # transient decode failures: bounded retry with exponential backoff
        # on the SimClock (retry_backoff · 2^attempt sim-seconds)
        if uplink_retries < 0:
            raise ValueError(f"uplink_retries must be ≥ 0, got {uplink_retries}")
        self.uplink_retries = uplink_retries
        self.retry_backoff = retry_backoff
        # obs recorder (repro.obs): the round lifecycle records nested spans
        # (round.collect → client.train → client.uplink → codec/ring) plus
        # per-round client-count metrics; propagated to the codec so
        # encode/decode byte counts land in the same stream.
        self.rec = recorder if recorder is not None else NULL
        if self.rec.enabled and not self.codec.rec.enabled:
            self.codec.rec = self.rec
        # optional streaming sink (core/engine.RoundBuffers): uplink payloads
        # are decoded INTO preallocated (C_max, …) device stacks as they
        # arrive — the fused round-close engine reads the stacks instead of
        # re-stacking a list of host trees at the deadline.
        self.sink = sink
        self._downlink_params: Optional[int] = None  # adapter tree is static

    # ------------------------------------------------------------------
    def _open_sink(self, candidates: List[int], round_id: int, *,
                   deadline: Optional[float] = None,
                   now: Optional[float] = None) -> None:
        """Assign this round's candidate clients to stack lanes in client-id
        order (stable: the uniform full-participation sum visits lanes in the
        same order the legacy list path visited clients). The round_id keys
        the sink's double-buffer ring: round N+1 uplinks stream into a fresh
        stack set while round N's set is still owned by its in-flight close.
        Zero-candidate rounds never open a set (there is nothing to stream
        and no close will ever take() it).

        ``deadline``/``now`` thread the ring's per-round eviction contract
        through (core/engine.RoundBuffers): when every ring set is in flight,
        open rounds whose deadline has passed are evicted instead of wedging
        the ring — the sync coordinator uses sim-seconds, the FedBuff
        coordinator commit VERSIONS, as the monotonic scale."""
        if self.sink is not None and candidates:
            self.sink.begin_round(
                {cid: i for i, cid in enumerate(sorted(candidates))},
                round_id=round_id, deadline=deadline, now=now)

    def _deliver(self, payload: Any, weight: float = 1.0) -> Tuple[Any, int]:
        """Decode one payload (into the sink when present) with bounded
        retry-with-backoff on transient failures. ``weight`` is the client's
        RAW aggregation weight, folded into a chunked sink's accumulators at
        ingest. Returns (host tree, retries spent); raises
        TransportError/StaleUplinkError when the payload must be
        quarantined/dropped."""
        attempt = 0
        while True:
            try:
                if self.faults is not None:
                    # a transient decode failure is a property of THIS
                    # delivery attempt, not of the (frozen) payload
                    self.faults.check_transient(payload.round_id,
                                                payload.client_id)
                if self.sink is not None:
                    return self.codec.decode_into(payload, self.sink,
                                                  weight=weight), attempt
                return self.codec.decode(payload), attempt
            except TransientTransportError as e:
                if attempt >= self.uplink_retries:
                    raise TransportError(
                        f"retries exhausted after {attempt} backoffs: {e}",
                        round_id=payload.round_id,
                        client_id=payload.client_id,
                        reason="retries_exhausted") from e
                self.clock.advance(self.retry_backoff * (2 ** attempt))
                attempt += 1
                if self.rec.enabled:
                    self.rec.counter("uplink.retries").inc()
                    self.rec.event("uplink.retry", cat="fedsrv",
                                   round=payload.round_id,
                                   client=payload.client_id, attempt=attempt)

    def _uplink(self, lora: Any, round_id: int, client_id: int, *,
                weight: float = 1.0,
                rank: Optional[int] = None) -> UplinkResult:
        """Client → server through the codec; the server aggregates what was
        actually transmitted (quantization included). With a streaming sink
        the decoded leaves additionally go straight into the client's stack
        lane (one decode, shared with the returned host tree). ``weight`` is
        the client's raw aggregation weight at delivery time — a chunked
        sink folds it in at ingest, so it must normalise to the close-time
        weighting (sync: example counts; async: the staleness discount,
        known here because commits drain AFTER the version they discount
        against).

        The defended path: an active fault injector corrupts the payload
        here (between encode and delivery — exactly where a real wire sits);
        validation failures QUARANTINE the uplink (ledger direction
        ``quarantined``, lane left zero for exact exclusion), addressing
        failures and mid-uplink crashes DROP it (direction ``dropped``).

        ``rank`` declares a ragged (hetero) uplink's true LoRA rank: it
        rides the payload header so rank-aware validation applies and the
        ring's slot rank vector records it at ingest.

        Obs: the ``client.uplink`` span, and its wall time summed into the
        round record's ``uplink_ms``.
        """
        rec = self.rec
        if not rec.enabled:
            return self._send(lora, round_id, client_id, weight, rank)
        t0 = time.perf_counter_ns()
        with rec.span("client.uplink", cat="fedsrv", round=round_id,
                      client=client_id):
            res = self._send(lora, round_id, client_id, weight, rank)
        rec.round_inc(round_id, "uplink_ms",
                      (time.perf_counter_ns() - t0) / 1e6)
        return res

    def _send(self, lora: Any, round_id: int, client_id: int, weight: float,
              rank: Optional[int]) -> UplinkResult:
        """The body of :meth:`_uplink`: encode, corrupt, deliver, ledger."""
        payload = self.codec.encode(lora, round_id=round_id,
                                    client_id=client_id,
                                    direction="uplink", rank=rank)
        kinds: List[str] = []
        if self.faults is not None:
            payload, applied = self.faults.corrupt(payload)
            kinds = [s.kind for s in applied]
        if "crash" in kinds:
            # client died mid-uplink: nothing ever reaches the server
            self.ledger.record(payload, note="fault:crash",
                               direction="dropped")
            self._note_undelivered(round_id, client_id, "crash",
                                   "dropped")
            return UplinkResult(ok=False, reason="crash",
                                status="dropped")
        if payload.round_id != round_id and self.sink is None:
            # replayed/misaddressed uplink with no ring to refuse it —
            # the coordinator rejects the address itself
            self.ledger.record(payload, note="drop:replay",
                               direction="dropped")
            self._note_undelivered(round_id, client_id, "replay",
                                   "dropped")
            return UplinkResult(ok=False, reason="replay",
                                status="dropped")
        try:
            tree, retries = self._deliver(payload, weight)
        except StaleUplinkError as e:
            self.ledger.record(payload, note=f"drop:{e.reason}",
                               direction="dropped")
            self._note_undelivered(round_id, client_id, e.reason,
                                   "dropped")
            return UplinkResult(ok=False, reason=e.reason,
                                status="dropped")
        except TransportError as e:
            self.ledger.record(payload, note=f"quarantine:{e.reason}",
                               direction="quarantined")
            self._note_undelivered(round_id, client_id, e.reason,
                                   "quarantined")
            return UplinkResult(ok=False, reason=e.reason,
                                status="quarantined")
        self.ledger.record(payload)
        if "duplicate" in kinds:
            # the duplicate copy consumed wire bytes but the ring drops
            # its lane write — record it, expect the StaleUplinkError
            try:
                self._deliver(payload)
            except StaleUplinkError:
                pass
            self.ledger.record(payload, note="fault:duplicate",
                               direction="dropped")
        return UplinkResult(ok=True, tree=tree, retries=retries)

    def _note_undelivered(self, round_id: int, client_id: int, reason: str,
                          status: str) -> None:
        """Obs + ledger bookkeeping shared by every not-delivered uplink:
        the downlink that fed this client never became aggregate input."""
        self.ledger.reclassify(round_id, client_id, "downlink", "dropped",
                               note=f"fed a {status} uplink")
        if self.rec.enabled:
            self.rec.counter(f"uplink.{status}[{reason}]").inc()
            self.rec.event("uplink.quarantine" if status == "quarantined"
                           else "uplink.drop", cat="fedsrv", round=round_id,
                           client=client_id, reason=reason)

    def _ensure_spec(self, global_lora: Any) -> None:
        """Register the global adapter's per-leaf (path → shape) spec with the
        codec on first use — every honest uplink must match it exactly."""
        v = self.codec.validation
        if v.enabled and v.check_spec and self.codec.spec is None:
            self.codec.register_spec(global_lora)

    def _record_downlink(self, lora: Any, round_id: int, client_id: int) -> None:
        """Downlink is always fp32 and the client trains on the original tree,
        so the ledger entry is recorded analytically (no serialize round-trip)."""
        if self._downlink_params is None:
            self._downlink_params = count_params(lora)
        self.ledger.record_analytic(round_id, "downlink",
                                    self._downlink_params,
                                    client_id=client_id, note="global adapters")

    # ------------------------------------------------------------------
    def run_round(self, round_id: int, train_fn: TrainFn, global_lora: Any
                  ) -> RoundOutcome:
        pol = self.policy
        self._ensure_spec(global_lora)
        participants = self.registry.sample_round(round_id, pol.participation,
                                                  max(1, pol.min_quorum))
        opened = self.clock.now()
        self.rec.event("round.open", cat="fedsrv", round=round_id,
                       sampled=len(participants))

        # schedule the event queue: dropout draws + arrival times
        dropped_out: List[int] = []
        stragglers = 0
        arrivals: List[Tuple[float, ClientInfo]] = []
        for c in participants:
            if self.stragglers.dropped(round_id, c):
                dropped_out.append(c.client_id)
                self.rec.event("client.dropout", cat="fedsrv", round=round_id,
                               client=c.client_id)
                continue
            lat, straggled = self.stragglers.draw(round_id, c)
            stragglers += int(straggled)
            arrivals.append((opened + lat, c))
        arrivals.sort(key=lambda tc: (tc[0], tc[1].client_id))

        # quorum: deliveries required before the deadline may cut stragglers.
        # min_quorum=0 → any delivery suffices (a positive deadline must be
        # able to drop; a round still can't close empty), but without a
        # deadline the round simply waits for every non-dropout.
        quorum = max(1, pol.min_quorum)
        quorum = min(quorum, len(arrivals)) if arrivals else 0

        # streaming close: every non-dropout candidate gets a stack lane up
        # front; late/dropped lanes simply stay masked (weight 0) at close.
        # A policy deadline doubles as the ring-eviction deadline: a round
        # that never closed by its deadline may be evicted from a full ring.
        self._open_sink([c.client_id for _, c in arrivals], round_id,
                        deadline=(opened + pol.deadline
                                  if pol.deadline > 0 else None),
                        now=opened)

        delivered: List[Delivery] = []
        dropped_deadline: List[int] = []
        quarantined: List[Tuple[int, str]] = []
        retries = 0
        with self.rec.span("round.collect", cat="fedsrv", round=round_id,
                           candidates=len(arrivals), quorum=quorum):
            for t, c in arrivals:
                late = pol.deadline > 0 and t > opened + pol.deadline
                if late and len(delivered) >= quorum:
                    dropped_deadline.append(c.client_id)
                    self.rec.event("client.deadline_drop", cat="fedsrv",
                                   round=round_id, client=c.client_id,
                                   arrived_at=t)
                    continue
                # downlink current global, train, uplink the result (codec)
                self._record_downlink(global_lora, round_id, c.client_id)
                with self.rec.span("client.train", cat="fedsrv",
                                   round=round_id, client=c.client_id):
                    lora_c = train_fn(c, global_lora, round_id)
                res = self._uplink(
                    lora_c, round_id, c.client_id,
                    weight=(float(c.num_examples)
                            if pol.weighting == "examples" else 1.0))
                # the arrival consumed sim-time whether or not it delivered
                # — a quarantined uplink and its crash twin leave the clock
                # (and thus every later draw) identical
                self.clock.advance_to(t)
                retries += res.retries
                if res.ok:
                    delivered.append(Delivery(client=c, lora=res.tree,
                                              launched_at=opened,
                                              arrived_at=t))
                else:
                    quarantined.append((c.client_id, res.reason))

        closed = self.clock.now()  # arrival of the last delivery this round
        # stable order: aggregation sums in client_id order (bitwise parity
        # with the seed loop under the trivial policy)
        delivered.sort(key=lambda d: d.client.client_id)

        # graceful degradation: quarantine can starve a round below quorum
        # (impossible in the clean path — quorum is capped to the arrivals
        # that all deliver). Carry-forward semantics: the round never
        # closes, so its sink set is evicted here, never take()n.
        degraded = bool(arrivals) and len(delivered) < quorum
        if degraded:
            self._evict_sink_round(round_id, "degraded: quorum failed "
                                   "after quarantine")
            if self.rec.enabled:
                self.rec.counter("round.degraded").inc()
            self.rec.event("round.degraded", cat="fedsrv", round=round_id,
                           delivered=len(delivered), quorum=quorum,
                           quarantined=len(quarantined))
            logger.warning(
                "round=%d DEGRADED: %d/%d deliveries after quarantine "
                "(quorum %d) — global adapter carried forward", round_id,
                len(delivered), len(arrivals), quorum)

        weights = None
        if pol.weighting == "examples" and delivered:
            weights = self.registry.weights_for(
                [d.client.client_id for d in delivered])
        elif pol.weighting not in ("uniform", "examples"):
            raise ValueError(f"unknown weighting {pol.weighting!r}")

        outcome = RoundOutcome(
            round_id=round_id, sampled=[c.client_id for c in participants],
            delivered=delivered, dropped_out=dropped_out,
            dropped_deadline=dropped_deadline, weights=weights,
            opened_at=opened, closed_at=closed,
            comm=self.ledger.round_totals(round_id),
            quarantined=quarantined, degraded=degraded, retries=retries)
        if self.rec.enabled:
            self.rec.round_set(round_id, sampled=len(participants),
                               delivered=len(delivered),
                               stragglers=stragglers,
                               dropped_out=len(dropped_out),
                               deadline_drops=len(dropped_deadline),
                               quarantined=len(quarantined),
                               retries=retries, degraded=int(degraded),
                               opened_at=round(opened, 3),
                               closed_at=round(closed, 3))
        logger.info(
            "round=%d sampled=%d delivered=%d dropout=%d deadline_drop=%d "
            "quarantined=%d open=%.2fs close=%.2fs", round_id,
            len(participants), len(delivered), len(dropped_out),
            len(dropped_deadline), len(quarantined), opened, closed)
        return outcome

    def _evict_sink_round(self, round_id: int, reason: str) -> None:
        """Evict a degraded round's stack set (if a sink opened one) so the
        ring never wedges on a round nobody will close."""
        if self.sink is not None and round_id in getattr(
                self.sink, "open_rounds", []):
            self.sink.evict(round_id, reason=reason)


class AsyncBufferCoordinator(RoundCoordinator):
    """FedBuff-style buffered commits with staleness-discounted exact folds.

    Each ``run_round`` call is ONE server commit: newly sampled clients are
    launched against the current global version, then the ``buffer_size``
    earliest arrivals (possibly launched several versions ago) are trained
    from their launch-time global snapshot and committed together.
    """

    def __init__(self, registry: ClientRegistry,
                 policy: Optional[RoundPolicy] = None,
                 stragglers: Optional[StragglerModel] = None,
                 codec: Optional[AdapterCodec] = None,
                 ledger: Optional[BytesLedger] = None,
                 clock: Optional[SimClock] = None,
                 buffer_size: int = 2,
                 staleness_alpha: float = 0.5,
                 max_version_lag: int = 1,
                 recorder: Optional[Any] = None,
                 faults: Optional[Any] = None,
                 uplink_retries: int = 2,
                 retry_backoff: float = 0.05):
        super().__init__(registry, policy, stragglers, codec, ledger, clock,
                         recorder=recorder, faults=faults,
                         uplink_retries=uplink_retries,
                         retry_backoff=retry_backoff)
        if buffer_size < 1:
            raise ValueError("buffer_size must be ≥ 1")
        if max_version_lag < 1:
            raise ValueError("max_version_lag must be ≥ 1")
        self.buffer_size = buffer_size
        self.staleness_alpha = staleness_alpha
        # ring eviction: a commit's stack set opened at version v is
        # evictable from a FULL ring once the server version has advanced by
        # max_version_lag — a commit lagging a full version (default lag 1)
        # is abandoned rather than wedging deeper (depth > 2) rings.
        self.max_version_lag = max_version_lag
        self._version = 0
        self._snapshots: Dict[int, Any] = {}  # version → global lora
        # in-flight: (arrival_time, client, launch_version)
        self._inflight: List[Tuple[float, ClientInfo, int]] = []

    def run_round(self, round_id: int, train_fn: TrainFn, global_lora: Any
                  ) -> RoundOutcome:
        pol = self.policy
        self._ensure_spec(global_lora)
        opened = self.clock.now()
        self._snapshots[self._version] = global_lora
        self.rec.event("commit.open", cat="fedsrv", round=round_id,
                       version=self._version, inflight=len(self._inflight))

        # launch newly sampled clients at the current version
        participants = self.registry.sample_round(round_id, pol.participation,
                                                  max(1, pol.min_quorum))
        dropped_out: List[int] = []
        busy = {c.client_id for _, c, _ in self._inflight}
        launched: List[int] = []
        for c in participants:
            if c.client_id in busy:
                continue  # still running an older version's assignment
            if self.stragglers.dropped(round_id, c):
                dropped_out.append(c.client_id)
                self.rec.event("client.dropout", cat="fedsrv", round=round_id,
                               client=c.client_id)
                continue
            t = opened + self.stragglers.latency(round_id, c)
            self._inflight.append((t, c, self._version))
            launched.append(c.client_id)
        self._inflight.sort(key=lambda e: (e[0], e[1].client_id))

        # commit the earliest buffer_size arrivals
        take = min(self.buffer_size, len(self._inflight))
        if take == 0:
            # every sampled client dropped out and nothing is in flight:
            # empty commit — keep the version, let the trainer keep its global
            # (mirrors the sync coordinator's zero-delivery round).
            logger.warning("commit=%d: no clients in flight; empty commit",
                           round_id)
            return RoundOutcome(
                round_id=round_id,
                sampled=[c.client_id for c in participants],
                delivered=[], dropped_out=dropped_out, dropped_deadline=[],
                weights=None, opened_at=opened, closed_at=self.clock.now(),
                comm=self.ledger.round_totals(round_id))
        batch, self._inflight = self._inflight[:take], self._inflight[take:]
        # versions are the FedBuff ring's monotonic scale: this commit's set
        # expires max_version_lag versions from now
        self._open_sink([c.client_id for _, c, _ in batch], round_id,
                        deadline=self._version + self.max_version_lag,
                        now=self._version)

        delivered: List[Delivery] = []
        quarantined: List[Tuple[int, str]] = []
        retries = 0
        with self.rec.span("commit.collect", cat="fedsrv", round=round_id,
                           version=self._version, take=take):
            for t, c, v in batch:
                start = self._snapshots[v]
                self._record_downlink(start, round_id, c.client_id)
                with self.rec.span("client.train", cat="fedsrv",
                                   round=round_id, client=c.client_id,
                                   launch_version=v):
                    lora_c = train_fn(c, start, round_id)
                n = (float(c.num_examples) if pol.weighting == "examples"
                     else 1.0)
                res = self._uplink(
                    lora_c, round_id, c.client_id,
                    weight=n * (1.0 + (self._version - v))
                    ** (-self.staleness_alpha))
                self.clock.advance_to(t)  # sim-time parity (see sync loop)
                retries += res.retries
                if res.ok:
                    delivered.append(Delivery(client=c, lora=res.tree,
                                              launched_at=t, arrived_at=t,
                                              staleness=self._version - v))
                else:
                    quarantined.append((c.client_id, res.reason))
        delivered.sort(key=lambda d: d.client.client_id)

        # graceful degradation: every buffered delivery was quarantined —
        # keep the version (nothing committed), evict the opened set, and
        # let the trainer carry the global forward.
        degraded = not delivered
        if degraded:
            self._evict_sink_round(round_id, "degraded: commit buffer fully "
                                   "quarantined")
            if self.rec.enabled:
                self.rec.counter("round.degraded").inc()
            self.rec.event("round.degraded", cat="fedsrv", round=round_id,
                           delivered=0, quorum=take,
                           quarantined=len(quarantined))
            logger.warning(
                "commit=%d DEGRADED: 0/%d deliveries after quarantine — "
                "version held at %d", round_id, take, self._version)
            return RoundOutcome(
                round_id=round_id,
                sampled=[c.client_id for c in participants],
                delivered=[], dropped_out=dropped_out, dropped_deadline=[],
                weights=None, opened_at=opened, closed_at=self.clock.now(),
                comm=self.ledger.round_totals(round_id),
                quarantined=quarantined, degraded=True, retries=retries)

        # weights: example count × staleness discount, renormalized — the
        # weighted residual identity stays exact for ANY normalized weights.
        raw = []
        for d in delivered:
            n = (d.client.num_examples if pol.weighting == "examples" else 1.0)
            raw.append(n * (1.0 + d.staleness) ** (-self.staleness_alpha))
        total = sum(raw)
        weights: Optional[List[float]] = [x / total for x in raw]

        self._version += 1
        # snapshots older than every in-flight launch can be freed
        live = {v for _, _, v in self._inflight} | {self._version}
        for v in list(self._snapshots):
            if v not in live and v != self._version - 1:
                del self._snapshots[v]

        outcome = RoundOutcome(
            round_id=round_id, sampled=[c.client_id for c in participants],
            delivered=delivered, dropped_out=dropped_out,
            dropped_deadline=[], weights=weights, opened_at=opened,
            closed_at=self.clock.now(),
            comm=self.ledger.round_totals(round_id),
            quarantined=quarantined, retries=retries)
        stale = [d.staleness for d in delivered]
        if self.rec.enabled:
            self.rec.hist("fedsrv.commit_staleness").observe(
                max(stale, default=0))
            self.rec.round_set(round_id, sampled=len(participants),
                               delivered=len(delivered),
                               dropped_out=len(dropped_out),
                               quarantined=len(quarantined),
                               retries=retries,
                               launched=len(launched),
                               inflight=len(self._inflight),
                               version=self._version,
                               staleness_max=max(stale, default=0),
                               staleness_mean=round(
                                   sum(stale) / max(len(stale), 1), 3),
                               opened_at=round(opened, 3),
                               closed_at=round(self.clock.now(), 3))
        logger.info(
            "commit=%d version=%d launched=%d committed=%d inflight=%d "
            "max_staleness=%d", round_id, self._version, len(launched),
            len(delivered), len(self._inflight),
            max((d.staleness for d in delivered), default=0))
        return outcome
