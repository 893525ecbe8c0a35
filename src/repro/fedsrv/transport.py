"""Adapter transport: payload serialization, uplink quantization, bytes ledger.

The coordinator never hands raw trees between "client" and "server": every
adapter crosses through :class:`AdapterCodec`, so uplink quantization (fp16 /
int8) actually changes the numbers the server aggregates — exactness claims
are then made about what was *transmitted*, as in a real deployment.

The :class:`BytesLedger` records every payload (params + bytes, per round and
direction) and can be reconciled against the analytic per-round parameter
counts of ``core/comm.py::round_comm_params`` — the ledger is the measured
twin of that closed-form accounting. Uplinks that never reach the close —
quarantined by validation, or dropped by the ring as stale/replayed/duplicate
— are recorded under their own ``quarantined``/``dropped`` directions, so
``reconcile()`` stays honest under faults: only *delivered* bytes count as
uplink/downlink traffic.

The defended ingest path: :meth:`AdapterCodec.decode_into` (and
:meth:`~AdapterCodec.decode`) validate every decoded payload against the
codec's :class:`ValidationPolicy` — declared-shape-vs-wire-length at the
decode boundary, per-leaf shape check against the registered adapter spec
(:meth:`AdapterCodec.register_spec`), a finite check, and an optional
∞-norm outlier limit. Failures raise a typed :class:`TransportError` with
(round, client) context so the coordinator can QUARANTINE the uplink — the
lane stays zero and the engine's zero-weight masking excludes it exactly —
instead of scattering poison into the donated device stacks.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.obs import NULL
from repro.util.tree import flatten_with_paths, unflatten_from_paths

CODECS = ("none", "fp16", "int8")


class TransportError(RuntimeError):
    """A payload failed decode/validation — quarantine it (round/client
    context travels with the error; ``reason`` is the short metric label)."""

    def __init__(self, message: str, *, round_id=None, client_id=None,
                 reason: str = "corrupt"):
        super().__init__(
            f"round={round_id} client={client_id} [{reason}]: {message}")
        self.round_id = round_id
        self.client_id = client_id
        self.reason = reason


class TransientTransportError(TransportError):
    """A decode failure worth retrying (the coordinator backs off on its
    SimClock and re-attempts up to its retry budget)."""


class StaleUplinkError(TransportError):
    """The payload's ADDRESS is bad — replayed/unknown round_id, or a
    duplicate (client, round) lane — so the ring refused it. Dropped, not
    quarantined: the bytes never threatened a live lane."""


@dataclass(frozen=True)
class EncodedTensor:
    data: np.ndarray            # fp32 / fp16 / int8 wire storage
    scale: Optional[float]      # int8 dequant scale (absmax/127), else None
    # declared logical shape; None → data.shape. A corrupted/truncated wire
    # buffer keeps its declared shape, so the decode boundary can detect the
    # length mismatch instead of mis-reshaping (fedsrv/faults.py exercises
    # this).
    shape: Optional[Tuple[int, ...]] = None

    @property
    def declared_shape(self) -> Tuple[int, ...]:
        return self.shape if self.shape is not None else tuple(self.data.shape)

    @property
    def nbytes(self) -> int:
        return int(self.data.nbytes) + (4 if self.scale is not None else 0)

    @property
    def num_params(self) -> int:
        return int(self.data.size)


@dataclass(frozen=True)
class Payload:
    """One serialized adapter tree in flight (uplink delta or downlink global).

    ``rank`` is the DECLARED LoRA rank of a ragged (hetero) uplink: the
    factor leaves on the wire are the client's true rank-r tensors, and the
    defended decode pads them to the registered r_max spec with zeros before
    scatter. ``None`` means uniform-rank (legacy wire frames parse to None).
    """

    round_id: int
    client_id: int
    direction: str              # "uplink" | "downlink"
    codec: str
    tensors: Dict[str, EncodedTensor]
    rank: Optional[int] = None  # declared ragged rank (hetero), None=uniform

    @property
    def num_params(self) -> int:
        return sum(t.num_params for t in self.tensors.values())

    @property
    def nbytes(self) -> int:
        return sum(t.nbytes for t in self.tensors.values())


@dataclass(frozen=True)
class ValidationPolicy:
    """What the defended decode checks (quarantine on failure).

    ``max_norm`` is the ∞-norm outlier limit per decoded leaf (byzantine-
    scaled uplinks); 0 disables it. ``check_spec`` only bites once an
    adapter spec is registered via :meth:`AdapterCodec.register_spec`.
    """

    enabled: bool = True
    check_finite: bool = True
    check_spec: bool = True
    max_norm: float = 0.0


class AdapterCodec:
    """Encode/decode adapter trees with optional uplink factor quantization.

    * ``none`` — fp32 passthrough (4 B/param).
    * ``fp16`` — half-precision factors (2 B/param), decode upcasts to fp32.
    * ``int8`` — per-tensor symmetric absmax quantization (1 B/param + one
      fp32 scale per tensor).

    Decoding is DEFENDED (see module docstring): wire-length-vs-declared-
    shape at the decode boundary, then the :class:`ValidationPolicy` checks.
    All failures raise :class:`TransportError` (or a subclass) carrying the
    payload's (round, client) identity.
    """

    def __init__(self, quantize: str = "none", recorder=None,
                 validation: Optional[ValidationPolicy] = None):
        if quantize not in CODECS:
            raise ValueError(f"quantize must be one of {CODECS}, got {quantize!r}")
        self.quantize = quantize
        # obs recorder (repro.obs): encode/decode spans + per-direction byte
        # counters. The coordinator propagates its own recorder here.
        self.rec = recorder if recorder is not None else NULL
        self.validation = validation if validation is not None \
            else ValidationPolicy()
        # path → expected decoded leaf shape (register_spec)
        self.spec: Optional[Dict[str, Tuple[int, ...]]] = None
        # cumulative ingest throughput (decode_into only): wire bytes landed
        # in the sink over wall time since the first ingest, surfaced as the
        # uplink.ingest_bytes_per_s gauge
        self._ingest_bytes = 0
        self._ingest_t0: Optional[int] = None
        # the HTTP server decodes uplinks from many handler threads at once;
        # the throughput accumulator is the only read-modify-write shared
        # state in the codec, so it gets its own lock
        self._ingest_lock = threading.Lock()

    def register_spec(self, tree: Any) -> None:
        """Pin the expected adapter structure (path → shape). Decoded uplinks
        must match it exactly — extra/missing leaves or shape drift are
        quarantined, never scattered into the ``(C_max, …)`` stacks."""
        self.spec = {path: tuple(np.shape(leaf))
                     for path, leaf in flatten_with_paths(tree).items()}

    def _encode_leaf(self, x, codec: str) -> EncodedTensor:
        arr = np.asarray(x, dtype=np.float32)
        if codec == "none":
            return EncodedTensor(arr, None)
        if codec == "fp16":
            return EncodedTensor(arr.astype(np.float16), None)
        absmax = float(np.max(np.abs(arr))) if arr.size else 0.0
        scale = absmax / 127.0 if absmax > 0 else 1.0
        q = np.clip(np.rint(arr / scale), -127, 127).astype(np.int8)
        return EncodedTensor(q, scale)

    def encode(self, tree: Any, *, round_id: int, client_id: int,
               direction: str = "uplink",
               rank: Optional[int] = None) -> Payload:
        codec = self.quantize if direction == "uplink" else "none"
        with self.rec.span("codec.encode", cat="transport", round=round_id,
                           client=client_id, codec=codec):
            flat = flatten_with_paths(tree)
            tensors = {path: self._encode_leaf(leaf, codec)
                       for path, leaf in flat.items()}
        payload = Payload(round_id=round_id, client_id=client_id,
                          direction=direction, codec=codec, tensors=tensors,
                          rank=None if rank is None else int(rank))
        if self.rec.enabled:
            self.rec.counter(f"transport.{direction}_bytes").inc(payload.nbytes)
            self.rec.counter(f"transport.{direction}_payloads").inc()
            if direction == "uplink":
                # each device leaf was one blocking fetch to the host
                fetched = sum(isinstance(x, jax.Array) for x in flat.values())
                if fetched:
                    self.rec.host_sync("uplink", fetched, round_id)
        return payload

    def _decode_flat(self, payload: Payload) -> Dict[str, np.ndarray]:
        """Dequantize the wire tensors; the FIRST defense line lives here:
        a wire buffer whose element count disagrees with its declared shape
        raises a typed :class:`TransportError` with (round, client) context
        — never a deep ``np.frombuffer`` crash or a silent mis-reshape."""
        flat = {}
        for path, enc in payload.tensors.items():
            declared = enc.declared_shape
            expected = int(np.prod(declared, dtype=np.int64)) if declared \
                else 1
            if int(enc.data.size) != expected:
                raise TransportError(
                    f"{path}: wire buffer has {enc.data.size} elements "
                    f"({enc.data.nbytes} B) but declares shape {declared} "
                    f"({expected} elements)",
                    round_id=payload.round_id, client_id=payload.client_id,
                    reason="bytes")
            arr = enc.data.reshape(declared)
            if enc.scale is not None:
                flat[path] = arr.astype(np.float32) * enc.scale
            else:
                flat[path] = arr.astype(np.float32)
        return flat

    def _validate_flat(self, payload: Payload,
                       flat: Dict[str, np.ndarray]) -> None:
        """The ValidationPolicy stage: spec/shape, finite, ∞-norm limit."""
        v = self.validation
        if not v.enabled:
            return
        ctx = dict(round_id=payload.round_id, client_id=payload.client_id)
        spec = self.spec
        if v.check_spec and spec is not None:
            # dict-view equality is O(n) key hashing with no allocation; the
            # sorted diffs are only built to format the failure message
            if flat.keys() != spec.keys():
                missing = sorted(set(spec) - set(flat))
                extra = sorted(set(flat) - set(spec))
                raise TransportError(
                    f"adapter tree mismatch vs registered spec "
                    f"(missing={missing}, extra={extra})",
                    reason="spec", **ctx)
            r = payload.rank
            for path, arr in flat.items():
                want, got = spec[path], tuple(arr.shape)
                ax = self._rank_axis(path) if r is not None else None
                if ax is None:
                    if got != want:
                        raise TransportError(
                            f"{path}: shape {got} != registered {want}",
                            reason="shape", **ctx)
                    continue
                # Ragged (hetero) uplink: the factor's rank axis carries the
                # client's declared rank — zero-padding to the registered
                # r_max happens at decode, AFTER validation. Already-padded
                # tensors pass too (masked columns contribute exactly zero).
                r_max = want[len(want) + ax]
                if not 1 <= r <= r_max:
                    raise TransportError(
                        f"{path}: declared rank {r} outside [1, {r_max}] "
                        f"(registered r_max)", reason="rank", **ctx)
                if len(got) != len(want) or any(
                        g != w for i, (g, w) in enumerate(zip(got, want))
                        if i != len(want) + ax):
                    raise TransportError(
                        f"{path}: shape {got} != registered {want}",
                        reason="shape", **ctx)
                if got[ax] not in (r, r_max):
                    raise TransportError(
                        f"{path}: rank axis has {got[ax]} columns, matching "
                        f"neither declared rank {r} nor registered r_max "
                        f"{r_max}", reason="rank", **ctx)
        check_finite, max_norm = v.check_finite, v.max_norm
        total = 0.0
        for path, arr in flat.items():
            # one float64 reduction per leaf, one finite check per payload:
            # any NaN/±Inf propagates into the running sum (cancelling ±Inf
            # makes NaN), and float64 accumulation of finite fp32/fp16
            # leaves cannot overflow — no O(size) bool temp, no per-leaf
            # isfinite dispatch
            if check_finite:
                total += float(arr.sum(dtype=np.float64))
            if max_norm > 0 and arr.size \
                    and float(np.max(np.abs(arr))) > max_norm:
                raise TransportError(
                    f"{path}: ∞-norm {float(np.max(np.abs(arr))):.3g} "
                    f"exceeds limit {max_norm:g}", reason="norm", **ctx)
        if check_finite and not np.isfinite(total):
            # quarantine slow path: re-scan to name the offending leaf
            for path, arr in flat.items():
                if not np.all(np.isfinite(arr)):
                    raise TransportError(
                        f"{path}: non-finite values in payload",
                        reason="nonfinite", **ctx)
            raise TransportError("non-finite values in payload",
                                 reason="nonfinite", **ctx)

    @staticmethod
    def _rank_axis(path: str) -> Optional[int]:
        """Which axis of a factor leaf is the LoRA rank axis: a is (…, m, r)
        → −1, b is (…, r, n) → −2. Non-factor leaves return None (they must
        match the registered spec exactly even on ragged uplinks)."""
        leaf = path.rsplit("/", 1)[-1]
        if leaf == "a":
            return -1
        if leaf == "b":
            return -2
        return None

    def _pad_ragged(self, payload: Payload,
                    flat: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """Zero-pad a VALIDATED ragged payload's factor leaves up to the
        registered r_max spec shapes. The engine's per-lane rank mask zeroes
        exactly the padded columns, so padding is the semantic identity
        (tests/test_engine_hetero.py proves masking == padding bitwise)."""
        if payload.rank is None or self.spec is None:
            return flat
        out = {}
        for path, arr in flat.items():
            want = self.spec.get(path)
            if want is not None and tuple(arr.shape) != want:
                arr = np.pad(arr, [(0, w - g) for g, w in
                                   zip(arr.shape, want)])
            out[path] = arr
        return out

    def decode(self, payload: Payload) -> Any:
        flat = self._decode_flat(payload)
        self._validate_flat(payload, flat)
        return unflatten_from_paths(self._pad_ragged(payload, flat))

    def decode_into(self, payload: Payload, buffers: Any, *,
                    weight: Optional[float] = None) -> Any:
        """Decode straight into a streaming sink (core/engine.RoundBuffers).

        The dequantized leaves are scattered into the sink's preallocated
        ``(C_max, …)`` device stacks at the payload's client lane as the
        delivery arrives — the round close reads the stacks, so there is no
        burst of stacking work at the deadline. The payload's ``round_id``
        selects the stack SET in the sink's double-buffer ring, so round
        N+1 uplinks stream into a fresh set while round N's close still owns
        the previous one. The sink aggregates exactly what was transmitted
        (quantization included), like :meth:`decode`. Also returns the host
        tree (one decode, shared) so the coordinator's ``Delivery.lora``
        stays inspectable by diagnostics and tests.

        Defended: validation runs BEFORE the scatter, so a quarantined
        payload never touches a stack lane (raises
        :class:`TransportError`). A payload the ring refuses — unknown or
        already-closed/evicted round_id, duplicate (client, round) lane —
        raises :class:`StaleUplinkError` (an addressing failure: dropped,
        not quarantined).

        ``weight`` is the client's RAW aggregation weight, forwarded to the
        sink — a chunked sink folds it into the running accumulators at
        ingest (the close later normalises by the total), so stream-time and
        close-time weighting must agree (the chunked close cross-checks).
        """
        with self.rec.span("codec.decode", cat="transport",
                           round=payload.round_id, client=payload.client_id,
                           codec=payload.codec, nbytes=payload.nbytes):
            flat = self._decode_flat(payload)
            self._validate_flat(payload, flat)
            flat = self._pad_ragged(payload, flat)
            # forward the declared rank only when set, so uniform payloads
            # keep working against sinks predating the rank= kwarg
            rank_kw = {} if payload.rank is None else {"rank": payload.rank}
            try:
                landed = buffers.write_flat(payload.client_id, flat,
                                            round_id=payload.round_id,
                                            weight=weight, **rank_kw)
            except KeyError as e:
                raise StaleUplinkError(
                    f"unroutable round_id: {e}", round_id=payload.round_id,
                    client_id=payload.client_id, reason="unroutable") from e
            if not landed:
                raise StaleUplinkError(
                    "ring refused the write (stale/evicted round or "
                    "duplicate lane)", round_id=payload.round_id,
                    client_id=payload.client_id, reason="stale")
        now = time.perf_counter_ns()
        with self._ingest_lock:
            if self._ingest_t0 is None:
                self._ingest_t0 = now
            self._ingest_bytes += payload.nbytes
            ingest_bytes, t0 = self._ingest_bytes, self._ingest_t0
        if self.rec.enabled:
            elapsed_s = max((now - t0) / 1e9, 1e-9)
            self.rec.gauge("uplink.ingest_bytes_per_s").set(
                round(ingest_bytes / elapsed_s, 1))
        return unflatten_from_paths(flat)


@dataclass
class LedgerEntry:
    round_id: int
    direction: str
    client_id: int
    params: int
    nbytes: int
    codec: str
    note: str = ""


class BytesLedger:
    """Per-round communication ledger (measured params + bytes).

    Directions are open-ended: besides ``uplink``/``downlink``, faulty
    payloads are accounted under ``quarantined`` (validation rejected the
    content) and ``dropped`` (crashed mid-uplink, or the ring refused a
    replayed/duplicate address; also the downlink that fed a client who
    never delivered). ``reconcile()`` compares only the delivered
    uplink/downlink params against the analytic form — which is exactly why
    the faulty bytes must NOT hide in those buckets.
    """

    def __init__(self):
        self.entries: List[LedgerEntry] = []

    def record(self, payload: Payload, note: str = "",
               direction: Optional[str] = None) -> None:
        """Record one payload; ``direction`` overrides the payload's own
        (e.g. a quarantined uplink is recorded as ``quarantined`` — the
        bytes crossed the wire but never became aggregate input)."""
        self.entries.append(LedgerEntry(
            round_id=payload.round_id,
            direction=direction or payload.direction,
            client_id=payload.client_id, params=payload.num_params,
            nbytes=payload.nbytes, codec=payload.codec, note=note))

    def reclassify(self, round_id: int, client_id: int, direction: str,
                   new_direction: str, note: str = "") -> bool:
        """Re-bucket the latest matching entry (e.g. the downlink that fed a
        client whose uplink was then quarantined → ``dropped``). Returns
        whether a matching entry was found."""
        for e in reversed(self.entries):
            if (e.round_id == round_id and e.client_id == client_id
                    and e.direction == direction):
                e.direction = new_direction
                if note:
                    e.note = (e.note + "; " + note) if e.note else note
                return True
        return False

    def record_analytic(self, round_id: int, direction: str, params: int,
                        bytes_per_param: int = 4, client_id: int = -1,
                        note: str = "") -> None:
        """Account a payload we model analytically (e.g. the factored residual
        broadcast, whose params come from decompose.factored_residual_params)."""
        self.entries.append(LedgerEntry(
            round_id=round_id, direction=direction, client_id=client_id,
            params=int(params), nbytes=int(params) * bytes_per_param,
            codec="none", note=note))

    def record_raw(self, round_id: int, direction: str, nbytes: int,
                   client_id: int = -1, note: str = "") -> None:
        """Account raw non-payload octets (params=0): HTTP request line +
        headers + wire frame envelope. These bytes crossed the socket but
        carry no adapter parameters, so they live under their own direction
        (``http_overhead``) — folding them into ``uplink_bytes`` would
        silently break the bytes-per-param story ``reconcile()`` audits."""
        self.entries.append(LedgerEntry(
            round_id=round_id, direction=direction, client_id=client_id,
            params=0, nbytes=int(nbytes), codec="raw", note=note))

    # -- views -------------------------------------------------------------
    def round_totals(self, round_id: int) -> Dict[str, int]:
        """Per-direction ``{direction}_params``/``{direction}_bytes`` sums.
        The four uplink/downlink keys are always present (zero-filled);
        fault directions (``dropped``/``quarantined``) appear only when a
        round actually recorded them."""
        tot = {"uplink_params": 0, "uplink_bytes": 0,
               "downlink_params": 0, "downlink_bytes": 0}
        for e in self.entries:
            if e.round_id != round_id:
                continue
            kp, kb = f"{e.direction}_params", f"{e.direction}_bytes"
            tot[kp] = tot.get(kp, 0) + e.params
            tot[kb] = tot.get(kb, 0) + e.nbytes
        return tot

    def totals(self) -> Dict[str, int]:
        rounds = {e.round_id for e in self.entries}
        out = {"uplink_params": 0, "uplink_bytes": 0,
               "downlink_params": 0, "downlink_bytes": 0}
        for r in rounds:
            for key, v in self.round_totals(r).items():
                out[key] = out.get(key, 0) + v
        return out

    def reconcile(self, round_id: int, analytic: Dict[str, int]
                  ) -> Dict[str, Any]:
        """Compare measured param counts against core/comm.py's closed form.

        analytic: the dict returned by ``round_comm_params`` (uplink/downlink
        PARAM counts for the round). Bytes are codec-dependent so only params
        are reconciled. Returns per-direction measured/analytic/match.
        """
        got = self.round_totals(round_id)
        out: Dict[str, Any] = {}
        for direction in ("uplink", "downlink"):
            measured = got[f"{direction}_params"]
            expected = int(analytic.get(direction, 0))
            out[direction] = {"measured": measured, "analytic": expected,
                              "match": measured == expected}
        out["ok"] = all(out[d]["match"] for d in ("uplink", "downlink"))
        return out

    # -- checkpoint/resume (crash-safe round state) ------------------------
    def state_dict(self) -> List[Dict[str, Any]]:
        import dataclasses as _dc
        return [_dc.asdict(e) for e in self.entries]

    def load_state(self, state: List[Dict[str, Any]]) -> None:
        self.entries = [LedgerEntry(**d) for d in state]

    def summary_lines(self) -> List[str]:
        rounds = sorted({e.round_id for e in self.entries})
        lines = [f"{'round':>5} {'up_params':>10} {'up_bytes':>10} "
                 f"{'down_params':>11} {'down_bytes':>10}"]
        for r in rounds:
            t = self.round_totals(r)
            lines.append(f"{r:>5} {t['uplink_params']:>10} {t['uplink_bytes']:>10} "
                         f"{t['downlink_params']:>11} {t['downlink_bytes']:>10}")
        t = self.totals()
        lines.append(f"{'all':>5} {t['uplink_params']:>10} {t['uplink_bytes']:>10} "
                     f"{t['downlink_params']:>11} {t['downlink_bytes']:>10}")
        return lines
