"""Fused weighted round-close: the single-dispatch stacked-client engine.

The seed trainer closed a FedEx round with a Python tree-walk over *lists* of
client adapter trees: per-leaf ``jnp.stack`` at deadline, an eager op per
factor for the mean, an eager dense ΔW_res materialisation, an eager add into
W0 — dozens of dispatches per round, each a host↔device round trip. This
module replaces that with ONE jitted program over pre-stacked client buffers,
for EVERY aggregation variant the paper studies:

* :class:`RoundBuffers` — preallocated ``(C_max, …)`` device stacks per
  adapter leaf, DOUBLE-BUFFERED: a ring of ``depth`` rotating stack sets lets
  the fedsrv transport stream round N+1 uplinks into a fresh set while round
  N's close (which owns — and donates — the previous set) is still in
  flight. Rotation rules: ``begin_round`` opens a new set (fresh zeros — the
  close program consumed the previous allocation via donation, so sets are
  never reused across rounds), ``write_flat`` routes a delivery to its
  round's set by the payload's ``round_id``, ``take`` pops the OLDEST open
  round and hands its stacks to the close program. At most ``depth`` rounds
  may be open; exceeding it is an error, not a silent overwrite — UNLESS the
  caller gave open rounds a ``deadline`` and passes ``now`` when opening the
  next one: expired rounds are then EVICTED (dropped with a warning, late
  uplinks for them discarded) instead of wedging the ring. ``depth > 2``
  plus per-round deadlines is the FedBuff regime: commits lagging
  ``max_version_lag`` or more versions are evicted rather than blocking new
  rounds.
* :class:`DeferredDivergence` — the §6 divergence metric leaves the close as
  a DEVICE scalar; the host sync (``float(...)``, a blocking device→host
  transfer) happens only when the caller resolves the handle, which the
  trainer does at the NEXT round boundary. Dispatching the close therefore
  returns immediately, and the ring's round-N+1 uplink decoding genuinely
  overlaps the round-N close on accelerators. The handle quacks like a float
  (comparisons, arithmetic, ``np.asarray``) — any numeric use resolves it.
* :func:`make_close_fn` / :class:`RoundCloseEngine` — the fused close for all
  engine methods, each one jitted program with W0 leaves and client stacks
  donated (``donate_argnums``) so XLA updates them in place:

  - ``fedex`` — weighted factor means + the exact residual fold (Eq. 11–14).
  - ``fedex_svd`` — the rank-r' truncated close (Eq. 15–16): the
    Eckart–Young-optimal truncation is computed from the STACKED FACTORS via
    two (C·r)×(C·r) Gram eigendecompositions plus one small SVD
    (:func:`factored_truncated_residual`) — the dense m×n residual that the
    eager ``fedex_svd_aggregate`` hands to ``jnp.linalg.svd`` never exists.
  - ``reinit`` (§6 Table 5) — the full ideal update Σw_c·a_c b_c folds into
    W0 (the signed product kernel); fresh adapters are drawn host-side with
    the same deterministic fold-in as ``aggregation.reinit_adapters``.
  - ``keep_local`` (§6 Table 5) — per-client residuals Σw_j·a_j b_j − a_i b_i
    fold into every delivered client's OWN W0 in one pass over stacked
    per-lane W0 buffers (the per-client kernel: per-lane sign vectors
    w − e_i without C separate passes).

Backends: ``jnp`` composes the operators of core/aggregation.py inside the
jit (the mathematical ground truth — on CPU XLA fuses the residual+fold so
nothing extra hits memory); ``pallas`` routes the folds through the
kernels/fedex_residual (+ product/per-client variants) and kernels/factor_mean
tiled kernels, which never materialise a dense m×n residual in HBM (the TPU
hot path). ``auto`` picks pallas on TPU, jnp elsewhere. The svd close's small
Gram eigendecomposition/SVD stays in jnp on EITHER backend (LAPACK / XLA
custom calls on (C·r)×(C·r) matrices — there is nothing to tile); only its
rank-r' fold goes through the product kernel on pallas.

Numerics contract: the uniform full-participation ``fedex`` / ``reinit`` /
``keep_local`` closes are **bitwise identical to the jitted composition** of
the core/aggregation.py operators (same op sequence, same XLA program). The
historical *eager* list path differs from any fused program by ≤2 ulp where
XLA contracts mul+add into FMA — asserted in tests/test_engine.py. Weighted
and ragged rounds hold the exact residual identity to tight float32
tolerance. The ``fedex_svd`` close matches the dense Eckart–Young oracle to
~1e-5 relative (Gram squaring halves the attainable precision; documented
and asserted in tests/test_engine_methods.py).

The divergence metric (paper §6) is computed WITHOUT materialising the dense
deviation: dev = Σu_c·a_c b_c − ā b̄ = Σ_c u_c·a_c (b_c − b̄) factors as L@R
with L = [u_0·a_0 … u_{C-1}·a_{C-1}] and R = [b_0 − b̄; …], and ‖L@R‖²_F =
Σ_{ij} (LᵀL)_{ij}·(R Rᵀ)_{ij} — two C·r × C·r Grams instead of an m×n
deviation matrix. The same factorisation feeds the svd close. Cancellation in
the Gram sum gives the metric an absolute noise floor around 1e-6 when
clients have barely diverged (it is exact at any magnitude the §6 analysis
cares about).
"""

from __future__ import annotations

import functools
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation as agg
from repro.obs import NULL
from repro.util.logging import get_logger
from repro.util.tree import flatten_with_paths, unflatten_from_paths

logger = get_logger("engine")

Params = Dict[str, Any]

ENGINE_METHODS = ("fedex", "fedex_svd", "reinit", "keep_local", "hetero")


class DeferredDivergence:
    """§6 divergence as a device scalar with the host sync deferred.

    The close program computes the divergence on device; wrapping it here
    instead of calling ``float()`` keeps the close dispatch ASYNCHRONOUS —
    the trainer resolves the handle at the next round boundary, so the
    round-N close overlaps round-N+1 uplink decoding (the whole point of the
    :class:`RoundBuffers` ring). Any numeric use (comparison, arithmetic,
    ``np.asarray``, ``float``) resolves the handle — i.e. blocks on the
    device value — and caches the result.
    """

    __slots__ = ("_raw", "_value", "round_id", "_recorder")

    def __init__(self, raw, round_id=None, recorder=None):
        self._raw = raw
        self._value: Optional[float] = None
        self.round_id = round_id
        # obs: resolution is the close's block-until-ready — record it as its
        # own span so a premature host sync is visible in the trace
        self._recorder = recorder

    @property
    def resolved(self) -> bool:
        """True once the host sync has happened (no device value pending)."""
        return self._value is not None

    @property
    def raw(self):
        """The unresolved device scalar (None after resolution)."""
        return self._raw

    def resolve(self) -> float:
        """Block on the device value (the ONLY host sync) and cache it."""
        if self._value is None:
            rec = self._recorder
            if rec is not None and rec.enabled:
                t0 = time.perf_counter_ns()
                with rec.span("divergence.resolve", cat="engine",
                              round=self.round_id):
                    self._value = float(self._raw)
                block_us = (time.perf_counter_ns() - t0) / 1e3
                rec.host_sync("divergence", 1, self.round_id)
                if self.round_id is not None:
                    rec.round_set(self.round_id,
                                  close_block_us=round(block_us, 1),
                                  divergence=self._value)
            else:
                self._value = float(self._raw)
            self._raw = None  # drop the device reference
        return self._value

    # -- float duck-typing: any numeric use resolves ------------------------
    def __float__(self) -> float:
        return self.resolve()

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.resolve(), dtype=dtype)

    def __lt__(self, other):
        return self.resolve() < other

    def __le__(self, other):
        return self.resolve() <= other

    def __gt__(self, other):
        return self.resolve() > other

    def __ge__(self, other):
        return self.resolve() >= other

    def __eq__(self, other):
        if isinstance(other, DeferredDivergence):
            return self.resolve() == other.resolve()
        return self.resolve() == other

    def __ne__(self, other):
        return not self.__eq__(other)

    __hash__ = None  # mutable (resolution caches); never a dict key

    def __abs__(self):
        return abs(self.resolve())

    def __sub__(self, other):
        return self.resolve() - other

    def __rsub__(self, other):
        return other - self.resolve()

    def __add__(self, other):
        return self.resolve() + other

    __radd__ = __add__

    def __mul__(self, other):
        return self.resolve() * other

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self.resolve() / other

    def __rtruediv__(self, other):
        return other / self.resolve()

    def __format__(self, spec):
        return format(self.resolve(), spec)

    def __repr__(self) -> str:
        if self.resolved:
            return f"DeferredDivergence({self._value!r}, resolved)"
        return f"DeferredDivergence(<device scalar>, round_id={self.round_id!r})"


def _jit_f32(fn, **jit_kwargs):
    """``jax.jit`` with every matmul traced at full f32 precision. The TPU's
    default precision rounds f32 operands to bf16, which would put a close
    outside the f32 error bound it is checked against (``core/bounds.py``);
    on the CPU f32 products are f32 either way."""
    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return jax.jit(traced, **jit_kwargs)


def _resolve_backend(backend: str) -> str:
    if backend == "auto":
        on_tpu = any(d.platform == "tpu" for d in jax.devices())
        return "pallas" if on_tpu else "jnp"
    if backend not in ("jnp", "pallas"):
        raise ValueError(f"unknown engine backend {backend!r}")
    return backend


def _tree_bytes(tree: Any) -> int:
    """Total array bytes of a (nested) container of array leaves."""
    return sum(int(getattr(x, "nbytes", 0)) for x in jax.tree.leaves(tree))


class _ProgramCache:
    """Bounded LRU of the engine's jitted close/fold programs.

    The stacked engine held exactly one program; the chunked mode multiplies
    signatures (partial fold, per-method finalize, keep_local per-chunk fold,
    the svd Gram/core/projection programs) and long-lived processes that
    rebuild engines would otherwise grow the population without bound.
    Eviction drops the least-recently-used program — it recompiles on next
    use, so correctness is unaffected — and is observable: the
    ``engine.compile_cache_size`` gauge tracks the population and the
    ``close.compile_evicted`` counter every eviction.
    """

    def __init__(self, cap: int = 16):
        if cap < 1:
            raise ValueError(f"program cache cap must be ≥ 1, got {cap}")
        self.cap = cap
        self.evictions = 0
        self._programs: "OrderedDict[Any, Any]" = OrderedDict()

    def __len__(self) -> int:
        return len(self._programs)

    def __contains__(self, key) -> bool:
        return key in self._programs

    def get(self, key, build, rec=NULL):
        """Return the cached program for ``key``, building (and possibly
        evicting the LRU entry) on a miss."""
        prog = self._programs.get(key)
        if prog is None:
            prog = build()
            self._programs[key] = prog
            while len(self._programs) > self.cap:
                old, _ = self._programs.popitem(last=False)
                self.evictions += 1
                if rec.enabled:
                    rec.counter("close.compile_evicted").inc()
                logger.info("evicted close program %r (cache cap %d)",
                            old, self.cap)
        else:
            self._programs.move_to_end(key)
        if rec.enabled:
            rec.gauge("engine.compile_cache_size").set(len(self._programs))
        return prog


# --------------------------------------------------------------------------
# factor specs: pair every lora {a, b} node with its W0 leaf in params
# --------------------------------------------------------------------------

class FactorSpec:
    """One adapted matrix: lora factor paths + the W0 leaf they update.

    ``key`` is the '/'-joined lora-tree path of the factor node; the W0 leaf
    lives at the same path in params, either as ``{key}/kernel`` (projection
    modules) or as a raw tensor (MoE expert stacks). Leading axes before the
    trailing (m, n) are scan-stacked layers / experts and batch through the
    engine unchanged.
    """

    def __init__(self, key: str, has_kernel: bool, w0_shape: Tuple[int, ...],
                 w0_dtype, a_shape: Tuple[int, ...], b_shape: Tuple[int, ...]):
        self.key = key
        self.has_kernel = has_kernel
        self.w0_shape = w0_shape
        self.w0_dtype = w0_dtype
        self.a_shape = a_shape
        self.b_shape = b_shape


def build_factor_specs(params: Params, lora: Params) -> List[FactorSpec]:
    """Walk the adapter tree against params, one spec per {a, b} node."""
    specs: List[FactorSpec] = []

    def walk(prefix: List[str], p: Any, l: Any) -> None:
        if isinstance(l, dict) and set(l.keys()) >= {"a", "b"}:
            key = "/".join(prefix)
            if isinstance(p, dict) and "kernel" in p:
                w0 = p["kernel"]
                has_kernel = True
            else:
                w0 = p  # raw tensor target (MoE experts)
                has_kernel = False
            specs.append(FactorSpec(key, has_kernel, tuple(w0.shape), w0.dtype,
                                    tuple(l["a"].shape), tuple(l["b"].shape)))
            return
        if isinstance(l, dict):
            for k in l:
                if isinstance(p, dict) and k in p:
                    walk(prefix + [k], p[k], l[k])

    walk([], params, lora)
    if not specs:
        raise ValueError("no adapter factors found — empty lora tree?")
    return specs


def _get_path(tree: Any, path: str) -> Any:
    node = tree
    for part in path.split("/"):
        node = node[part]
    return node


def _set_path(tree: Params, path: str, value: Any) -> Params:
    """Functional nested-dict update (copies only the spine)."""
    parts = path.split("/")
    out = dict(tree)
    node = out
    for p in parts[:-1]:
        node[p] = dict(node[p])
        node = node[p]
    node[parts[-1]] = value
    return out


def collect_w0_leaves(specs: Sequence[FactorSpec],
                      params: Params) -> Dict[str, jnp.ndarray]:
    """key → adapted W0 leaf (the ``kernel`` child for projection modules,
    the raw tensor for MoE expert stacks). Shared by the streaming engine and
    the mesh-mode closer (launch/mesh_train.py)."""
    return {
        s.key: (_get_path(params, s.key)["kernel"] if s.has_kernel
                else _get_path(params, s.key))
        for s in specs
    }


def fold_back_w0(specs: Sequence[FactorSpec], params: Params,
                 new_w0: Dict[str, jnp.ndarray]) -> Params:
    """Write the close's updated W0 leaves back into the params tree
    (functional spine-copy update). Inverse of :func:`collect_w0_leaves`."""
    new_params = params
    for s in specs:
        if s.has_kernel:
            node = dict(_get_path(params, s.key), kernel=new_w0[s.key])
            new_params = _set_path(new_params, s.key, node)
        else:
            new_params = _set_path(new_params, s.key, new_w0[s.key])
    return new_params


# --------------------------------------------------------------------------
# streaming round buffers (double-buffered ring)
# --------------------------------------------------------------------------

def _ring_locked(fn):
    """Serialise a RoundBuffers method on the ring's RLock. The HTTP
    federation service (fedsrv/server.py) decodes uplinks on ThreadingHTTP-
    Server worker threads, so ``write_flat`` races ``begin_round``/
    ``evict``/``take`` — decode and validation stay parallel (they happen in
    the codec, before the ring is touched); only the scatter and the round
    bookkeeping serialise. Re-entrant: ``begin_round`` evicts under its own
    lock, and single-threaded callers (the sim coordinators) pay one
    uncontended acquire per call."""
    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        with self._ring_lock:
            return fn(self, *args, **kwargs)
    return wrapper


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter(stacks, slot, leaves):
    """Write one delivery's leaves into lane ``slot`` of the donated stacks
    (in place: no copy of the full stack per arrival)."""
    return {
        p: jax.lax.dynamic_update_index_in_dim(
            stacks[p], jnp.asarray(leaves[p], jnp.float32), slot, 0)
        for p in stacks
    }


class RoundBuffers:
    """Preallocated ``(C_max, …)`` device stacks, written slot-by-slot, with a
    ``depth``-deep ring of rotating stack sets.

    The coordinator assigns each round's candidate clients to slots (client-id
    order) via :meth:`begin_round`; deliveries scatter into their round's set
    via :meth:`write_flat` (the transport passes the payload's ``round_id``
    so round N+1 uplinks can stream while round N's set awaits — or is being
    consumed by — its close); :meth:`take` pops the OLDEST open round (FIFO)
    and hands its stacks to the close program.

    Rotation / donation-safety rules:

    * every ``begin_round`` allocates a FRESH zero set — the close program
      donates (consumes) the set ``take`` handed it, so a set is never reused
      across rounds and an in-flight close can never see the next round's
      writes;
    * at most ``depth`` rounds may be open at once; opening more raises
      (never silently overwrites an un-closed round's data) — unless expired
      rounds can be evicted first, see below;
    * within a round, slot lanes are written at most once per client and
      non-delivered lanes simply stay zero (the weight mask handles them).

    Per-round deadlines / eviction (the ``depth > 2`` FedBuff regime): a
    round may be opened with a ``deadline`` on whatever monotonic scale its
    coordinator uses (sim-seconds for the sync coordinator, commit VERSIONS
    for FedBuff). When a ``begin_round`` with ``now=...`` finds all ``depth``
    sets in flight, open rounds whose deadline has passed (``deadline ≤
    now``) are EVICTED — their stacks dropped with a warning — instead of
    wedging the ring; a commit lagging ``max_version_lag`` or more versions
    behind is abandoned, not waited on. Uplinks that later arrive for an
    evicted round are discarded (``write_flat`` returns ``False``), never
    scattered into a live round's lanes. Rounds without a deadline are never
    evicted implicitly; :meth:`evict` drops one explicitly.

    :meth:`write_flat` scatters one decoded payload into its lane via a
    single jitted ``dynamic_update_index_in_dim`` program with the stack
    buffers donated, so the update is in place — no copy of the full stack
    per arrival — on every platform.
    """

    def __init__(self, lora_template: Params, c_max: int, depth: int = 2,
                 recorder=None, *, chunk: int = 0, on_chunk=None,
                 retain_chunks: bool = False):
        if c_max < 1:
            raise ValueError("c_max must be ≥ 1")
        if depth < 1:
            raise ValueError("depth must be ≥ 1")
        if chunk < 0:
            raise ValueError(f"chunk must be ≥ 0, got {chunk}")
        if chunk > 0 and on_chunk is None:
            raise ValueError("a chunked ring needs an on_chunk fold callback")
        self.c_max = c_max
        self.depth = depth
        # chunked streaming mode: rounds with more than ``chunk`` candidate
        # lanes stage uplinks in (chunk, …) host buffers; each chunk that
        # fills (and is next in SLOT order) is eagerly folded into a running
        # accumulator via ``on_chunk(acc, chunk_stacks, raw_weights, rid, k)``
        # while later uplinks keep streaming. Determinism rule: chunk k never
        # folds before chunks < k, so the fold sequence is a pure function of
        # the slot assignment — never of uplink arrival order — and two runs
        # (or a crash twin) produce bitwise-identical accumulators.
        # ``retain_chunks`` keeps folded chunks' host buffers (keep_local and
        # fedex_svd closes re-stream them); rounds that fit in one chunk take
        # the classic stacked path so small rounds keep the stacked bitwise
        # contract ("auto" semantics of FedConfig.close_chunk).
        self.chunk = chunk
        self.on_chunk = on_chunk
        self.retain_chunks = retain_chunks
        self.partial_folds = 0  # eager (mid-round) chunk folds, all rounds
        self.rec = recorder if recorder is not None else NULL
        flat = flatten_with_paths(lora_template)
        self._shapes = {p: tuple(x.shape) for p, x in flat.items()}
        # round_id → {"slots": cid→lane, "written": cid→lane, "stacks": dict,
        #             "deadline": Optional[float]}
        self._open: "OrderedDict[Any, Dict[str, Any]]" = OrderedDict()
        # recently evicted round ids (bounded): late uplinks for them are
        # dropped silently instead of raising as unroutable
        self._evicted: "OrderedDict[Any, Any]" = OrderedDict()
        # recently CLOSED (taken) round ids (bounded): a replayed uplink for
        # a round whose close already consumed its set is dropped, not a
        # KeyError — the ring remembers where the round went
        self._closed: "OrderedDict[Any, Any]" = OrderedDict()
        self.evictions = 0
        self.stale_drops = 0  # uplinks discarded for already-evicted rounds
        self.replay_drops = 0  # uplinks replayed for already-closed rounds
        self.duplicate_drops = 0  # second (client, round) write, same lane
        self._auto = 0
        # threaded ingest (fedsrv/server.py): see _ring_locked
        self._ring_lock = threading.RLock()

    def _alloc(self):
        return {p: jnp.zeros((self.c_max,) + s, jnp.float32)
                for p, s in self._shapes.items()}

    def _alloc_chunk(self):
        # chunk staging is ALWAYS host numpy (every backend): the eager fold
        # pays one host→device conversion per chunk, and partially written
        # chunks stay cheaply checkpointable (state_dict slices them out
        # without a device sync)
        return {p: np.zeros((self.chunk,) + s, np.float32)
                for p, s in self._shapes.items()}

    def _entry(self, round_id=None) -> Tuple[Any, Dict[str, Any]]:
        if not self._open:
            raise RuntimeError("no open round — begin_round() first")
        if round_id is None:
            rid = next(iter(self._open))
            return rid, self._open[rid]
        if round_id not in self._open:
            raise KeyError(f"round {round_id!r} is not open "
                           f"(open: {list(self._open)})")
        return round_id, self._open[round_id]

    # -- round lifecycle ----------------------------------------------------
    @_ring_locked
    def begin_round(self, slots: Dict[int, int], round_id=None, *,
                    deadline: Optional[float] = None,
                    now: Optional[float] = None):
        """Open a new round: ``slots`` maps client_id → lane over the round's
        candidate set. Returns the round id (auto-assigned when omitted).

        ``deadline`` (optional) marks when this round becomes evictable, on
        the caller's monotonic scale (sim-time / commit version); ``now`` is
        the current value on that scale. A full ring first evicts expired
        rounds (``deadline ≤ now``) before giving up; without ``now`` (or
        with nothing expired) a full ring still raises."""
        if len(slots) > self.c_max:
            raise ValueError(f"{len(slots)} candidates > C_max={self.c_max}")
        if any(not 0 <= s < self.c_max for s in slots.values()):
            raise ValueError(f"slot out of range in {slots}")
        if round_id is None:
            round_id = f"_auto{self._auto}"
            self._auto += 1
        if round_id in self._open:
            raise ValueError(f"round {round_id!r} is already open")
        # ring wrap: a caller legitimately reusing an old id (e.g. a round
        # counter that wrapped) gets a FRESH round — forget the stale
        # closed/evicted memory so its uplinks route to the new set. A
        # replayed uplink racing this begin_round is only droppable BEFORE
        # the id is reopened; afterwards the id names the live round again.
        self._evicted.pop(round_id, None)
        self._closed.pop(round_id, None)
        if len(self._open) >= self.depth and now is not None:
            for rid in [r for r, e in self._open.items()
                        if e["deadline"] is not None and e["deadline"] <= now]:
                self.evict(rid, reason=f"deadline {self._open[rid]['deadline']}"
                                       f" ≤ now {now}")
        if len(self._open) >= self.depth:
            raise RuntimeError(
                f"all {self.depth} buffer sets are in flight (open rounds: "
                f"{list(self._open)}) — take() the oldest before opening "
                "another, or give open rounds a deadline so a full ring can "
                "evict them")
        # "auto" chunking contract: a round whose candidate set fits in one
        # chunk takes the classic stacked path (same program, same bitwise
        # behaviour as a chunk=0 ring); larger rounds stream in chunks
        chunked = 0 < self.chunk < len(slots)
        entry: Dict[str, Any] = {"slots": dict(slots), "written": {},
                                 "deadline": deadline, "chunked": chunked}
        if chunked:
            num_chunks = max(slots.values()) // self.chunk + 1
            expected = [0] * num_chunks
            for s in slots.values():
                expected[s // self.chunk] += 1
            entry.update(
                stacks=None, chunks={}, retained={}, acc=None,
                w=np.zeros(num_chunks * self.chunk, np.float32),
                # per-slot TRUE adapter ranks (hetero rounds): −1 = full rank
                # (unmasked — the non-hetero default), set by write_flat's
                # ``rank`` and snapshotted with the round state so a resumed
                # twin replays the same masked folds
                ranks=np.full(num_chunks * self.chunk, -1, np.int32),
                next_chunk=0, num_chunks=num_chunks, expected=expected,
                filled=[0] * num_chunks, eager_folds=0)
        else:
            with self.rec.span("ring.alloc", cat="ring", round=round_id):
                entry["stacks"] = self._alloc()
        self._open[round_id] = entry
        if self.rec.enabled:
            self.rec.event("ring.begin", cat="ring", round=round_id,
                           lanes=len(slots), deadline=deadline,
                           chunked=chunked)
            self.rec.gauge("ring.occupancy").set(len(self._open))
        return round_id

    @_ring_locked
    def evict(self, round_id, reason: str = "explicit") -> Dict[int, int]:
        """Drop an open round WITHOUT closing it: its stacks are discarded and
        any late uplink for it will be dropped (not an error). Returns the
        evicted round's delivered {client_id: lane} map for accounting."""
        rid, e = self._entry(round_id)
        del self._open[rid]
        self._evicted[rid] = reason
        while len(self._evicted) > 64:  # bounded memory of evicted ids
            self._evicted.popitem(last=False)
        self.evictions += 1
        if self.rec.enabled:
            self.rec.counter("ring.evictions").inc()
            self.rec.event("ring.evict", cat="ring", round=rid, reason=reason,
                           delivered=len(e["written"]), lanes=len(e["slots"]))
            self.rec.gauge("ring.occupancy").set(len(self._open))
        logger.warning("evicted round %r (%s): %d/%d lanes delivered — "
                       "its uplinks are discarded", rid, reason,
                       len(e["written"]), len(e["slots"]))
        return dict(e["written"])

    @_ring_locked
    def write_flat(self, client_id: int, flat: Dict[str, Any],
                   round_id=None, *, weight: Optional[float] = None,
                   rank: Optional[int] = None) -> bool:
        """Scatter one client's decoded adapter leaves into its lane.

        ``round_id=None`` routes to the oldest open round that has a lane for
        this client (single-open callers never need to pass it). Returns
        ``True`` when the write landed; a write addressed to an EVICTED round
        is dropped (returns ``False``) — the uplink lost its race against the
        ring's deadline and must not scatter into a live round's lanes.
        The eviction-drop guarantee needs the EXPLICIT ``round_id``: with
        ``None`` there is no payload identity to check against the evicted
        set, so a late uplink could land in a newer open round that also has
        a lane for this client. Any caller that evicts (the coordinators, via
        ``decode_into``) must route by the payload's round_id — they do.

        ``weight`` is this uplink's RAW (unnormalised) aggregation weight —
        chunked rounds fold it into the running accumulators at ingest, so
        the caller must stream the same weighting it will close with (the
        close cross-checks and raises on a mismatch). Defaults to 1.0
        (uniform); stacked rounds ignore it (they weight at close time).

        ``rank`` is this uplink's TRUE adapter rank (hetero rounds stream
        rank-rᵢ payloads zero-padded to the template r_max): chunked rounds
        record it per slot so the eager partial folds mask the padded
        columns, and it rides in ``state_dict`` for crash-safe resume.
        ``None`` (every non-hetero caller) means full rank."""
        if round_id is None:
            for rid, e in self._open.items():
                if client_id in e["slots"]:
                    round_id = rid
                    break
            else:
                raise KeyError(
                    f"client {client_id} has no lane in any open round "
                    f"(open: {list(self._open)}) — stale uplink from an "
                    "already-closed round?")
        if round_id in self._evicted and round_id not in self._open:
            self.stale_drops += 1
            if self.rec.enabled:
                self.rec.counter("ring.stale_drops").inc()
                self.rec.event("ring.stale_drop", cat="ring", round=round_id,
                               client=client_id)
            logger.warning("dropping uplink from client %d for evicted "
                           "round %r", client_id, round_id)
            return False
        if round_id in self._closed and round_id not in self._open:
            # a replayed uplink for a round whose close already consumed its
            # set — drop it; it must never scatter into a live round's lanes
            self.replay_drops += 1
            if self.rec.enabled:
                self.rec.counter("ring.replay_drops").inc()
                self.rec.event("ring.replay_drop", cat="ring", round=round_id,
                               client=client_id)
            logger.warning("dropping replayed uplink from client %d for "
                           "closed round %r", client_id, round_id)
            return False
        _, e = self._entry(round_id)
        if client_id in e["written"]:
            # duplicate (client, round): the lane was already written this
            # round — the first copy wins, the duplicate is dropped
            self.duplicate_drops += 1
            if self.rec.enabled:
                self.rec.counter("ring.duplicate_drops").inc()
                self.rec.event("ring.duplicate_drop", cat="ring",
                               round=round_id, client=client_id)
            logger.warning("dropping duplicate uplink from client %d for "
                           "round %r", client_id, round_id)
            return False
        slot = e["slots"][client_id]
        # obs: the ring.write span is the overlap invariant's witness — round
        # N+1 write intervals must land inside round N's close window
        with self.rec.span("ring.write", cat="ring", round=round_id,
                           client=client_id):
            if e["chunked"]:
                k, row = divmod(slot, self.chunk)
                buf = e["chunks"].get(k)
                if buf is None:
                    buf = e["chunks"].setdefault(k, self._alloc_chunk())
                for p in self._shapes:
                    buf[p][row] = np.asarray(flat[p], np.float32)
                e["w"][slot] = np.float32(1.0 if weight is None else weight)
                if rank is not None:
                    e["ranks"][slot] = np.int32(rank)
                e["filled"][k] += 1
            else:
                leaves = {p: flat[p] for p in self._shapes}
                # the scatter dispatch, apart from the host work around it;
                # ``open`` is the ring's open rounds, the sets it may wait on
                with self.rec.span("ring.scatter", cat="ring", round=round_id,
                                   open=len(self._open)):
                    e["stacks"] = _scatter(e["stacks"], jnp.int32(slot),
                                           leaves)
        e["written"][client_id] = slot
        if e["chunked"]:
            self._cascade(round_id, e)
        return True

    def write(self, client_id: int, lora_tree: Params, round_id=None, *,
              weight: Optional[float] = None,
              rank: Optional[int] = None) -> bool:
        return self.write_flat(client_id, flatten_with_paths(lora_tree),
                               round_id, weight=weight, rank=rank)

    @_ring_locked
    def chunk_ranks(self, round_id, k: int) -> Optional[np.ndarray]:
        """Chunk k's per-slot rank vector (−1 = full rank), or None for a
        stacked round. Read by the hetero partial fold to mask padded
        columns at ingest; re-entrant under the ring lock (the fold cascade
        calls back into the engine while holding it)."""
        _, e = self._entry(round_id)
        if not e["chunked"]:
            return None
        return np.asarray(e["ranks"][k * self.chunk:(k + 1) * self.chunk])

    # -- chunked fold cascade ----------------------------------------------
    def _cascade(self, rid, e) -> None:
        """Eagerly fold every complete chunk that is NEXT IN SLOT ORDER.

        A full chunk k only folds once chunks < k have folded — the fold
        sequence (and therefore the accumulator value) is a pure function of
        the slot assignment and the delivered payloads, never of arrival
        order. A full out-of-order chunk simply waits its turn."""
        while (e["next_chunk"] < e["num_chunks"]
               and e["filled"][e["next_chunk"]]
               == e["expected"][e["next_chunk"]]):
            self._fold_next(rid, e, eager=True)

    def _fold_next(self, rid, e, *, eager: bool) -> None:
        k = e["next_chunk"]
        buf = e["chunks"].pop(k, None)
        if buf is None:
            # nothing of this chunk was delivered: zero rows with zero
            # weights fold as an exact no-op, keeping every fold the same
            # (chunk, …) program signature
            buf = self._alloc_chunk()
        w = np.asarray(e["w"][k * self.chunk:(k + 1) * self.chunk])
        t0 = time.perf_counter_ns()
        # eager folds are the chunked path's overlap witnesses (the obs
        # report joins them with ring.write spans); close-time flushes of
        # trailing partial chunks use their own span name
        span = "close.partial_fold" if eager else "close.chunk_flush"
        with self.rec.span(span, cat="engine", round=rid, chunk=k):
            e["acc"] = self.on_chunk(e["acc"], buf, w, rid, k)
        if self.retain_chunks:
            e["retained"][k] = buf
        e["next_chunk"] = k + 1
        if eager:
            e["eager_folds"] += 1
            self.partial_folds += 1
        if self.rec.enabled:
            self.rec.hist("close.chunk_flush_us").observe(
                (time.perf_counter_ns() - t0) / 1e3)
            if eager:
                self.rec.counter("close.partial_folds").inc()

    @_ring_locked
    def is_chunked(self, round_id=None) -> bool:
        return bool(self._entry(round_id)[1]["chunked"])

    # -- views --------------------------------------------------------------
    @property
    @_ring_locked
    def open_rounds(self) -> List[Any]:
        return list(self._open)

    @property
    @_ring_locked
    def delivered(self) -> Dict[int, int]:
        """client_id → slot written in the OLDEST open round (next to close)."""
        return dict(self._entry()[1]["written"])

    @_ring_locked
    def delivered_in(self, round_id=None) -> Dict[int, int]:
        return dict(self._entry(round_id)[1]["written"])

    @_ring_locked
    def lanes(self, round_id=None) -> Dict[int, int]:
        """client_id → lane for ALL of a round's candidates (delivered or not)."""
        return dict(self._entry(round_id)[1]["slots"])

    @_ring_locked
    def slot_of(self, client_id: int, round_id=None) -> int:
        return self._entry(round_id)[1]["slots"][client_id]

    @_ring_locked
    def take(self, round_id=None) -> Dict[str, jnp.ndarray]:
        """Pop the oldest (or named) open round; hand its stacks to the close
        program (donated there — this set is gone for good)."""
        rid, e = self._entry(round_id)
        if e["chunked"]:
            raise RuntimeError(f"round {rid!r} streams in chunks — close it "
                               "via take_chunked()")
        del self._open[rid]
        self._closed[rid] = True
        while len(self._closed) > 64:  # bounded memory of closed ids
            self._closed.popitem(last=False)
        if self.rec.enabled:
            self.rec.event("ring.take", cat="ring", round=rid,
                           delivered=len(e["written"]), lanes=len(e["slots"]))
            self.rec.gauge("ring.occupancy").set(len(self._open))
        return e["stacks"]

    @_ring_locked
    def take_chunked(self, round_id=None) -> Tuple[Any, Dict[str, Any]]:
        """Flush the remaining chunks IN SLOT ORDER, pop the round and return
        ``(round_id, entry)`` — the entry carries the folded accumulators
        (``acc``), the raw ingest weights (``w``), retained chunk buffers
        when the method re-streams them, and the delivery bookkeeping.

        Trailing chunks that never filled flush here: unwritten lanes hold
        zero factors AND zero weight, so the padded fold is exact — every
        fold in the round's life shares one (chunk, …) program signature."""
        rid, e = self._entry(round_id)
        if not e["chunked"]:
            raise RuntimeError(f"round {rid!r} is stacked — close it via "
                               "take()")
        while e["next_chunk"] < e["num_chunks"]:
            self._fold_next(rid, e, eager=False)
        del self._open[rid]
        self._closed[rid] = True
        while len(self._closed) > 64:
            self._closed.popitem(last=False)
        if self.rec.enabled:
            self.rec.event("ring.take", cat="ring", round=rid,
                           delivered=len(e["written"]), lanes=len(e["slots"]),
                           chunked=True, partial_folds=e["eager_folds"])
            self.rec.gauge("ring.occupancy").set(len(self._open))
        return rid, e

    # -- checkpoint/resume (crash-safe round state) -------------------------
    @_ring_locked
    def state_dict(self) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        """(json-able bookkeeping, array leaves) snapshot of the ring.

        Open rounds' partially-written stacks ride along as flat arrays keyed
        ``ring/{round}/{path}`` so a resumed coordinator can keep streaming
        into them; at a round boundary the ring is normally empty and the
        snapshot is just the drop counters + closed/evicted id memories."""
        meta: Dict[str, Any] = {
            "open": [],
            "evicted": list(self._evicted.items()),
            "closed": list(self._closed),
            "evictions": self.evictions,
            "stale_drops": self.stale_drops,
            "replay_drops": self.replay_drops,
            "duplicate_drops": self.duplicate_drops,
            "partial_folds": self.partial_folds,
            "auto": self._auto,
        }
        arrays: Dict[str, Any] = {}
        for rid, e in self._open.items():
            entry: Dict[str, Any] = {
                "round": rid,
                "slots": {str(c): s for c, s in e["slots"].items()},
                "written": {str(c): s for c, s in e["written"].items()},
                "deadline": e["deadline"],
                "chunked": e["chunked"],
            }
            if e["chunked"]:
                # a mid-round chunked entry is its accumulators + the not-yet
                # -folded chunk buffers + the slot-indexed raw weights; the
                # fold cascade's position (next_chunk/filled) rides in meta
                # so a resumed twin replays the exact remaining fold sequence
                entry.update(next_chunk=e["next_chunk"],
                             num_chunks=e["num_chunks"],
                             expected=list(e["expected"]),
                             filled=list(e["filled"]),
                             eager_folds=e["eager_folds"],
                             pending_chunks=sorted(e["chunks"]),
                             retained_chunks=sorted(e["retained"]),
                             acc_keys=sorted(e["acc"]) if e["acc"] else [])
                arrays[f"ring/{rid}/_w"] = np.asarray(e["w"])
                arrays[f"ring/{rid}/_ranks"] = np.asarray(e["ranks"])
                for k, buf in e["chunks"].items():
                    for p, x in buf.items():
                        arrays[f"ring/{rid}/_chunk{k}/{p}"] = np.asarray(x)
                for k, buf in e["retained"].items():
                    for p, x in buf.items():
                        arrays[f"ring/{rid}/_ret{k}/{p}"] = np.asarray(x)
                if e["acc"]:
                    for name, x in e["acc"].items():
                        arrays[f"ring/{rid}/_acc/{name}"] = np.asarray(x)
            else:
                for p, x in e["stacks"].items():
                    arrays[f"ring/{rid}/{p}"] = np.asarray(x)
            meta["open"].append(entry)
        return meta, arrays

    @_ring_locked
    def load_state(self, meta: Dict[str, Any],
                   arrays: Dict[str, Any]) -> None:
        self._open = OrderedDict()
        for entry in meta["open"]:
            rid = entry["round"]
            e: Dict[str, Any] = {
                "slots": {int(c): s for c, s in entry["slots"].items()},
                "written": {int(c): s for c, s in entry["written"].items()},
                "deadline": entry["deadline"],
                "chunked": bool(entry.get("chunked", False))}
            if e["chunked"]:
                def _bufs(prefix, ks):
                    return {int(k): {p: np.asarray(
                        arrays[f"ring/{rid}/_{prefix}{k}/{p}"], np.float32)
                        for p in self._shapes} for k in ks}
                acc = None
                if entry["acc_keys"]:
                    acc = {name: jnp.asarray(
                        arrays[f"ring/{rid}/_acc/{name}"])
                        for name in entry["acc_keys"]}
                e.update(stacks=None,
                         chunks=_bufs("chunk", entry["pending_chunks"]),
                         retained=_bufs("ret", entry["retained_chunks"]),
                         acc=acc,
                         w=np.asarray(arrays[f"ring/{rid}/_w"], np.float32),
                         ranks=(np.asarray(arrays[f"ring/{rid}/_ranks"],
                                           np.int32)
                                if f"ring/{rid}/_ranks" in arrays
                                else np.full(int(entry["num_chunks"])
                                             * self.chunk, -1, np.int32)),
                         next_chunk=int(entry["next_chunk"]),
                         num_chunks=int(entry["num_chunks"]),
                         expected=[int(x) for x in entry["expected"]],
                         filled=[int(x) for x in entry["filled"]],
                         eager_folds=int(entry["eager_folds"]))
            else:
                e["stacks"] = {
                    p: jnp.asarray(arrays[f"ring/{rid}/{p}"], jnp.float32)
                    for p in self._shapes}
            self._open[rid] = e
        self._evicted = OrderedDict(
            (rid, reason) for rid, reason in meta["evicted"])
        self._closed = OrderedDict((rid, True) for rid in meta["closed"])
        self.evictions = int(meta["evictions"])
        self.stale_drops = int(meta["stale_drops"])
        self.replay_drops = int(meta.get("replay_drops", 0))
        self.duplicate_drops = int(meta.get("duplicate_drops", 0))
        self.partial_folds = int(meta.get("partial_folds", 0))
        self._auto = int(meta["auto"])


# --------------------------------------------------------------------------
# factored residual machinery (shared by divergence + the svd close)
# --------------------------------------------------------------------------

def _stacked_residual_factors(a_stack: jnp.ndarray, b_stack: jnp.ndarray,
                              u: jnp.ndarray
                              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Low-rank factors of the weighted residual, straight from the stacks.

    Σ_c u_c·a_c b_c − ā b̄  =  Σ_c u_c·a_c (b_c − b̄)  =  L @ R  with
    L = [u_0·a_0 | … | u_{C-1}·a_{C-1}]  (…, m, C·r)  and
    R = [b_0 − b̄ ; … ; b_{C-1} − b̄]     (…, C·r, n),  b̄ = Σ_c u_c·b_c —
    the rank-≤C·r form (ā b̄ lies inside span{a_c}, so no extra block is
    needed). Zero-weight lanes contribute zero L columns.
    """
    a = a_stack.astype(jnp.float32)  # (C, ..., m, r)
    b = b_stack.astype(jnp.float32)  # (C, ..., r, n)
    c = a.shape[0]
    bbar = jnp.einsum("c,c...rn->...rn", u, b)
    L = jnp.concatenate([u[i] * a[i] for i in range(c)], axis=-1)
    R = jnp.concatenate([b[i] - bbar for i in range(c)], axis=-2)
    return L, R


def _dev_fro_scaled(a_stack: jnp.ndarray, b_stack: jnp.ndarray,
                    u: jnp.ndarray) -> jnp.ndarray:
    """Scaled Frobenius norm of Σu_c·a_c b_c − ā b̄ via the factored Grams —
    never materialises the (…, m, n) deviation. Returns (…,) per leading axes."""
    L, R = _stacked_residual_factors(a_stack, b_stack, u)
    gl = jnp.einsum("...mi,...mj->...ij", L, L)
    gr = jnp.einsum("...in,...jn->...ij", R, R)
    fro_sq = jnp.maximum(jnp.einsum("...ij,...ij->...", gl, gr), 0.0)
    m, n = a_stack.shape[-2], b_stack.shape[-1]
    return jnp.sqrt(fro_sq) / np.sqrt(m * n)


def _safe_inv_sqrt(lam: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(λ^{-1/2}, λ^{1/2}) with pseudo-inverse semantics: eigenvalues below
    the rank-detection floor (masked lanes, redundant factors) map to 0."""
    tol = jnp.max(lam, axis=-1, keepdims=True) * (lam.shape[-1] * 1e-7)
    pos = lam > tol
    safe = jnp.where(pos, lam, 1.0)
    return (jnp.where(pos, jax.lax.rsqrt(safe), 0.0),
            jnp.where(pos, jnp.sqrt(safe), 0.0))


def factored_truncated_residual(a_stack: jnp.ndarray, b_stack: jnp.ndarray,
                                weights: jnp.ndarray, rank: int
                                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Eckart–Young-optimal rank-``rank`` factors of the weighted residual,
    computed WITHOUT ever forming the dense (m, n) matrix.

    With ΔW = L @ R from :func:`_stacked_residual_factors` (P = C·r columns):
    eigendecompose the two small Grams G_L = LᵀL = E_L Λ_L E_Lᵀ and
    G_R = R Rᵀ = E_R Λ_R E_Rᵀ, so L = Q_L Λ_L^{1/2} E_Lᵀ with orthonormal
    Q_L = L E_L Λ_L^{-1/2} (pseudo-inverse on null directions — masked lanes
    give zero columns) and likewise R = E_R Λ_R^{1/2} Q_Rᵀ. Then
    ΔW = Q_L S Q_Rᵀ with the P×P core S = Λ_L^{1/2} E_Lᵀ E_R Λ_R^{1/2}; the
    SVD of S gives ΔW's singular triplets, and the top-r' slice yields

        A' = L E_L Λ_L^{-1/2} U_{:r'} Σ_{:r'}   (…, m, r')
        B' = V_{:r'}ᵀ Λ_R^{-1/2} E_Rᵀ R          (…, r', n)

    with A' @ B' the optimal rank-r' approximation (Eq. 15–16). Every
    intermediate is (m, P), (P, n) or (P, P) — asserted shape-by-shape on the
    jaxpr in tests. Leading stacked-layer / expert axes batch through.
    Accuracy: the Gram squaring costs ~half the float32 digits; the result
    matches the dense-SVD oracle to ~1e-5 relative (documented tolerance).
    """
    L, R = _stacked_residual_factors(a_stack, b_stack, weights)
    gl = jnp.einsum("...mi,...mj->...ij", L, L)
    gr = jnp.einsum("...in,...jn->...ij", R, R)
    el, vl = jnp.linalg.eigh(gl)
    er, vr = jnp.linalg.eigh(gr)
    il, sl = _safe_inv_sqrt(el)
    ir, sr = _safe_inv_sqrt(er)
    core = sl[..., :, None] * (jnp.swapaxes(vl, -1, -2) @ vr) * sr[..., None, :]
    u, s, vt = jnp.linalg.svd(core, full_matrices=False)
    u_r = u[..., :, :rank]
    s_r = s[..., :rank]
    vt_r = vt[..., :rank, :]
    aprime = L @ ((vl * il[..., None, :]) @ u_r) * s_r[..., None, :]
    bprime = (vt_r @ jnp.swapaxes(vr * ir[..., None, :], -1, -2)) @ R
    return aprime, bprime


def factored_truncated_product(L: jnp.ndarray, R: jnp.ndarray, rank: int
                               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Eckart–Young-optimal rank-``rank`` factors of the UNCENTERED product
    ``L @ R`` — the hetero close's truncation primitive, shared with the eager
    oracle (core/hetero.py) so engine and oracle compose the SAME ops.

    Identical Gram machinery to :func:`factored_truncated_residual` (two
    (P, P) eigendecompositions + one small SVD, P = L's column count; the
    dense (m, n) product never exists — jaxpr-asserted in tests), but on the
    raw product rather than the centred residual, and with the BALANCED
    singular split A' = Q_L U √Σ, B' = √Σ Vᵀ Q_Rᵀ R (the LoRA-friendly
    parameterisation of core/hetero.py) instead of folding Σ into A' alone.
    Zero columns of L / zero rows of R (rank-padded lanes) yield zero Gram
    eigenvalues that ``_safe_inv_sqrt`` floors away, so r_max-padded ragged
    stacks truncate exactly as their unpadded originals. The rank-r' slice of
    the returned factors IS the optimal rank-r' truncation for any r' ≤ rank
    (same singular triplets), which is how the hetero close serves every
    client rank from ONE decomposition.
    """
    gl = jnp.einsum("...mi,...mj->...ij", L, L)
    gr = jnp.einsum("...in,...jn->...ij", R, R)
    el, vl = jnp.linalg.eigh(gl)
    er, vr = jnp.linalg.eigh(gr)
    il, sl = _safe_inv_sqrt(el)
    ir, sr = _safe_inv_sqrt(er)
    core = sl[..., :, None] * (jnp.swapaxes(vl, -1, -2) @ vr) * sr[..., None, :]
    u, s, vt = jnp.linalg.svd(core, full_matrices=False)
    sq = jnp.sqrt(jnp.maximum(s[..., :rank], 0.0))
    aprime = L @ ((vl * il[..., None, :]) @ u[..., :, :rank]) * sq[..., None, :]
    bprime = sq[..., :, None] * (
        (vt[..., :rank, :] @ jnp.swapaxes(vr * ir[..., None, :], -1, -2)) @ R)
    return aprime, bprime


def _rank_mask(ranks: jnp.ndarray, r: int) -> jnp.ndarray:
    """(C,) int rank vector → (C, r) 0/1 float mask: column j of lane c is
    live iff j < ranks[c]. Negative ranks mean "unmasked" (full r)."""
    rk = jnp.where(ranks < 0, r, ranks)
    return (jnp.arange(r)[None, :] < rk[:, None]).astype(jnp.float32)


def _mask_factor_stacks(a: jnp.ndarray, b: jnp.ndarray, ranks: jnp.ndarray
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Zero the rank-padded columns of a (C, …, m, r) stack and the matching
    rows of its (C, …, r, n) twin. Multiplying by the 0/1 mask is EXACT
    (0·x = 0, 1·x = x), so lanes whose padding carries garbage (a defended
    decode that validated but over-wrote) still contribute exactly zero."""
    c, r = a.shape[0], a.shape[-1]
    mask = _rank_mask(ranks, r)
    ma = mask.reshape((c,) + (1,) * (a.ndim - 2) + (r,))
    mb = mask.reshape((c,) + (1,) * (b.ndim - 3) + (r, 1))
    return a * ma, b * mb


def _l_block(a_chunk: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """(chunk, …, m, r) → (…, m, chunk·r): one chunk's weighted L columns,
    lane-major — exactly the columns the stacked ``_stacked_residual_factors``
    concatenation would give these lanes, so chunk-pair Gram blocks tile the
    full (C·r)² Gram."""
    a = a_chunk.astype(jnp.float32)
    la = w.reshape((-1,) + (1,) * (a.ndim - 1)) * a
    la = jnp.moveaxis(la, 0, -2)  # (…, m, chunk, r)
    return la.reshape(la.shape[:-2] + (la.shape[-2] * la.shape[-1],))


def _r_block(b_chunk: jnp.ndarray, bbar: jnp.ndarray) -> jnp.ndarray:
    """(chunk, …, r, n) → (…, chunk·r, n): one chunk's centred R rows,
    lane-major (the stacked concatenation's row blocks)."""
    rb = b_chunk.astype(jnp.float32) - bbar[None]
    rb = jnp.moveaxis(rb, 0, -3)  # (…, chunk, r, n)
    return rb.reshape(rb.shape[:-3] + (rb.shape[-3] * rb.shape[-2],
                                       rb.shape[-1]))


# --------------------------------------------------------------------------
# the fused close programs (one per engine method)
# --------------------------------------------------------------------------

def _slice_client_trees(specs: Sequence[FactorSpec],
                        stacks: Dict[str, jnp.ndarray],
                        c_max: int) -> List[Params]:
    """Stack lanes as a list of adapter trees — the uniform closes feed these
    to the aggregation operators verbatim, so the jitted program is the jnp
    ground truth op-for-op (the bitwise contract)."""
    return [
        {s.key: {"a": stacks[s.key + "/a"][c], "b": stacks[s.key + "/b"][c]}
         for s in specs}
        for c in range(c_max)
    ]


def _uniform_close(specs: Sequence[FactorSpec], scale: float,
                   w0_leaves: Dict[str, jnp.ndarray],
                   stacks: Dict[str, jnp.ndarray], c_max: int):
    """Full-participation uniform fedex close — literally the aggregation
    operators over stack slices, so the jitted program is the jnp ground
    truth."""
    client_trees = _slice_client_trees(specs, stacks, c_max)
    g = agg.fedit_aggregate(client_trees)
    res = agg.fedex_residual(client_trees, g)
    new_w0 = {
        s.key: (w0_leaves[s.key].astype(jnp.float32)
                + scale * res[s.key]).astype(s.w0_dtype)
        for s in specs
    }
    glob = {s.key: g[s.key] for s in specs}
    return new_w0, glob


def _weighted_close_jnp(specs: Sequence[FactorSpec], scale: float,
                        w0_leaves: Dict[str, jnp.ndarray],
                        stacks: Dict[str, jnp.ndarray],
                        w: jnp.ndarray, c_max: int):
    """Weighted/masked fedex close, jnp twin: Σw_c a_c b_c − ā b̄ folded into
    W0. Zero-weight lanes vanish from every sum — the participation mask."""
    new_w0, glob = {}, {}
    for s in specs:
        a = stacks[s.key + "/a"]  # (C, ..., m, r) f32
        b = stacks[s.key + "/b"]
        ga = jnp.einsum("c,c...mr->...mr", w, a)
        gb = jnp.einsum("c,c...rn->...rn", w, b)
        mean_prod = jnp.einsum("c,c...mr,c...rn->...mn", w, a, b)
        res = mean_prod - jnp.matmul(ga, gb)
        new_w0[s.key] = (w0_leaves[s.key].astype(jnp.float32)
                         + scale * res).astype(s.w0_dtype)
        glob[s.key] = {"a": ga, "b": gb}
    return new_w0, glob


def _weighted_close_pallas(specs: Sequence[FactorSpec], scale: float,
                           w0_leaves: Dict[str, jnp.ndarray],
                           stacks: Dict[str, jnp.ndarray],
                           w: Optional[jnp.ndarray], interpret: Optional[bool]):
    """Fused-kernel fedex close: factor means + residual fold through the
    tiled Pallas kernels — the dense m×n residual never exists in HBM."""
    from repro.kernels import factor_mean, fedex_fold

    new_w0, glob = {}, {}
    for s in specs:
        a = stacks[s.key + "/a"]  # (C, ..., m, r)
        b = stacks[s.key + "/b"]
        ga = factor_mean(a, w, interpret=interpret)
        gb = factor_mean(b, w, interpret=interpret)
        # kernel layout: leading layer axes first, client axis innermost
        am = jnp.moveaxis(a, 0, -3)
        bm = jnp.moveaxis(b, 0, -3)
        new_w0[s.key] = fedex_fold(
            w0_leaves[s.key], am, bm, scale, weights=w,
            interpret=interpret).astype(s.w0_dtype)
        glob[s.key] = {"a": ga, "b": gb}
    return new_w0, glob


def _svd_close(specs: Sequence[FactorSpec], scale: float, svd_rank: int,
               w0_leaves: Dict[str, jnp.ndarray],
               stacks: Dict[str, jnp.ndarray], w: jnp.ndarray,
               backend: str, interpret: Optional[bool]):
    """Truncated-SVD close: factored Eckart–Young residual (never dense),
    folded into W0 as the rank-r' product A' @ B'."""
    new_w0, glob = {}, {}
    for s in specs:
        a = stacks[s.key + "/a"]  # (C, ..., m, r)
        b = stacks[s.key + "/b"]
        if backend == "pallas":
            from repro.kernels import factor_mean, product_fold
            ga = factor_mean(a, w, interpret=interpret)
            gb = factor_mean(b, w, interpret=interpret)
            ap, bp = factored_truncated_residual(a, b, w, svd_rank)
            new_w0[s.key] = product_fold(
                w0_leaves[s.key], jnp.expand_dims(ap, -3),
                jnp.expand_dims(bp, -3), jnp.ones((1,), jnp.float32), scale,
                interpret=interpret).astype(s.w0_dtype)
        else:
            ga = jnp.einsum("c,c...mr->...mr", w, a)
            gb = jnp.einsum("c,c...rn->...rn", w, b)
            ap, bp = factored_truncated_residual(a, b, w, svd_rank)
            new_w0[s.key] = (w0_leaves[s.key].astype(jnp.float32)
                             + scale * jnp.matmul(ap, bp)).astype(s.w0_dtype)
        glob[s.key] = {"a": ga, "b": gb}
    return new_w0, glob


def _reinit_close(specs: Sequence[FactorSpec], scale: float,
                  w0_leaves: Dict[str, jnp.ndarray],
                  stacks: Dict[str, jnp.ndarray], w: jnp.ndarray,
                  c_max: int, uniform: bool, backend: str,
                  interpret: Optional[bool]):
    """Reinit close (Table 5): the FULL ideal update Σw_c·a_c b_c folds into
    W0 (fresh adapters carry b=0, so nothing is left behind). The uniform
    branch composes product_mean over stack slices — bitwise twin of the
    jitted assignment oracle on EVERY backend (like the fedex uniform
    branch; the kernel path serves weighted/ragged rounds)."""
    if uniform:
        client_trees = _slice_client_trees(specs, stacks, c_max)
        ideal = agg.product_mean(client_trees)
        return {
            s.key: (w0_leaves[s.key].astype(jnp.float32)
                    + scale * ideal[s.key]).astype(s.w0_dtype)
            for s in specs
        }
    new_w0 = {}
    for s in specs:
        a = stacks[s.key + "/a"]
        b = stacks[s.key + "/b"]
        if backend == "pallas":
            from repro.kernels import product_fold
            am = jnp.moveaxis(a, 0, -3)
            bm = jnp.moveaxis(b, 0, -3)
            new_w0[s.key] = product_fold(
                w0_leaves[s.key], am, bm, w, scale,
                interpret=interpret).astype(s.w0_dtype)
        else:
            ideal = jnp.einsum("c,c...mr,c...rn->...mn", w, a, b)
            new_w0[s.key] = (w0_leaves[s.key].astype(jnp.float32)
                             + scale * ideal).astype(s.w0_dtype)
    return new_w0


def _keep_local_close(specs: Sequence[FactorSpec], scale: float,
                      w0_stacks: Dict[str, jnp.ndarray],
                      stacks: Dict[str, jnp.ndarray], w: jnp.ndarray,
                      c_max: int, uniform: bool, backend: str,
                      interpret: Optional[bool]):
    """Keep_local close (Table 5): every lane's OWN base gets its residual
    Σ_j w_j·a_j b_j − a_c b_c. ``w0_stacks`` carry the per-lane W0 leaves
    ((C_max, …) like the factor stacks); non-delivered lanes produce a lane
    the caller discards. The uniform branch composes per_client_residuals
    over stack slices — bitwise twin of the jitted assignment oracle on
    EVERY backend (like the fedex uniform branch; the kernel path serves
    weighted/ragged rounds)."""
    if uniform:
        # the bitwise branch composes the eager operators lane-by-lane; it
        # costs ~2× the batched-einsum branch below (unbatchable per-client
        # matmul chains) — the price of the uniform bitwise contract. The
        # trainer's full-round close still beats the eager path (fused
        # divergence + single dispatch); weighted/ragged rounds take the
        # fast branch.
        client_trees = _slice_client_trees(specs, stacks, c_max)
        residuals = agg.per_client_residuals(client_trees)
        return {
            s.key: jnp.stack([
                (w0_stacks[s.key][c].astype(jnp.float32)
                 + scale * residuals[c][s.key]).astype(s.w0_dtype)
                for c in range(c_max)
            ])
            for s in specs
        }
    new_w0 = {}
    for s in specs:
        a = stacks[s.key + "/a"]  # (C, ..., m, r)
        b = stacks[s.key + "/b"]
        if backend == "pallas":
            from repro.kernels import perclient_fold
            new_w0[s.key] = perclient_fold(
                w0_stacks[s.key], a, b, w, scale,
                interpret=interpret).astype(s.w0_dtype)
        else:
            ideal = jnp.einsum("c,c...mr,c...rn->...mn", w, a, b)
            own = jnp.matmul(a, b)
            new_w0[s.key] = (w0_stacks[s.key].astype(jnp.float32)
                             + scale * (ideal[None] - own)).astype(s.w0_dtype)
    return new_w0


def _hetero_close(specs: Sequence[FactorSpec], scale: float,
                  w0_stacks: Dict[str, jnp.ndarray],
                  stacks: Dict[str, jnp.ndarray], w: jnp.ndarray,
                  ranks: jnp.ndarray, c_max: int, uniform: bool,
                  backend: str, interpret: Optional[bool]):
    """Heterogeneous-rank close (core/hetero.py's scheme, engine-side): the
    ideal update Δ̄ = Σ_c w_c·a_c b_c is truncated ONCE at the template rank
    r_max via :func:`factored_truncated_product`; lane c's adapters are the
    leading-rᵢ slice of that truncation (same singular triplets — rank masks
    in place of per-client SVDs) and its residual Δ̄ − aᵢ'bᵢ' folds into its
    OWN (C_max, …)-stacked W0, so W0_c + ΔW_c + a_c'b_c' = W0_c + Δ̄ exactly
    for every lane. Ragged lanes ride zero-padded to r_max with a (C_max,)
    rank vector next to the weight vector; the masks multiply by exact 0/1 so
    masked rank columns contribute exactly zero to every sum. The uniform
    branch (full participation, uniform weights, every delivered rank =
    r_max) composes the eager oracle's op sequence over stack slices — the
    bitwise contract; the ragged branch shares every decomposition input with
    the oracle's padded form, differing only by the fold's FMA contraction
    (≤2 ulp, asserted in tests/test_engine_hetero.py).

    Returns ``(new_w0_stacks, glob, masked_stacks)`` with ``glob[key] =
    {"a": A'(r_max), "b": B'(r_max)}`` (callers slice per-client ranks) and
    the rank-masked stacks for the divergence tail."""
    new_w0, glob, masked = {}, {}, {}
    for s in specs:
        a = stacks[s.key + "/a"].astype(jnp.float32)  # (C, ..., m, r_max)
        b = stacks[s.key + "/b"].astype(jnp.float32)  # (C, ..., r_max, n)
        r = s.a_shape[-1]
        if uniform:
            am, bm = a, b  # every lane at full rank: masking is the identity
            L = jnp.concatenate([a[i] / c_max for i in range(c_max)], axis=-1)
        else:
            am, bm = _mask_factor_stacks(a, b, ranks)
            L = jnp.concatenate([w[i] * am[i] for i in range(c_max)], axis=-1)
        R = jnp.concatenate([bm[i] for i in range(c_max)], axis=-2)
        ap, bp = factored_truncated_product(L, R, r)
        if backend == "pallas" and not uniform:
            from repro.kernels import hetero_fold
            new_w0[s.key] = hetero_fold(
                w0_stacks[s.key], a, b, w, ranks, ap, bp, scale,
                interpret=interpret).astype(s.w0_dtype)
        else:
            # Δ̄ as the factored product (the oracle's op), per-lane own =
            # masked slice of the shared truncation; adding the exact-zero
            # masked terms reproduces the oracle's sliced matmul exactly
            ideal = L @ R
            if uniform:
                own_full = ap @ bp  # every lane at r_max: one shared own
                owns = [own_full] * c_max
            else:
                mask = _rank_mask(ranks, r)
                owns = [(ap * mask[c]) @ bp for c in range(c_max)]
            new_w0[s.key] = jnp.stack([
                (w0_stacks[s.key][c].astype(jnp.float32)
                 + scale * (ideal - owns[c])).astype(s.w0_dtype)
                for c in range(c_max)
            ])
        glob[s.key] = {"a": ap, "b": bp}
        masked[s.key + "/a"] = am
        masked[s.key + "/b"] = bm
    return new_w0, glob, masked


def make_close_fn(specs: Sequence[FactorSpec], *, scale: float, c_max: int,
                  method: str = "fedex", svd_rank: int = 0,
                  backend: str = "auto", interpret: Optional[bool] = None,
                  donate: bool = True):
    """Build the jitted close program for one engine method.

    Signature: ``close(w0_leaves, stacks, weights, mask, uniform=...)`` →
    ``(new_w0_leaves, global_factors, divergence)`` with ``w0_leaves`` and
    ``stacks`` donated (in-place update on every platform; pass
    ``donate=False`` for callers that replay the program on the same
    buffers, e.g. benchmarks).

    * ``method="fedex"`` — ``uniform=True`` is the static full-participation
      branch, bitwise twin of the jitted list path; otherwise ``weights`` is
      the (C_max,) vector with zeros masking non-delivered lanes and ``mask``
      its 0/1 indicator (used for the uniform-over-delivered divergence).
    * ``method="fedex_svd"`` — the factored rank-``svd_rank`` truncated close
      (requires ``svd_rank ≥ 1``); both uniform and ragged rounds go through
      the weight vector (truncation has no bitwise-uniform contract).
    * ``method="reinit"`` — ``w0_leaves`` as fedex; returns ``glob={}``
      (fresh adapters are drawn host-side by the engine).
    * ``method="keep_local"`` — ``w0_leaves`` holds (C_max, …)-stacked
      per-lane W0 leaves and the returned ``new_w0`` is stacked likewise;
      ``glob={}``.
    * ``method="hetero"`` — heterogeneous client ranks: ``w0_leaves`` is
      (C_max, …)-stacked per-lane W0 leaves (as keep_local) and the ``mask``
      positional slot carries the (C_max,) INT rank vector (ragged lanes
      zero-padded to the template rank r_max; rank 0 masks a lane entirely,
      negative means full rank). Returns stacked ``new_w0`` plus ``glob`` =
      the shared rank-r_max truncation factors per spec — the caller slices
      each client's leading rᵢ columns/rows.
    """
    backend = _resolve_backend(backend)
    specs = list(specs)
    if method not in ENGINE_METHODS:
        raise ValueError(f"unknown engine method {method!r} "
                         f"(expected one of {ENGINE_METHODS})")
    if method == "fedex_svd" and svd_rank < 1:
        raise ValueError(f"fedex_svd close needs svd_rank ≥ 1, got {svd_rank}"
                         " (svd_rank=0 means exact — use the fedex close)")

    def _close(w0_leaves, stacks, weights, mask, *, uniform: bool):
        if method == "fedex":
            if uniform:
                new_w0, glob = _uniform_close(specs, scale, w0_leaves, stacks,
                                              c_max)
            elif backend == "pallas":
                new_w0, glob = _weighted_close_pallas(
                    specs, scale, w0_leaves, stacks, weights, interpret)
            else:
                new_w0, glob = _weighted_close_jnp(
                    specs, scale, w0_leaves, stacks, weights, c_max)
        elif method == "fedex_svd":
            new_w0, glob = _svd_close(specs, scale, svd_rank, w0_leaves,
                                      stacks, weights, backend, interpret)
        elif method == "reinit":
            new_w0 = _reinit_close(specs, scale, w0_leaves, stacks, weights,
                                   c_max, uniform, backend, interpret)
            glob = {}
        elif method == "hetero":
            new_w0, glob, masked = _hetero_close(
                specs, scale, w0_leaves, stacks, weights, mask, c_max,
                uniform, backend, interpret)
            # divergence over the rank-masked stacks: padded columns must
            # contribute exactly zero to the §6 metric too
            stacks = masked
        else:  # keep_local
            new_w0 = _keep_local_close(specs, scale, w0_leaves, stacks,
                                       weights, c_max, uniform, backend,
                                       interpret)
            glob = {}
        if method == "hetero" and not uniform:
            # the mask slot carries the rank vector — a lane participates in
            # the divergence iff it delivered weight AND a non-empty rank
            live = jnp.where((mask > 0) & (weights > 0),
                             jnp.float32(1.0), jnp.float32(0.0))
            u = live / jnp.maximum(live.sum(), 1.0)
        else:
            u = (jnp.full((c_max,), 1.0 / c_max, jnp.float32) if uniform
                 else mask / jnp.maximum(mask.sum(), 1.0))
        parts = [
            _dev_fro_scaled(stacks[s.key + "/a"], stacks[s.key + "/b"],
                            u).ravel()
            for s in specs
        ]
        div = jnp.concatenate(parts).mean() if parts else jnp.float32(0)
        return new_w0, glob, div

    donate_argnums = (0, 1) if donate else ()
    return _jit_f32(_close, static_argnames=("uniform",),
                   donate_argnums=donate_argnums)


class RoundCloseEngine:
    """Owns the streaming buffers + the compiled close program for a trainer.

    One engine per (params structure, adapter structure, C_max, scale,
    method): ``buffers`` is handed to the fedsrv coordinator as the delivery
    sink, and :meth:`close` / :meth:`close_keep_local` run the
    single-dispatch fused close over whatever subset actually arrived, with
    any weighting. The C_max padding contract: stacks are always
    ``(C_max, …)``; a round's candidates get lanes in client-id order;
    weights (zeros on non-delivered lanes) mask the rest — so ragged quorums
    and weighted rounds reuse ONE compiled program, and the uniform
    full-participation fedex/reinit/keep_local rounds keep their own
    bitwise-stable branch. ``depth`` (default 2) double-buffers the streaming
    stacks so the next round's uplinks can be decoded into a fresh set while
    this round's close still owns the previous one.
    """

    def __init__(self, params: Params, lora_template: Params, *,
                 c_max: int, scale: float, method: str = "fedex",
                 svd_rank: int = 0, backend: str = "auto",
                 interpret: Optional[bool] = None, donate: bool = True,
                 depth: int = 2, recorder=None, chunk: int = 0,
                 program_cache_cap: int = 16,
                 client_ranks: Optional[Sequence[int]] = None):
        self.specs = build_factor_specs(params, lora_template)
        self.c_max = c_max
        # hetero: per-client TRUE adapter ranks (index = client id). The
        # template rank is r_max — every stack lane is padded to it and the
        # close masks the truncation back down per lane. None = every client
        # at full rank (the uniform bitwise branch).
        if client_ranks is not None:
            rmax = self.specs[0].a_shape[-1] if self.specs else 0
            client_ranks = tuple(int(r) for r in client_ranks)
            if len(client_ranks) != c_max:
                raise ValueError(
                    f"client_ranks has {len(client_ranks)} entries for "
                    f"c_max={c_max}")
            bad = [r for r in client_ranks if not 1 <= r <= rmax]
            if bad:
                raise ValueError(
                    f"client_ranks {bad} outside [1, r_max={rmax}] — the "
                    "lora template must be built at the LARGEST client rank")
        self.client_ranks = client_ranks
        self.scale = scale
        self.method = method
        self.svd_rank = svd_rank
        self.backend = _resolve_backend(backend)
        self.rec = recorder if recorder is not None else NULL
        self.chunk = int(chunk)
        self._interpret = interpret
        self._donate = donate
        # LRU'd jitted programs: the stacked close plus, in chunked mode, the
        # partial fold / finalize / keep_local per-chunk / svd Gram-core-
        # projection family — bounded so long-lived engines can't grow the
        # compile cache without limit (satellite fix; see _ProgramCache)
        self._programs = _ProgramCache(cap=program_cache_cap)
        # analytic peak-live-device-bytes accounting per in-flight close (see
        # the "Memory model" docs section): inputs + outputs + materialised
        # intermediates, with a donated input/output pair counted once;
        # identical formula on every backend so CPU runs model accelerator
        # residency rather than host RAM
        self._peak: Dict[Any, int] = {}
        self.last_peak_bytes = 0
        self.buffers = RoundBuffers(
            lora_template, c_max, depth=depth, recorder=self.rec,
            chunk=self.chunk,
            on_chunk=self._fold_chunk if self.chunk else None,
            # keep_local folds each lane's OWN base, and fedex_svd / hetero
            # re-stream the L/R blocks for the projection pass — all three
            # need the chunk factor buffers back at close time
            retain_chunks=method in ("keep_local", "fedex_svd", "hetero"))
        self._lora_template = lora_template
        self._close = make_close_fn(self.specs, scale=scale, c_max=c_max,
                                    method=method, svd_rank=svd_rank,
                                    backend=self.backend, interpret=interpret,
                                    donate=donate)
        self._programs.get(("stacked", method), lambda: self._close)

    # ------------------------------------------------------------------
    def _dispatch(self, w0_leaves, stacks, w, mask, uniform: bool, round_id):
        """Run the jitted close program with obs instrumentation: the
        ``close.dispatch`` span times ONLY the (async) dispatch — the
        block-until-ready half lives in ``DeferredDivergence.resolve`` —
        and the compile-cache delta distinguishes a compile (miss) from a
        cache hit per (method, uniform) signature."""
        rec = self.rec
        self._note_peak(round_id, _tree_bytes(w0_leaves) + _tree_bytes(stacks)
                        + self._div_temp_bytes(self.c_max))
        if not rec.enabled:
            return self._close(w0_leaves, stacks, jnp.asarray(w),
                               jnp.asarray(mask), uniform=uniform)
        before = self._close._cache_size()
        t0 = time.perf_counter_ns()
        with rec.span("close.dispatch", cat="engine", round=round_id,
                      method=self.method, uniform=uniform):
            out = self._close(w0_leaves, stacks, jnp.asarray(w),
                              jnp.asarray(mask), uniform=uniform)
        dispatch_us = (time.perf_counter_ns() - t0) / 1e3
        sig = f"{self.method}[uniform={uniform}]"
        compiled = self._close._cache_size() > before
        rec.counter(f"engine.compile_{'miss' if compiled else 'hit'}"
                    f"[{sig}]").inc()
        if round_id is not None:
            rec.round_set(round_id, method=self.method,
                          close_dispatch_us=round(dispatch_us, 1),
                          compile_miss=int(compiled),
                          ring_occupancy=len(self.buffers.open_rounds),
                          ring_evictions=self.buffers.evictions,
                          stale_drops=self.buffers.stale_drops,
                          replay_drops=self.buffers.replay_drops,
                          duplicate_drops=self.buffers.duplicate_drops)
        return out

    # ------------------------------------------------------------------
    def weight_vector(self, client_ids: Sequence[int],
                      weights: Optional[Sequence[float]],
                      round_id=None) -> Tuple[np.ndarray, np.ndarray, bool]:
        """(C_max,) weights + mask from the delivered ids; uniform? flag."""
        slots = [self.buffers.slot_of(cid, round_id) for cid in client_ids]
        mask = np.zeros(self.c_max, np.float32)
        mask[slots] = 1.0
        norm = agg.normalize_weights(weights, len(client_ids))
        uniform = norm is None and len(client_ids) == self.c_max
        w = np.zeros(self.c_max, np.float32)
        if norm is None:
            w[slots] = 1.0 / len(client_ids)
        else:
            for s, wi in zip(slots, norm):
                w[s] = wi
        return w, mask, uniform

    def _validate_delivered(self, client_ids: Sequence[int],
                            round_id=None) -> None:
        if not client_ids:
            raise ValueError("cannot close a round with no deliveries")
        written = self.buffers.delivered_in(round_id)
        missing = [c for c in client_ids if c not in written]
        if missing:
            raise ValueError(f"clients {missing} were never written to the "
                             "round buffers")

    def _w0_leaves(self, params: Params) -> Dict[str, jnp.ndarray]:
        return collect_w0_leaves(self.specs, params)

    def _fold_back(self, params: Params,
                   new_w0: Dict[str, jnp.ndarray]) -> Params:
        return fold_back_w0(self.specs, params, new_w0)

    # -- analytic peak-memory accounting -------------------------------
    def _note_peak(self, round_id, nbytes: int) -> None:
        cur = self._peak.get(round_id, 0)
        if nbytes > cur:
            self._peak[round_id] = nbytes
            while len(self._peak) > 64:  # bounded (abandoned rounds)
                self._peak.pop(next(iter(self._peak)))

    def _finish_peak(self, round_id) -> int:
        peak = self._peak.pop(round_id, 0)
        self.last_peak_bytes = peak
        if self.rec.enabled and round_id is not None:
            self.rec.round_set(round_id, peak_bytes=peak)
        return peak

    def peak_close_bytes(self, round_id) -> int:
        """Recorded peak live device bytes of a still-accumulating round."""
        return self._peak.get(round_id, 0)

    def _div_temp_bytes(self, c: int) -> int:
        """Device bytes of the divergence intermediates a stacked close
        materialises: per spec the L (…, m, C·r) and R (…, C·r, n) factors
        plus two (C·r)² Grams — the terms that make stacked closes O(C) and
        O((C·r)²) in memory."""
        total = 0
        for s in self.specs:
            lead = int(np.prod(s.a_shape[:-2], dtype=np.int64))
            m, r = s.a_shape[-2], s.a_shape[-1]
            n = s.b_shape[-1]
            p = c * r
            total += 4 * lead * (m * p + p * n + 2 * p * p)
        return total

    def _prod_temp_bytes(self) -> int:
        """Bytes of one dense (…, m, n) residual temp per spec (the chunked
        finalize's only dense intermediate)."""
        return sum(
            4 * int(np.prod(s.a_shape[:-1], dtype=np.int64))
            * s.b_shape[-1] for s in self.specs)

    # -- chunked accumulators + fold ------------------------------------
    def _init_acc(self) -> Dict[str, jnp.ndarray]:
        """Fresh float32 accumulators: weighted factor sums Σŵa / Σŵb for
        every method, plus the weighted product fold target Σŵ·a b for the
        methods whose close needs the dense ideal/residual (fedex / reinit /
        keep_local; fedex_svd stays factored — its close works off Gram
        blocks of the retained chunks)."""
        acc: Dict[str, jnp.ndarray] = {}
        need_prod = self.method != "fedex_svd"
        for s in self.specs:
            acc["ga/" + s.key] = jnp.zeros(s.a_shape, jnp.float32)
            acc["gb/" + s.key] = jnp.zeros(s.b_shape, jnp.float32)
            if need_prod:
                acc["prod/" + s.key] = jnp.zeros(
                    s.a_shape[:-1] + (s.b_shape[-1],), jnp.float32)
        return acc

    def _build_fold(self):
        """One jitted partial fold shared by EVERY chunk of every round:
        acc += Σ_lanes ŵ·(a, b, a@b). Zero-weight lanes (unwritten rows of a
        padded trailing chunk) are exact no-ops, so partial chunks reuse the
        same (chunk, …) program signature — the compile cache stays O(1) in
        round count and chunk fill."""
        specs, backend, interpret = self.specs, self.backend, self._interpret
        need_prod = self.method != "fedex_svd"

        def _fold(acc, stacks, w):
            out = dict(acc)
            for s in specs:
                a = stacks[s.key + "/a"].astype(jnp.float32)
                b = stacks[s.key + "/b"].astype(jnp.float32)
                if backend == "pallas":
                    from repro.kernels import factor_mean, product_accum
                    out["ga/" + s.key] = (acc["ga/" + s.key]
                                          + factor_mean(a, w,
                                                        interpret=interpret))
                    out["gb/" + s.key] = (acc["gb/" + s.key]
                                          + factor_mean(b, w,
                                                        interpret=interpret))
                    if need_prod:
                        out["prod/" + s.key] = product_accum(
                            acc["prod/" + s.key], jnp.moveaxis(a, 0, -3),
                            jnp.moveaxis(b, 0, -3), w, 1.0,
                            interpret=interpret)
                else:
                    out["ga/" + s.key] = acc["ga/" + s.key] + jnp.einsum(
                        "c,c...mr->...mr", w, a)
                    out["gb/" + s.key] = acc["gb/" + s.key] + jnp.einsum(
                        "c,c...rn->...rn", w, b)
                    if need_prod:
                        out["prod/" + s.key] = acc["prod/" + s.key] + \
                            jnp.einsum("c,c...mr,c...rn->...mn", w, a, b)
            return out

        donate = (0,) if self._donate else ()
        return _jit_f32(_fold, donate_argnums=donate)

    def _fold_chunk(self, acc, chunk_bufs, w, round_id, chunk_index):
        """RoundBuffers' on_chunk callback: one H2D conversion + one fold
        dispatch per chunk. The accumulator is donated to the fold program,
        so the partial fold is a true read-modify-write."""
        if acc is None:
            acc = self._init_acc()
        stacks = {p: jnp.asarray(x) for p, x in chunk_bufs.items()}
        if self.method == "hetero":
            # rank-mask the chunk's lanes BEFORE accumulation so padded
            # truncation columns contribute exactly zero even if a decoder
            # ever writes junk past a lane's true rank (decode pads with
            # zeros, so this is a defended no-op on the honest path)
            rk = self.buffers.chunk_ranks(round_id, chunk_index)
            if rk is not None:
                rkd = jnp.asarray(rk, jnp.int32)
                for s in self.specs:
                    am, bm = _mask_factor_stacks(
                        stacks[s.key + "/a"], stacks[s.key + "/b"], rkd)
                    stacks[s.key + "/a"], stacks[s.key + "/b"] = am, bm
        wd = jnp.asarray(w, jnp.float32)
        prog = self._programs.get(("fold",), self._build_fold, self.rec)
        new_acc = prog(acc, stacks, wd)
        self._note_peak(round_id, _tree_bytes(stacks) + _tree_bytes(new_acc)
                        + int(wd.nbytes))
        return new_acc

    def _check_ingest_weights(self, entry, w: np.ndarray, round_id) -> float:
        """Chunked closes weight at INGEST — verify the streamed raw weights
        normalise to the close-time weight vector, and return their sum W.
        A mismatch means chunks were folded under one weighting and the close
        was asked for another: the accumulators are already wrong, so this
        raises instead of silently corrupting the fold."""
        raw = np.asarray(entry["w"], np.float64)
        wsum = float(raw.sum())
        if wsum <= 0.0:
            raise ValueError("chunked close: total ingest weight is 0")
        for cid, slot in entry["written"].items():
            want = float(w[slot]) if slot < len(w) else 0.0
            got = raw[slot] / wsum
            if not np.isclose(got, want, rtol=1e-4, atol=1e-6):
                raise ValueError(
                    f"chunked close of round {round_id!r}: client {cid}'s "
                    f"ingest weight normalises to {got:.6g} but the close "
                    f"was given {want:.6g} — stream and close must use the "
                    "same weighting (and the same delivered set)")
        return wsum

    # -- chunked finalize programs --------------------------------------
    def _build_finalize(self):
        """fedex/reinit chunked finalize: normalise the accumulators, form
        the residual (fedex) or ideal (reinit) and fold into W0. Divergence
        comes free from the dense residual: ‖Σŵ·ab − (Σŵa)(Σŵb)‖_F/√(mn) —
        the INGEST-weighted convention (equal to the stacked close's
        uniform-over-delivered metric whenever ingest weights are uniform)."""
        specs, scale, method = self.specs, self.scale, self.method

        def _fin(w0_leaves, acc, winv):
            new_w0, glob, parts = {}, {}, []
            for s in specs:
                ga = acc["ga/" + s.key] * winv
                gb = acc["gb/" + s.key] * winv
                mean_prod = acc["prod/" + s.key] * winv
                res = mean_prod - jnp.matmul(ga, gb)
                upd = mean_prod if method == "reinit" else res
                new_w0[s.key] = (w0_leaves[s.key].astype(jnp.float32)
                                 + scale * upd).astype(s.w0_dtype)
                if method == "fedex":
                    glob[s.key] = {"a": ga, "b": gb}
                m, n = s.a_shape[-2], s.b_shape[-1]
                fro = jnp.sqrt(jnp.maximum(
                    jnp.sum(res * res, axis=(-2, -1)), 0.0)) / np.sqrt(m * n)
                parts.append(fro.ravel())
            div = jnp.concatenate(parts).mean() if parts else jnp.float32(0)
            return new_w0, glob, div

        donate = (0, 1) if self._donate else ()
        return _jit_f32(_fin, donate_argnums=donate)

    def _build_kl_finalize(self):
        """keep_local chunked finalize, part 1: the shared ideal update
        Σŵ·ab / W plus the divergence (same residual identity as above)."""
        specs = self.specs

        def _fin(acc, winv):
            ideal, parts = {}, []
            for s in specs:
                ga = acc["ga/" + s.key] * winv
                gb = acc["gb/" + s.key] * winv
                mp_ = acc["prod/" + s.key] * winv
                ideal[s.key] = mp_
                res = mp_ - jnp.matmul(ga, gb)
                m, n = s.a_shape[-2], s.b_shape[-1]
                parts.append((jnp.sqrt(jnp.maximum(
                    jnp.sum(res * res, axis=(-2, -1)), 0.0))
                    / np.sqrt(m * n)).ravel())
            div = jnp.concatenate(parts).mean() if parts else jnp.float32(0)
            return ideal, div

        return _jit_f32(_fin)

    def _build_kl_chunk(self):
        """keep_local chunked finalize, part 2 — one chunk of lanes: every
        lane's own base gets W0_c + scale·(ideal − a_c b_c). Op-for-op the
        stacked jnp branch restricted to this chunk's lanes, so chunked
        keep_local closes stay bitwise twins on exactly-representable data."""
        specs, scale = self.specs, self.scale

        def _klc(w0c, stacks, ideal):
            out = {}
            for s in specs:
                a = stacks[s.key + "/a"].astype(jnp.float32)
                b = stacks[s.key + "/b"].astype(jnp.float32)
                own = jnp.matmul(a, b)
                out[s.key] = (w0c[s.key].astype(jnp.float32)
                              + scale * (ideal[s.key][None] - own)
                              ).astype(s.w0_dtype)
            return out

        donate = (0,) if self._donate else ()
        return _jit_f32(_klc, donate_argnums=donate)

    def _build_svd_gram(self):
        """fedex_svd chunked, stage 1: the (i, j) chunk-pair Gram blocks
        G_L[i,j] = L_iᵀ L_j and G_R[i,j] = R_i R_jᵀ — tiles of the exact
        stacked (C·r)² Grams, accumulated pair-wise so no more than two
        chunks' factors are ever resident at once. The dense m×n residual
        still never exists."""
        specs = self.specs

        def _gram(ci, cj, wi, wj, bbar):
            gl, gr = {}, {}
            for s in specs:
                li = _l_block(ci[s.key + "/a"], wi)
                lj = _l_block(cj[s.key + "/a"], wj)
                ri = _r_block(ci[s.key + "/b"], bbar[s.key])
                rj = _r_block(cj[s.key + "/b"], bbar[s.key])
                gl[s.key] = jnp.einsum("...mi,...mj->...ij", li, lj)
                gr[s.key] = jnp.einsum("...in,...jn->...ij", ri, rj)
            return gl, gr

        return _jit_f32(_gram)

    def _build_svd_core(self):
        """fedex_svd chunked, stage 2: the eigh/eigh/svd core on the
        assembled Grams — identical math to factored_truncated_residual, but
        returning the UNSCALED projection operators (and the top singular
        values separately) so stage 3 can stream chunks through them."""
        specs, rank = self.specs, self.svd_rank

        def _core(gl, gr):
            out = {}
            for s in specs:
                el, vl = jnp.linalg.eigh(gl[s.key])
                er, vr = jnp.linalg.eigh(gr[s.key])
                il, sl = _safe_inv_sqrt(el)
                ir, sr = _safe_inv_sqrt(er)
                core = sl[..., :, None] * (jnp.swapaxes(vl, -1, -2) @ vr) \
                    * sr[..., None, :]
                u, sv, vt = jnp.linalg.svd(core, full_matrices=False)
                projl = (vl * il[..., None, :]) @ u[..., :, :rank]
                projr = vt[..., :rank, :] @ jnp.swapaxes(
                    vr * ir[..., None, :], -1, -2)
                out[s.key] = (projl, sv[..., :rank], projr)
            return out

        return _jit_f32(_core)

    def _build_svd_proj(self):
        """fedex_svd chunked, stage 3 — one chunk: accumulate its block of
        A' = Σ_k L_k projL_k and B' = Σ_k projR_k R_k (slot order again)."""
        specs = self.specs

        def _proj(stacks, w, projl_i, projr_i, bbar, ap, bp):
            new_ap, new_bp = {}, {}
            for s in specs:
                li = _l_block(stacks[s.key + "/a"], w)
                ri = _r_block(stacks[s.key + "/b"], bbar[s.key])
                new_ap[s.key] = ap[s.key] + li @ projl_i[s.key]
                new_bp[s.key] = bp[s.key] + projr_i[s.key] @ ri
            return new_ap, new_bp

        donate = (5, 6) if self._donate else ()
        return _jit_f32(_proj, donate_argnums=donate)

    def _build_svd_fin(self):
        """fedex_svd chunked, stage 4: scale A' by the singular values (the
        stacked close's op order), fold the rank-r' product into W0, and read
        the divergence off the Grams: ‖ΔW‖²_F = Σ_ij G_L∘G_R."""
        specs, scale = self.specs, self.scale

        def _fin(w0_leaves, ap, sr, bp, acc, winv, gl, gr):
            new_w0, glob, parts = {}, {}, []
            for s in specs:
                apr = ap[s.key] * sr[s.key][..., None, :]
                new_w0[s.key] = (w0_leaves[s.key].astype(jnp.float32)
                                 + scale * jnp.matmul(apr, bp[s.key])
                                 ).astype(s.w0_dtype)
                glob[s.key] = {"a": acc["ga/" + s.key] * winv,
                               "b": acc["gb/" + s.key] * winv}
                fro_sq = jnp.maximum(jnp.einsum(
                    "...ij,...ij->...", gl[s.key], gr[s.key]), 0.0)
                m, n = s.a_shape[-2], s.b_shape[-1]
                parts.append((jnp.sqrt(fro_sq) / np.sqrt(m * n)).ravel())
            div = jnp.concatenate(parts).mean() if parts else jnp.float32(0)
            return new_w0, glob, div

        donate = (0,) if self._donate else ()
        return _jit_f32(_fin, donate_argnums=donate)

    def _build_hetero_core(self):
        """hetero chunked, stage 2: the eigh/eigh/svd core on the
        UNCENTERED Grams at each spec's template rank r_max —
        factored_truncated_product's math, returning the unscaled
        projection operators (and singular values separately) so stage 3
        can stream chunks through them."""
        specs = self.specs

        def _core(gl, gr):
            out = {}
            for s in specs:
                rank = s.a_shape[-1]
                el, vl = jnp.linalg.eigh(gl[s.key])
                er, vr = jnp.linalg.eigh(gr[s.key])
                il, sl = _safe_inv_sqrt(el)
                ir, sr = _safe_inv_sqrt(er)
                core = sl[..., :, None] * (jnp.swapaxes(vl, -1, -2) @ vr) \
                    * sr[..., None, :]
                u, sv, vt = jnp.linalg.svd(core, full_matrices=False)
                projl = (vl * il[..., None, :]) @ u[..., :, :rank]
                projr = vt[..., :rank, :] @ jnp.swapaxes(
                    vr * ir[..., None, :], -1, -2)
                out[s.key] = (projl, sv[..., :rank], projr)
            return out

        return _jit_f32(_core)

    def _build_hetero_fin(self):
        """hetero chunked, stage 3½: the balanced √s split of the streamed
        projections — factored_truncated_product's final op order, so the
        chunked factors match the stacked close's convention."""
        specs = self.specs

        def _fin(ap0, sr, bp0):
            ap, bp = {}, {}
            for s in specs:
                sq = jnp.sqrt(jnp.maximum(sr[s.key], 0.0))
                ap[s.key] = ap0[s.key] * sq[..., None, :]
                bp[s.key] = sq[..., :, None] * bp0[s.key]
            return ap, bp

        return _jit_f32(_fin)

    def _build_hetero_chunk(self):
        """hetero chunked, stage 4 — one chunk of lanes: every lane's own
        base gets W0_c + scale·(ideal − (A'∘mask_c) B'), where mask_c zeroes
        the shared truncation's columns past the lane's true rank — the
        leading-slice Eckart–Young truncation, computed without slicing so
        one program serves every rank in the fleet."""
        specs, scale = self.specs, self.scale

        def _hc(w0c, masks, ap, bp, ideal):
            out = {}
            for s in specs:
                r = s.a_shape[-1]
                mk = masks[:, :r]
                shaped = mk.reshape(
                    (mk.shape[0],) + (1,) * (ap[s.key].ndim - 1) + (r,))
                own = jnp.matmul(ap[s.key][None] * shaped, bp[s.key][None])
                out[s.key] = (w0c[s.key].astype(jnp.float32)
                              + scale * (ideal[s.key][None] - own)
                              ).astype(s.w0_dtype)
            return out

        donate = (0,) if self._donate else ()
        return _jit_f32(_hc, donate_argnums=donate)

    # -- chunked closes --------------------------------------------------
    def _slot_weights(self, entry, w) -> np.ndarray:
        """Slot-indexed NORMALISED weights (the cross-check already proved
        they match the close-time vector; use the close-time values so the
        L blocks equal the stacked close's columns)."""
        nslots = entry["num_chunks"] * self.buffers.chunk
        wn = np.zeros(nslots, np.float32)
        ncopy = min(len(w), nslots)
        wn[:ncopy] = np.asarray(w, np.float32)[:ncopy]
        return wn

    def _pairwise_grams(self, entry, wn, bbar, round_id):
        """(i, j) chunk-pair Gram tiles assembled into the full (C·r)²
        Grams — shared by the fedex_svd (centered) and hetero (uncentered;
        zero ``bbar``) chunked closes. At most two chunks' factors are ever
        resident at once; the dense m×n residual still never exists."""
        chunk, nk = self.buffers.chunk, entry["num_chunks"]
        dev = {}

        def _chunk_dev(k):
            if k not in dev:
                dev.clear()  # at most ONE cached chunk besides the current
                dev[k] = {p: jnp.asarray(x)
                          for p, x in entry["retained"][k].items()}
            return dev[k]

        gram = self._programs.get(("svdgram",), self._build_svd_gram,
                                  self.rec)
        blocks = [[None] * nk for _ in range(nk)]
        for i in range(nk):
            ci = {p: jnp.asarray(x) for p, x in entry["retained"][i].items()}
            wi = jnp.asarray(wn[i * chunk:(i + 1) * chunk])
            for j in range(i + 1):
                cj = ci if j == i else _chunk_dev(j)
                wj = wi if j == i else jnp.asarray(
                    wn[j * chunk:(j + 1) * chunk])
                blocks[i][j] = gram(ci, cj, wi, wj, bbar)
        # assemble the full Grams from the block tiles (Gram symmetry gives
        # the upper triangle as transposes)
        gl_full, gr_full = {}, {}
        for s in self.specs:
            rows_l, rows_r = [], []
            for i in range(nk):
                row_l, row_r = [], []
                for j in range(nk):
                    if j <= i:  # computed pair: blocks[i][j] IS G(i, j)
                        bl, br = blocks[i][j]
                        row_l.append(bl[s.key])
                        row_r.append(br[s.key])
                    else:  # mirror: G(i, j) = G(j, i)ᵀ (Gram symmetry)
                        bl, br = blocks[j][i]
                        row_l.append(jnp.swapaxes(bl[s.key], -1, -2))
                        row_r.append(jnp.swapaxes(br[s.key], -1, -2))
                rows_l.append(jnp.concatenate(row_l, axis=-1))
                rows_r.append(jnp.concatenate(row_r, axis=-1))
            gl_full[s.key] = jnp.concatenate(rows_l, axis=-2)
            gr_full[s.key] = jnp.concatenate(rows_r, axis=-2)
        return gl_full, gr_full

    def _stream_projection(self, entry, wn, proj_ops, bbar, rank_of):
        """Stream every retained chunk through the projection operators:
        A' = Σ_k L_k projL_k and B' = Σ_k projR_k R_k (slot order).
        ``rank_of(spec)`` is the truncation width — ``svd_rank`` for
        fedex_svd, the template r_max for hetero."""
        chunk, nk = self.buffers.chunk, entry["num_chunks"]
        proj = self._programs.get(("svdproj",), self._build_svd_proj,
                                  self.rec)
        ap = {s.key: jnp.zeros(s.a_shape[:-1] + (rank_of(s),), jnp.float32)
              for s in self.specs}
        bp = {s.key: jnp.zeros(s.b_shape[:-2] + (rank_of(s), s.b_shape[-1]),
                               jnp.float32) for s in self.specs}
        for i in range(nk):
            ci = {p: jnp.asarray(x) for p, x in entry["retained"][i].items()}
            wi = jnp.asarray(wn[i * chunk:(i + 1) * chunk])
            projl_i, projr_i = {}, {}
            for s in self.specs:
                projl, _sv, projr = proj_ops[s.key]
                cr = chunk * s.a_shape[-1]
                projl_i[s.key] = projl[..., i * cr:(i + 1) * cr, :]
                projr_i[s.key] = projr[..., :, i * cr:(i + 1) * cr]
            ap, bp = proj(ci, wi, projl_i, projr_i, bbar, ap, bp)
        return ap, bp

    def _svd_chunked(self, w0_leaves, entry, w, winv, round_id):
        """Orchestrate the four svd stages over the retained chunks. Memory:
        at most two chunks' factors + the (C·r)² Grams are live — the Grams
        dominate exactly as in the stacked close (they ARE the method), but
        the full (C, …) factor stacks never materialise on device."""
        acc = entry["acc"]
        bbar = {s.key: acc["gb/" + s.key] * winv for s in self.specs}
        wn = self._slot_weights(entry, w)
        gl_full, gr_full = self._pairwise_grams(entry, wn, bbar, round_id)
        gram_bytes = _tree_bytes(gl_full) + _tree_bytes(gr_full)
        self._note_peak(round_id, 2 * gram_bytes + _tree_bytes(acc))
        core = self._programs.get(("svdcore",), self._build_svd_core,
                                  self.rec)
        proj_ops = core(gl_full, gr_full)
        ap, bp = self._stream_projection(entry, wn, proj_ops, bbar,
                                         lambda s: self.svd_rank)
        self._note_peak(round_id, gram_bytes + _tree_bytes(ap)
                        + _tree_bytes(bp) + _tree_bytes(w0_leaves)
                        + _tree_bytes(acc))
        sr = {s.key: proj_ops[s.key][1] for s in self.specs}
        fin = self._programs.get(("svdfin",), self._build_svd_fin, self.rec)
        return fin(w0_leaves, ap, sr, bp, acc, jnp.float32(winv),
                   gl_full, gr_full)

    def _chunked_obs(self, round_id, entry, t0) -> None:
        """Mirror _dispatch's per-close metrics for the chunked path."""
        rec = self.rec
        if not rec.enabled:
            return
        dispatch_us = (time.perf_counter_ns() - t0) / 1e3
        if round_id is not None:
            rec.round_set(round_id, method=self.method, chunked=1,
                          close_dispatch_us=round(dispatch_us, 1),
                          partial_folds=entry["eager_folds"],
                          ring_occupancy=len(self.buffers.open_rounds),
                          ring_evictions=self.buffers.evictions,
                          stale_drops=self.buffers.stale_drops,
                          replay_drops=self.buffers.replay_drops,
                          duplicate_drops=self.buffers.duplicate_drops)

    def _close_chunked(self, params: Params, client_ids: Sequence[int],
                       weights: Optional[Sequence[float]], *,
                       round_id, rng: Optional[jax.Array]
                       ) -> Tuple[Params, Params, DeferredDivergence]:
        """Chunked fedex / fedex_svd / reinit close: flush the trailing
        chunks in slot order, normalise the streamed accumulators by the
        total ingest weight, and finalize — the full (C, …) stacks never
        exist on device, so peak close memory is O(chunk) + accumulators
        (+ the (C·r)² Grams for fedex_svd, which needs them regardless)."""
        w, _mask, _uniform = self.weight_vector(client_ids, weights, round_id)
        rid, entry = self.buffers.take_chunked(round_id)
        wsum = self._check_ingest_weights(entry, w, rid)
        winv = jnp.float32(1.0 / np.float32(wsum))
        w0_leaves = self._w0_leaves(params)
        t0 = time.perf_counter_ns()
        with self.rec.span("close.dispatch", cat="engine", round=rid,
                           method=self.method, uniform=False, chunked=True):
            if self.method == "fedex_svd":
                new_w0, glob, div = self._svd_chunked(w0_leaves, entry, w,
                                                      winv, rid)
            else:
                self._note_peak(rid, _tree_bytes(w0_leaves)
                                + _tree_bytes(entry["acc"])
                                + self._prod_temp_bytes())
                fin = self._programs.get(("cfin", self.method),
                                         self._build_finalize, self.rec)
                new_w0, glob, div = fin(w0_leaves, entry["acc"], winv)
        self._chunked_obs(rid, entry, t0)
        self._finish_peak(rid)
        new_params = self._fold_back(params, new_w0)
        if self.method == "reinit":
            global_lora = agg.reinit_adapters(self._lora_template, rng)
        else:
            flat = {}
            for s in self.specs:
                flat[s.key + "/a"] = glob[s.key]["a"]
                flat[s.key + "/b"] = glob[s.key]["b"]
            global_lora = unflatten_from_paths(flat)
        return global_lora, new_params, DeferredDivergence(
            div, rid, recorder=self.rec if self.rec.enabled else None)

    def _close_keep_local_chunked(self, client_params: Sequence[Params],
                                  client_ids: Sequence[int],
                                  weights: Optional[Sequence[float]], *,
                                  round_id
                                  ) -> Tuple[Dict[int, Params],
                                             DeferredDivergence]:
        """Chunked keep_local close: one shared ideal from the accumulators,
        then each retained chunk's lanes fold their OWN bases chunk-by-chunk
        in slot order — peak memory holds one chunk of per-lane W0s instead
        of all C_max of them."""
        w, _mask, _uniform = self.weight_vector(client_ids, weights, round_id)
        lanes = self.buffers.lanes(round_id)
        lane_to_cid = {lane: cid for cid, lane in lanes.items()}
        delivered = set(client_ids)
        rid, entry = self.buffers.take_chunked(round_id)
        self._check_ingest_weights(entry, w, rid)
        wsum = float(np.asarray(entry["w"], np.float64).sum())
        winv = jnp.float32(1.0 / np.float32(wsum))
        chunk = self.buffers.chunk
        t0 = time.perf_counter_ns()
        out: Dict[int, Params] = {}
        with self.rec.span("close.dispatch", cat="engine", round=rid,
                           method=self.method, uniform=False, chunked=True):
            fin = self._programs.get(("klfin",), self._build_kl_finalize,
                                     self.rec)
            ideal, div = fin(entry["acc"], winv)
            klc = self._programs.get(("klchunk",), self._build_kl_chunk,
                                     self.rec)
            for k in range(entry["num_chunks"]):
                rows = [lane_to_cid.get(k * chunk + row)
                        for row in range(chunk)]
                if not any(cid in delivered for cid in rows
                           if cid is not None):
                    continue
                w0c = {}
                for s in self.specs:
                    leaves = []
                    for cid in rows:
                        p = (client_params[cid] if cid is not None
                             else client_params[0])
                        node = _get_path(p, s.key)
                        leaves.append(node["kernel"] if s.has_kernel
                                      else node)
                    w0c[s.key] = jnp.stack(leaves)
                stacks = {p: jnp.asarray(x)
                          for p, x in entry["retained"][k].items()}
                self._note_peak(rid, _tree_bytes(ideal) + _tree_bytes(w0c)
                                + _tree_bytes(stacks)
                                + _tree_bytes(entry["acc"]))
                new_chunk = klc(w0c, stacks, ideal)
                for row, cid in enumerate(rows):
                    if cid is None or cid not in delivered:
                        continue
                    newp = client_params[cid]
                    for s in self.specs:
                        leaf = new_chunk[s.key][row]
                        if s.has_kernel:
                            node = dict(_get_path(client_params[cid], s.key),
                                        kernel=leaf)
                            newp = _set_path(newp, s.key, node)
                        else:
                            newp = _set_path(newp, s.key, leaf)
                    out[cid] = newp
        self._chunked_obs(rid, entry, t0)
        self._finish_peak(rid)
        return out, DeferredDivergence(
            div, rid, recorder=self.rec if self.rec.enabled else None)

    # -- hetero helpers --------------------------------------------------
    def _client_rank(self, cid: int) -> int:
        """Client ``cid``'s TRUE adapter rank (template r_max when no
        per-client spec was registered)."""
        rmax = self.specs[0].a_shape[-1]
        if self.client_ranks is None:
            return rmax
        return int(self.client_ranks[cid])

    def _rank_vector(self, client_ids, lanes) -> np.ndarray:
        """(C_max,) int32 slot-indexed rank vector for the delivered set —
        0 on non-delivered lanes (fully masked), the registered true rank on
        delivered ones. Rides in the close's ``mask`` argument slot."""
        ranks = np.zeros(self.c_max, np.int32)
        for cid in client_ids:
            ranks[lanes[cid]] = self._client_rank(cid)
        return ranks

    def _writeback_lane(self, client_params, cid, new_stacks, lane):
        """Client ``cid``'s params with lane ``lane`` of the per-lane W0
        output stacks folded back in (keep_local/hetero write-back)."""
        newp = client_params[cid]
        for s in self.specs:
            leaf = new_stacks[s.key][lane]
            if s.has_kernel:
                node = dict(_get_path(client_params[cid], s.key),
                            kernel=leaf)
                newp = _set_path(newp, s.key, node)
            else:
                newp = _set_path(newp, s.key, leaf)
        return newp

    def _hetero_loras(self, glob_flat, client_ids, ranks, lanes
                      ) -> Dict[int, Params]:
        """Per-client rank-r_i adapters: the LEADING slices of the shared
        r_max truncation factors (the balanced √s split makes the leading-
        r_i slice the Eckart–Young rank-r_i truncation of the same mean)."""
        out: Dict[int, Params] = {}
        for cid in client_ids:
            r_i = int(ranks[lanes[cid]])
            flat = {}
            for s in self.specs:
                flat[s.key + "/a"] = glob_flat[s.key + "/a"][..., :, :r_i]
                flat[s.key + "/b"] = glob_flat[s.key + "/b"][..., :r_i, :]
            out[cid] = unflatten_from_paths(flat)
        return out

    def _close_hetero_chunked(self, client_params: Sequence[Params],
                              client_ids: Sequence[int],
                              weights: Optional[Sequence[float]], *,
                              round_id
                              ) -> Tuple[Dict[int, Params],
                                         Dict[int, Params], Params,
                                         DeferredDivergence]:
        """Chunked hetero close: ideal + divergence from the streamed
        accumulators (ingest-weighted convention, as every chunked close),
        the shared r_max truncation from UNCENTERED pairwise chunk Grams
        (``_pairwise_grams`` with a zero centering vector — dense m×n never
        formed), then each retained chunk's lanes fold their OWN bases with
        rank-masked truncations, one chunk of per-lane W0s resident at a
        time."""
        w, _mask, _uniform = self.weight_vector(client_ids, weights,
                                                round_id)
        lanes = self.buffers.lanes(round_id)
        lane_to_cid = {lane: cid for cid, lane in lanes.items()}
        delivered = set(client_ids)
        ranks = self._rank_vector(client_ids, lanes)
        rmax = self.specs[0].a_shape[-1]
        rid, entry = self.buffers.take_chunked(round_id)
        wsum = self._check_ingest_weights(entry, w, rid)
        winv = jnp.float32(1.0 / np.float32(wsum))
        chunk = self.buffers.chunk
        # lane rank masks over ALL slots (chunks may pad past C_max)
        nslots = entry["num_chunks"] * chunk
        slot_ranks = np.zeros(nslots, np.int32)
        slot_ranks[:len(ranks)] = ranks
        rmask = (np.arange(rmax)[None, :]
                 < slot_ranks[:, None]).astype(np.float32)
        t0 = time.perf_counter_ns()
        out: Dict[int, Params] = {}
        with self.rec.span("close.dispatch", cat="engine", round=rid,
                           method=self.method, uniform=False, chunked=True):
            fin = self._programs.get(("klfin",), self._build_kl_finalize,
                                     self.rec)
            ideal, div = fin(entry["acc"], winv)
            zero_bbar = {s.key: jnp.zeros(s.b_shape, jnp.float32)
                         for s in self.specs}
            wn = self._slot_weights(entry, w)
            gl_full, gr_full = self._pairwise_grams(entry, wn, zero_bbar,
                                                    rid)
            self._note_peak(rid, 2 * (_tree_bytes(gl_full)
                                      + _tree_bytes(gr_full))
                            + _tree_bytes(entry["acc"]))
            core = self._programs.get(("hcore",), self._build_hetero_core,
                                      self.rec)
            proj_ops = core(gl_full, gr_full)
            ap0, bp0 = self._stream_projection(entry, wn, proj_ops,
                                               zero_bbar,
                                               lambda s: s.a_shape[-1])
            hfin = self._programs.get(("hfin",), self._build_hetero_fin,
                                      self.rec)
            sr = {s.key: proj_ops[s.key][1] for s in self.specs}
            ap, bp = hfin(ap0, sr, bp0)
            hc = self._programs.get(("hchunk",), self._build_hetero_chunk,
                                    self.rec)
            for k in range(entry["num_chunks"]):
                rows = [lane_to_cid.get(k * chunk + row)
                        for row in range(chunk)]
                if not any(cid in delivered for cid in rows
                           if cid is not None):
                    continue
                w0c = {}
                for s in self.specs:
                    leaves = []
                    for cid in rows:
                        p = (client_params[cid] if cid is not None
                             else client_params[0])
                        node = _get_path(p, s.key)
                        leaves.append(node["kernel"] if s.has_kernel
                                      else node)
                    w0c[s.key] = jnp.stack(leaves)
                masks = jnp.asarray(rmask[k * chunk:(k + 1) * chunk])
                self._note_peak(rid, _tree_bytes(ideal) + _tree_bytes(w0c)
                                + _tree_bytes(ap) + _tree_bytes(bp)
                                + _tree_bytes(entry["acc"]))
                new_chunk = hc(w0c, masks, ap, bp, ideal)
                for row, cid in enumerate(rows):
                    if cid is None or cid not in delivered:
                        continue
                    out[cid] = self._writeback_lane(
                        client_params, cid, new_chunk, row)
        self._chunked_obs(rid, entry, t0)
        self._finish_peak(rid)
        glob_flat = {}
        for s in self.specs:
            glob_flat[s.key + "/a"] = ap[s.key]
            glob_flat[s.key + "/b"] = bp[s.key]
        global_lora = unflatten_from_paths(glob_flat)
        client_loras = self._hetero_loras(glob_flat, client_ids, ranks,
                                          lanes)
        return out, client_loras, global_lora, DeferredDivergence(
            div, rid, recorder=self.rec if self.rec.enabled else None)

    # ------------------------------------------------------------------
    def close(self, params: Params, client_ids: Sequence[int],
              weights: Optional[Sequence[float]] = None, *,
              round_id=None, rng: Optional[jax.Array] = None
              ) -> Tuple[Params, Params, DeferredDivergence]:
        """Close the round over the delivered subset (fedex / fedex_svd /
        reinit methods — keep_local closes through :meth:`close_keep_local`).

        Returns ``(global_lora, new_params, divergence)``. ``params`` W0
        leaves and the streamed stacks are donated to the close program.
        The divergence comes back as a :class:`DeferredDivergence` device
        handle — NO host sync happens inside the close; the caller resolves
        the handle at its next round boundary (or on first numeric use).
        ``reinit`` additionally needs the round's ``rng`` and returns the
        freshly drawn adapters (identical to ``aggregation.reinit_adapters``)
        as the new global.
        """
        if self.method == "keep_local":
            raise ValueError("keep_local engine closes per-client bases — "
                             "use close_keep_local()")
        if self.method == "hetero":
            raise ValueError("hetero engine closes per-client bases — "
                             "use close_hetero()")
        if self.method == "reinit" and rng is None:
            raise ValueError("reinit close needs the round's rng")
        if round_id is None and self.buffers.open_rounds:
            round_id = self.buffers.open_rounds[0]  # oldest — same as take()
        self._validate_delivered(client_ids, round_id)
        if self.buffers.is_chunked(round_id):
            return self._close_chunked(params, client_ids, weights,
                                       round_id=round_id, rng=rng)
        w, mask, uniform = self.weight_vector(client_ids, weights, round_id)
        w0_leaves = self._w0_leaves(params)
        stacks = self.buffers.take(round_id)
        new_w0, glob, div = self._dispatch(w0_leaves, stacks, w, mask,
                                           uniform, round_id)
        self._finish_peak(round_id)
        new_params = self._fold_back(params, new_w0)
        if self.method == "reinit":
            global_lora = agg.reinit_adapters(self._lora_template, rng)
        else:
            flat = {}
            for s in self.specs:
                flat[s.key + "/a"] = glob[s.key]["a"]
                flat[s.key + "/b"] = glob[s.key]["b"]
            global_lora = unflatten_from_paths(flat)
        return global_lora, new_params, DeferredDivergence(
            div, round_id, recorder=self.rec if self.rec.enabled else None)

    def close_keep_local(self, client_params: Sequence[Params],
                         client_ids: Sequence[int],
                         weights: Optional[Sequence[float]] = None, *,
                         round_id=None
                         ) -> Tuple[Dict[int, Params], DeferredDivergence]:
        """Close a keep_local round: every DELIVERED client's own base gets
        its residual Σ_j w_j·a_j b_j − a_i b_i folded in, all lanes in one
        jitted dispatch over (C_max, …)-stacked per-lane W0 buffers.

        ``client_params`` is the trainer's per-client params list (indexed by
        client id). Returns ``({client_id: new_params}, divergence)`` for the
        delivered subset only — non-delivered lanes' outputs are discarded.
        The divergence is a :class:`DeferredDivergence` (no host sync here).
        """
        if self.method != "keep_local":
            raise ValueError(f"engine method is {self.method!r}, "
                             "not keep_local")
        if round_id is None and self.buffers.open_rounds:
            round_id = self.buffers.open_rounds[0]  # oldest — same as take()
        self._validate_delivered(client_ids, round_id)
        if self.buffers.is_chunked(round_id):
            return self._close_keep_local_chunked(client_params, client_ids,
                                                  weights, round_id=round_id)
        w, mask, uniform = self.weight_vector(client_ids, weights, round_id)
        lanes = self.buffers.lanes(round_id)
        lane_to_cid = {lane: cid for cid, lane in lanes.items()}
        w0_stacks = {}
        for s in self.specs:
            leaves = []
            for lane in range(self.c_max):
                cid = lane_to_cid.get(lane)
                p = client_params[cid] if cid is not None else client_params[0]
                node = _get_path(p, s.key)
                leaves.append(node["kernel"] if s.has_kernel else node)
            w0_stacks[s.key] = jnp.stack(leaves)
        stacks = self.buffers.take(round_id)
        new_stacks, _, div = self._dispatch(w0_stacks, stacks, w, mask,
                                            uniform, round_id)
        self._finish_peak(round_id)
        out: Dict[int, Params] = {}
        for cid in client_ids:
            out[cid] = self._writeback_lane(client_params, cid, new_stacks,
                                            lanes[cid])
        return out, DeferredDivergence(
            div, round_id, recorder=self.rec if self.rec.enabled else None)

    def close_hetero(self, client_params: Sequence[Params],
                     client_ids: Sequence[int],
                     weights: Optional[Sequence[float]] = None, *,
                     round_id=None
                     ) -> Tuple[Dict[int, Params], Dict[int, Params],
                                Params, DeferredDivergence]:
        """Close a rank-heterogeneous round (the paper's §6 open question,
        engine-side): ONE shared rank-r_max Eckart–Young truncation of the
        weighted factored mean — computed from (C·r_max)² Grams, the dense
        m×n mean never formed — then every DELIVERED client's own base
        absorbs ΔW_i = Δ̄ − a'_i b'_i, where (a'_i, b'_i) is the LEADING
        rank-r_i slice of the shared factors (the balanced √s split makes
        that slice the optimal rank-r_i truncation). Every client then
        satisfies W0_i + ΔW_i + a'_i b'_i = W0 + Δ̄ exactly.

        ``client_params`` is the trainer's per-client params list (indexed
        by client id); ranks come from the engine's ``client_ranks``
        registry (template r_max when unset). Returns
        ``({cid: new_params}, {cid: rank-r_i lora}, global_lora,
        divergence)`` — the global is the shared r_max truncation, the
        divergence a :class:`DeferredDivergence` (no host sync here).
        """
        if self.method != "hetero":
            raise ValueError(f"engine method is {self.method!r}, "
                             "not hetero")
        if round_id is None and self.buffers.open_rounds:
            round_id = self.buffers.open_rounds[0]  # oldest — same as take()
        self._validate_delivered(client_ids, round_id)
        if self.buffers.is_chunked(round_id):
            return self._close_hetero_chunked(client_params, client_ids,
                                              weights, round_id=round_id)
        w, mask, uniform = self.weight_vector(client_ids, weights, round_id)
        lanes = self.buffers.lanes(round_id)
        lane_to_cid = {lane: cid for cid, lane in lanes.items()}
        ranks = self._rank_vector(client_ids, lanes)
        rmax = self.specs[0].a_shape[-1]
        # the bitwise-stable uniform branch additionally needs every
        # delivered lane at full rank (no masking anywhere)
        uniform = uniform and bool(np.all(ranks == rmax))
        w0_stacks = {}
        for s in self.specs:
            leaves = []
            for lane in range(self.c_max):
                cid = lane_to_cid.get(lane)
                p = (client_params[cid] if cid is not None
                     else client_params[0])
                node = _get_path(p, s.key)
                leaves.append(node["kernel"] if s.has_kernel else node)
            w0_stacks[s.key] = jnp.stack(leaves)
        stacks = self.buffers.take(round_id)
        new_stacks, glob, div = self._dispatch(w0_stacks, stacks, w, ranks,
                                               uniform, round_id)
        self._finish_peak(round_id)
        out: Dict[int, Params] = {}
        for cid in client_ids:
            out[cid] = self._writeback_lane(client_params, cid, new_stacks,
                                            lanes[cid])
        glob_flat = {}
        for s in self.specs:
            glob_flat[s.key + "/a"] = glob[s.key]["a"]
            glob_flat[s.key + "/b"] = glob[s.key]["b"]
        global_lora = unflatten_from_paths(glob_flat)
        client_loras = self._hetero_loras(glob_flat, client_ids, ranks,
                                          lanes)
        return out, client_loras, global_lora, DeferredDivergence(
            div, round_id, recorder=self.rec if self.rec.enabled else None)
