"""Mesh-mode federated rounds: co-scheduled clients, one pjit'd close.

The host-orchestrated trainer (core/federated.py) runs clients sequentially
and closes rounds through the streaming engine. THIS module is the
datacenter twin (``launch/train.py --mode mesh``): client state is STACKED
on a leading ``(C_max, …)`` axis sharded over a ``client`` mesh axis
(launch/mesh.make_client_mesh + sharding/specs.client_stack_spec), and each
phase of a round is ONE pjit'd program:

* **local training** — ``make_mesh_round_fn`` vmaps a client's whole
  ``local_steps`` scan over the client axis, so every client's AdamW steps
  for the round run in a single compiled program (lanes co-scheduled on the
  mesh; base params replicated across the client axis, adapters/optimizer
  state/batches lane-sharded).
* **the round close** — the engine's weighted close program
  (core/engine.make_close_fn, jnp backend) compiled over the client-sharded
  stacks: weighted factor means, the exact residual fold into W0 and the §6
  divergence. Under GSPMD the ``Σ_c w_c·…`` reductions over the sharded
  client axis lower to psum-mean collectives — the masked psum-mean.

Partial participation / weighting contract (same C_max padding contract as
the streaming engine): lane c always belongs to client c; a round's sampled
subset and its weights enter ONLY through the ``(C_max,)`` weight vector —
zero weight masks a lane exactly (its factors vanish from every sum), so a
50 % sampled round, an example-weighted round and a full uniform round all
reuse the SAME compiled close program. One program per (method, shapes)
signature, asserted via the close's compile-cache count in
tests/test_mesh_round.py. Non-sampled lanes still train (the hardware lanes
exist either way — their updates are simply masked at the close); their
compute is the padding cost, not a correctness concern.

Numerics: mesh mode always takes the engine's weighted branch (there is no
bitwise-uniform branch here — a uniform round is just the uniform weight
vector), which matches the eager weighted oracle to tight float32 tolerance
(≤ ~1e-5; see docs/architecture.md for the full contract table).

Overlap: the close returns its divergence as a
core/engine.DeferredDivergence device handle — the mesh loop resolves it at
the next round boundary, never inside the close.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.configs.base import FedConfig, LoRAConfig, TrainConfig
from repro.core import aggregation as agg
from repro.core.engine import (DeferredDivergence, build_factor_specs,
                               collect_w0_leaves, fold_back_w0, make_close_fn)
from repro.core.federated import (RoundRecord, evaluate_on_batches,
                                  make_eval_fn, resolve_divergences)
from repro.core.lora import init_lora
from repro.optim import adamw_update, clip_by_global_norm, init_adamw, lr_at
from repro.sharding import client_axis_size, client_stack_spec
from repro.util.logging import get_logger
from repro.util.tree import flatten_with_paths, unflatten_from_paths

logger = get_logger("mesh_train")

Params = Dict[str, Any]

MESH_METHODS = ("fedex", "fedex_svd")


# --------------------------------------------------------------------------
# the stacked local-training program (one pjit'd program per round)
# --------------------------------------------------------------------------

def make_mesh_round_fn(model, lora_scale: float,
                       train_cfg: TrainConfig,
                       masked: bool = False, lanes: int = 1) -> Callable:
    """One round of local training for ALL lanes in a single jitted program.

    ``round_fn(params, lora_stack, batches, lrs)`` scans a lane's
    ``local_steps`` of clipped AdamW (identical math to
    core/federated.make_local_step) and vmaps the scan over the leading
    client axis; ``batches`` leaves are ``(C_max, steps, B, …)``, ``lrs`` is
    the precomputed ``(steps,)`` schedule slice (shared by every lane, like
    the host trainer). Returns ``(new_lora_stack, losses (C_max, steps))``.
    Base ``params`` broadcast unsharded across lanes; the adapter stack and
    batches shard over the client axis where the caller placed them so XLA
    partitions lane compute across the mesh.

    ``masked=True`` compiles the uneven-budget variant:
    ``round_fn(params, lora_stack, batches, lrs, budgets)`` takes a
    per-lane ``(C_max,)`` int step-budget vector and freezes lane c's
    adapter/optimizer state once ``t ≥ budgets[c]`` (``jnp.where`` selects
    on every leaf — the scan stays co-scheduled, dead iterations are the
    padding cost). A frozen lane's reported losses repeat its last live
    loss, so ``losses[:, -1]`` remains "the lane's final training loss".
    The default path compiles WITHOUT the masking selects and is
    bitwise-unchanged.

    ``lanes`` is how many lanes share one device: the forward's remat
    reckoning sees one lane's shapes and multiplies by it.
    """

    def one_lane(params, lora, batches, lrs, budget):
        opt_state = init_adamw(lora)

        def body(carry, xs):
            lora, opt_state, t, last = carry
            batch, lr = xs

            def loss_fn(l):
                return model.loss(params, batch, lora=l,
                                  lora_scale=lora_scale, lanes=lanes)

            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(lora)
            grads, _ = clip_by_global_norm(grads, train_cfg.grad_clip)
            new_lora, new_opt = adamw_update(
                grads, opt_state, lora, learning_rate=lr,
                beta1=train_cfg.beta1, beta2=train_cfg.beta2,
                eps=train_cfg.eps, weight_decay=train_cfg.weight_decay)
            if masked:
                live = t < budget
                sel = lambda n, o: jnp.where(live, n, o)  # noqa: E731
                new_lora = jax.tree.map(sel, new_lora, lora)
                new_opt = jax.tree.map(sel, new_opt, opt_state)
                loss = jnp.where(live, loss, last)
            return (new_lora, new_opt, t + 1, loss), loss

        (lora, _, _, _), losses = jax.lax.scan(
            body, (lora, opt_state, jnp.int32(0), jnp.float32(0.0)),
            (batches, lrs))
        return lora, losses

    if masked:
        def round_fn(params, lora_stack, batches, lrs, budgets):
            return jax.vmap(one_lane, in_axes=(None, 0, 0, None, 0))(
                params, lora_stack, batches, lrs, budgets)
    else:
        def round_fn(params, lora_stack, batches, lrs):
            return jax.vmap(one_lane, in_axes=(None, 0, 0, None, None))(
                params, lora_stack, batches, lrs, jnp.int32(0))

    return jax.jit(round_fn)


# --------------------------------------------------------------------------
# the mesh close: the engine's weighted program over client-sharded stacks
# --------------------------------------------------------------------------

class MeshRoundCloser:
    """Masked psum-mean round close for mesh mode.

    Wraps the engine's weighted close program (core/engine.make_close_fn,
    jnp backend — its client-axis einsum reductions are what GSPMD lowers to
    collectives over the ``client`` mesh axis) with the mesh-mode lane
    contract: lane c IS client c, and a round's participation pattern lives
    entirely in the ``(C_max,)`` weight vector, so every round of a run —
    full, sampled, weighted — hits ONE compiled program per (method, shapes)
    signature (``compiled_programs`` exposes the cache count for the tests).

    The close returns the divergence as a :class:`DeferredDivergence` — no
    host sync inside the close; resolve at the next round boundary.
    """

    def __init__(self, mesh, params: Params, lora_template: Params, *,
                 c_max: int, scale: float, method: str = "fedex",
                 svd_rank: int = 0, donate: bool = False, recorder=None):
        if method not in MESH_METHODS:
            raise ValueError(
                f"mesh mode closes {MESH_METHODS} rounds, got {method!r} "
                "(the §6 assignment strategies are host-orchestrated — "
                "see core/federated.py)")
        from repro.obs import NULL
        self.mesh = mesh
        self.c_max = c_max
        self.method = method
        self.rec = recorder if recorder is not None else NULL
        self.specs = build_factor_specs(params, lora_template)
        self._close = make_close_fn(self.specs, scale=scale, c_max=c_max,
                                    method=method, svd_rank=svd_rank,
                                    backend="jnp", donate=donate)

    # ------------------------------------------------------------------
    @property
    def compiled_programs(self) -> int:
        """How many close programs have been compiled (the padding-contract
        promise is that this stays at 1 per (method, shapes) signature no
        matter how participation or weights vary across rounds)."""
        return self._close._cache_size()

    def stack_shardings(self, stacks: Dict[str, jnp.ndarray]):
        """path → NamedSharding placing each (C_max, …) stack's leading axis
        on the ``client`` mesh axis (divisibility-guarded)."""
        return {p: NamedSharding(self.mesh, client_stack_spec(p, x, self.mesh))
                for p, x in stacks.items()}

    def shard_stacks(self, stacks: Dict[str, jnp.ndarray]
                     ) -> Dict[str, jnp.ndarray]:
        shardings = self.stack_shardings(stacks)
        return {p: jax.device_put(stacks[p], shardings[p]) for p in stacks}

    def weight_vector(self, client_ids: Sequence[int],
                      weights: Optional[Sequence[float]] = None
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """(C_max,) weight vector + 0/1 mask for the sampled subset.

        Lane c ≡ client c (mesh mode co-schedules every lane); non-sampled
        lanes get weight 0 — the participation mask. Uniform-over-subset
        when ``weights`` is None."""
        if not client_ids:
            raise ValueError("cannot close a round with no participants")
        ids = sorted(client_ids)
        if len(set(ids)) != len(ids):
            raise ValueError(f"duplicate client ids in {list(client_ids)}")
        if ids[0] < 0 or ids[-1] >= self.c_max:
            raise ValueError(f"client ids {ids} outside [0, {self.c_max})")
        mask = np.zeros(self.c_max, np.float32)
        mask[ids] = 1.0
        w = np.zeros(self.c_max, np.float32)
        norm = agg.normalize_weights(weights, len(ids))
        if norm is None:
            w[ids] = 1.0 / len(ids)
        else:
            # norm[i] belongs to client_ids[i] — pair in the CALLER's order
            # (lane c ≡ client c regardless of how the subset was listed)
            for cid, wi in zip(client_ids, norm):
                w[cid] = wi
        return w, mask

    # ------------------------------------------------------------------
    def close(self, params: Params, stacks: Dict[str, jnp.ndarray],
              client_ids: Sequence[int],
              weights: Optional[Sequence[float]] = None, *, round_id=None
              ) -> Tuple[Params, Params, DeferredDivergence]:
        """Close a mesh round over the sampled subset.

        ``stacks`` is the flattened client-stacked adapter tree (path →
        ``(C_max, …)``, e.g. a round_fn output through
        :func:`flatten_with_paths`). Returns ``(global_lora, new_params,
        divergence)`` exactly like the streaming engine's close, with the
        divergence deferred."""
        w, mask = self.weight_vector(client_ids, weights)
        w0_leaves = collect_w0_leaves(self.specs, params)
        rec = self.rec
        if rec.enabled:
            import time as _time
            before = self._close._cache_size()
            t0 = _time.perf_counter_ns()
            with rec.span("close.dispatch", cat="engine", round=round_id,
                          method=self.method, mesh=True):
                new_w0, glob, div = self._close(
                    w0_leaves, stacks, jnp.asarray(w), jnp.asarray(mask),
                    uniform=False)
            dispatch_us = (_time.perf_counter_ns() - t0) / 1e3
            compiled = self._close._cache_size() > before
            sig = f"mesh:{self.method}"
            rec.counter(
                f"engine.compile_{'miss' if compiled else 'hit'}[{sig}]").inc()
            if round_id is not None:
                rec.round_set(round_id, method=self.method,
                              close_dispatch_us=round(dispatch_us, 1),
                              compile_miss=int(compiled))
        else:
            new_w0, glob, div = self._close(w0_leaves, stacks, jnp.asarray(w),
                                            jnp.asarray(mask), uniform=False)
        new_params = fold_back_w0(self.specs, params, new_w0)
        flat = {}
        for s in self.specs:
            flat[s.key + "/a"] = glob[s.key]["a"]
            flat[s.key + "/b"] = glob[s.key]["b"]
        return (unflatten_from_paths(flat), new_params,
                DeferredDivergence(div, round_id,
                                   recorder=rec if rec.enabled else None))


# --------------------------------------------------------------------------
# the mesh-mode federated loop
# --------------------------------------------------------------------------

@dataclass
class MeshFederatedTrainer:
    """Mesh-mode orchestration: every round is two pjit'd programs.

    The loop mirrors core/federated.FederatedTrainer's record format but
    replaces host-side orchestration with the stacked programs above:
    sampling draws a per-round subset (seeded, like the fedsrv registry),
    ALL lanes run the local-training program from the broadcast global
    adapter, and the masked weighted close folds the exact residual server-
    side. Divergence handles resolve at round boundaries (overlap-aware).
    """

    model: Any
    lora_cfg: LoRAConfig
    fed_cfg: FedConfig
    train_cfg: TrainConfig
    client_loaders: List[Any]
    eval_batches: List[Dict] = field(default_factory=list)
    seed: int = 0
    mesh: Any = None
    # obs recorder (repro.obs). None → built from fed_cfg.obs.
    recorder: Any = None

    def __post_init__(self):
        from repro.launch.mesh import make_client_mesh

        if self.recorder is None:
            from repro.obs import make_recorder
            self.recorder = make_recorder(self.fed_cfg.obs)

        fc = self.fed_cfg
        if fc.method not in MESH_METHODS:
            raise ValueError(f"--mode mesh supports {MESH_METHODS}, "
                             f"got {fc.method!r}")
        rng = jax.random.key(self.seed)
        rp, rl = jax.random.split(rng)
        if self.mesh is None:
            self.mesh = make_client_mesh(fc.num_clients)
        # commit base params REPLICATED on the mesh up front: the close's
        # updated W0 leaves come back committed, and matching shardings on
        # round 0 keep every round on the same compiled program (the
        # one-program-per-signature contract). The init runs under jit with
        # that output sharding, so no device first holds a staging copy.
        from jax.sharding import PartitionSpec as P
        self.params = jax.jit(
            self.model.init,
            out_shardings=NamedSharding(self.mesh, P()))(rp)
        self.global_lora = init_lora(rl, self.params, self.model.cfg,
                                     self.lora_cfg)
        if not self.global_lora:
            raise ValueError("no LoRA targets matched — check target_modules")
        self.scale = self.lora_cfg.scale
        method = fc.method
        svd_rank = fc.svd_rank
        if method == "fedex_svd" and not svd_rank:
            method = "fedex"  # svd_rank=0 means exact (config contract)
        self.closer = MeshRoundCloser(
            self.mesh, self.params, self.global_lora,
            c_max=fc.num_clients, scale=self.scale, method=method,
            svd_rank=svd_rank, recorder=self.recorder)
        # uneven per-lane step budgets compile the masked-scan variant;
        # the default budget-free path keeps its bitwise-unchanged program
        self._budgets = (jnp.asarray(fc.client_local_steps, jnp.int32)
                         if fc.client_local_steps else None)
        # lanes per device: the client axis splits them, or (when it does
        # not divide C_max) every device holds them all
        n_axis = client_axis_size(self.mesh)
        lanes = (fc.num_clients // n_axis if fc.num_clients % n_axis == 0
                 else fc.num_clients)
        self.round_fn = make_mesh_round_fn(
            self.model, self.scale, self.train_cfg,
            masked=self._budgets is not None, lanes=lanes)
        self.eval_fn = make_eval_fn(self.model, self.scale)
        self.history: List[RoundRecord] = []
        self._total_steps = fc.rounds * fc.local_steps
        self._examples = [len(l.sequences) for l in self.client_loaders]
        # mesh fault injection (fedsrv/faults.py): only the adapter-VALUE
        # kinds apply — co-scheduled lanes cross no wire, so codec and
        # addressing faults have nothing to corrupt. Faulty lanes are
        # quarantined by DROPPING their ids from the close's subset: the
        # weight vector masks them to exact zero, same program, exact close
        # over the survivors.
        self.fault_injector = None
        if fc.faults:
            from repro.fedsrv.faults import (MESH_KINDS, FaultInjector,
                                             FaultPlan)
            plan = FaultPlan.parse(fc.faults, seed=fc.seed)
            self.fault_injector = FaultInjector(plan,
                                                recorder=self.recorder)
            skipped = sorted({s.kind for s in plan.specs
                              if s.kind not in MESH_KINDS})
            if skipped:
                logger.warning(
                    "mesh mode applies value faults %s only; plan kind(s) %s "
                    "need a wire/ring and are skipped", MESH_KINDS,
                    ", ".join(skipped))

    # ------------------------------------------------------------------
    def _sample_round(self, rnd: int) -> Tuple[List[int],
                                               Optional[List[float]]]:
        """Seeded participant subset + optional example-count weights."""
        fc = self.fed_cfg
        k = fc.num_clients
        n = max(1, int(round(fc.participation * k)))
        rng = np.random.default_rng((self.seed, rnd))
        ids = sorted(rng.choice(k, size=n, replace=False).tolist())
        weights = None
        if fc.weighting == "examples":
            weights = [float(self._examples[c % len(self._examples)])
                       for c in ids]
        return ids, weights

    def _stack_batches(self, steps: int) -> Dict[str, jnp.ndarray]:
        """(C_max, steps, B, …) batch stacks, lane c fed by loader c."""
        per_lane = []
        for c in range(self.fed_cfg.num_clients):
            loader = self.client_loaders[c % len(self.client_loaders)]
            per_lane.append([loader.next_batch() for _ in range(steps)])
        return jax.tree.map(
            lambda *leaves: jnp.stack(leaves),
            *[jax.tree.map(lambda *xs: jnp.stack(xs), *lane)
              for lane in per_lane])

    def _shard_client_tree(self, tree):
        """Place each (C_max, …) leaf's leading axis on the client mesh axis
        so the training program's lanes partition across the mesh."""
        return jax.tree.map(
            lambda x: jax.device_put(
                x, NamedSharding(self.mesh,
                                 client_stack_spec("", x, self.mesh))),
            tree)

    def _resolve_divergences(self) -> None:
        resolve_divergences(self.history)

    def _screen_lanes(self, rnd: int, stacks: Dict[str, Any],
                      ids: List[int], weights: Optional[List[float]]):
        """Apply the round's mesh value faults, then quarantine bad lanes.

        A lane fails the screen when any leaf is non-finite or (with
        ``uplink_max_norm`` set) its ∞-norm exceeds the limit; failing ids
        are dropped from the subset so the close's weight vector masks them
        to exact zero. Returns (stacks', survivors, weights', quarantined)."""
        fc = self.fed_cfg
        host = {p: np.array(x) for p, x in stacks.items()}
        if self.recorder.enabled:
            self.recorder.host_sync("uplink", len(host), rnd)
        survivors: List[int] = []
        surv_w: List[float] = []
        quarantined: List[Tuple[int, str]] = []
        for j, cid in enumerate(ids):
            lane = {p: host[p][cid] for p in host}
            lane2, applied = self.fault_injector.corrupt_lane(rnd, cid, lane)
            if applied:
                for p in host:
                    host[p][cid] = lane2[p]
            bad = ""
            for p in host:
                if not np.isfinite(host[p][cid]).all():
                    bad = "nonfinite"
                    break
                if (fc.uplink_max_norm > 0
                        and np.abs(host[p][cid]).max() > fc.uplink_max_norm):
                    bad = "norm"
                    break
            if bad:
                # zero the lane, don't just mask it: 0·NaN = NaN, so a
                # poisoned lane must never reach the close's weighted sums
                # (mirrors the streaming sink, where a quarantined uplink
                # never writes its lane)
                for p in host:
                    host[p][cid] = 0
                quarantined.append((cid, bad))
                if self.recorder.enabled:
                    self.recorder.counter(f"uplink.quarantined[{bad}]").inc()
                self.recorder.event("uplink.quarantine", cat="fedsrv",
                                    round=rnd, client=cid, reason=bad)
            else:
                survivors.append(cid)
                if weights is not None:
                    surv_w.append(weights[j])
        return (host, survivors,
                surv_w if weights is not None else None, quarantined)

    # ------------------------------------------------------------------
    def run(self) -> List[RoundRecord]:
        fc = self.fed_cfg
        c = fc.num_clients
        step0 = 0
        for rnd in range(fc.rounds):
            lrs = jnp.asarray([
                lr_at(step0 + s, base_lr=self.train_cfg.learning_rate,
                      total_steps=self._total_steps,
                      warmup_ratio=self.train_cfg.warmup_ratio,
                      kind=self.train_cfg.schedule)
                for s in range(fc.local_steps)], jnp.float32)
            ids, weights = self._sample_round(rnd)
            n_sampled = len(ids)

            # downlink broadcast: every lane starts from the global adapter
            lora_stack = self._shard_client_tree(jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (c,) + x.shape),
                self.global_lora))
            batches = self._shard_client_tree(
                self._stack_batches(fc.local_steps))
            with self.recorder.span("mesh.train_round", cat="trainer",
                                    round=rnd, lanes=c):
                if self._budgets is not None:
                    new_stack, losses = self.round_fn(
                        self.params, lora_stack, batches, lrs, self._budgets)
                else:
                    new_stack, losses = self.round_fn(self.params, lora_stack,
                                                      batches, lrs)
            # round boundary: the PREVIOUS close's divergence resolves only
            # after this round's training program has been dispatched, so
            # the in-flight close overlaps lane compute (mesh-mode twin of
            # the host trainer's resolve-after-uplinks ordering)
            self._resolve_divergences()

            stacks_flat = dict(flatten_with_paths(new_stack))
            quarantined: List[Tuple[int, str]] = []
            if self.fault_injector is not None:
                stacks_flat, ids, weights, quarantined = self._screen_lanes(
                    rnd, stacks_flat, ids, weights)
            if not ids:
                # every sampled lane quarantined: degraded round — the
                # global adapter and base params carry forward unchanged
                div: Any = 0.0
                if self.recorder.enabled:
                    self.recorder.counter("round.degraded").inc()
                self.recorder.event("round.degraded", cat="fedsrv",
                                    round=rnd, delivered=0,
                                    quarantined=len(quarantined))
                logger.warning("round=%d DEGRADED: every lane quarantined; "
                               "global carried forward", rnd)
            else:
                stacks = self.closer.shard_stacks(stacks_flat)
                with self.recorder.span("round.close", cat="trainer",
                                        round=rnd, mesh=True):
                    self.global_lora, self.params, div = self.closer.close(
                        self.params, stacks, ids, weights, round_id=rnd)

            step0 += fc.local_steps
            with self.recorder.span("round.eval", cat="trainer", round=rnd,
                                    batches=len(self.eval_batches)):
                ev_loss, ev_acc = self._evaluate(rnd)
            if self.recorder.enabled:
                self.recorder.round_set(rnd, sampled=n_sampled,
                                        delivered=len(ids),
                                        quarantined=len(quarantined),
                                        degraded=int(not ids),
                                        eval_loss=round(ev_loss, 6),
                                        eval_acc=round(ev_acc, 6))
            if self.recorder.enabled and self.fault_injector is not None:
                finite = all(
                    bool(np.isfinite(np.asarray(x, np.float32)).all())
                    for x in jax.tree.leaves(self.global_lora))
                self.recorder.round_set(rnd, global_finite=int(finite))
            lane_losses = np.asarray(losses)[:, -1]
            rec = RoundRecord(
                round=rnd, client_losses=([float(lane_losses[i]) for i in ids]
                                          or [float("nan")]),
                eval_loss=ev_loss, eval_acc=ev_acc, divergence_scaled=div,
                lr=float(lrs[0]))
            if self.recorder.enabled:
                self.recorder.host_sync("step", 1, rnd)
                self.recorder.host_sync("schedule", 1, rnd)
            self.history.append(rec)
            logger.info(
                "round=%d mode=mesh sampled=%d/%d delivered=%d "
                "quarantined=%d eval_loss=%.4f eval_acc=%.4f div=deferred "
                "programs=%d", rnd, n_sampled, c, len(ids), len(quarantined),
                ev_loss, ev_acc, self.closer.compiled_programs)
        self._resolve_divergences()
        return self.history

    def _evaluate(self, rnd: int) -> Tuple[float, float]:
        return evaluate_on_batches(self.eval_fn, self.params,
                                   self.global_lora, self.eval_batches,
                                   self.recorder, rnd)
