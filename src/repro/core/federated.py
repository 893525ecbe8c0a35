"""Federated fine-tuning driver (paper §4.2 pipeline, host-orchestrated).

Simulates the paper's cross-silo setting: k clients, each doing ``local_steps``
of AdamW on its LoRA adapters per round, followed by server aggregation
(fedex / fedit / ffa / fedex_svd / centralized) and — for FedEx — the residual
fold-in ``W0 ← W0 + (α/r)·ΔW_res`` (Eq. 14).

Round *orchestration* is delegated to the fedsrv coordinator (fedsrv/): the
trainer injects ``train_fn`` (one client's local steps, DP, keep_local base
selection) and the coordinator decides WHO runs and WHAT arrives — client
sampling, seeded dropout/stragglers, deadlines, uplink quantization, async
buffered commits. The seed behavior (all k clients, uniform weights, no
transport) is exactly the coordinator's trivial policy, bit-for-bit. The
trainer then dispatches the method-specific CLOSE (aggregation + residual
fold) over the delivered subset with the round's weights.

This is the *reference orchestration*: one process, clients sequential, every
client step jit'd. The mesh-parallel launcher (launch/mesh_train.py, via
``launch/train.py --mode mesh``) vmaps clients over a mesh axis and replaces
the host-side tree arithmetic with collectives — both paths run the SAME
close program over the same aggregation math (core/aggregation.py).

Overlap-aware closes: when the fused engine is on, the round close returns
its §6 divergence as a ``DeferredDivergence`` DEVICE handle — the trainer
records it un-synced and resolves it at the NEXT round boundary, so the
close's dispatch returns immediately and the RoundBuffers ring can stream
round N+1 uplinks while round N's close executes.
"""

from __future__ import annotations

import copy
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import FedConfig, LoRAConfig, TrainConfig
from repro.core import aggregation as agg
from repro.core.divergence import mean_deviation
from repro.core.lora import init_lora
from repro.obs import NULL, NULL_SPAN
from repro.optim import adamw_update, clip_by_global_norm, init_adamw, lr_at
from repro.util.logging import get_logger

logger = get_logger("federated")


def _freeze_a(grads):
    return agg.map_factors(lambda f: {"a": jnp.zeros_like(f["a"]), "b": f["b"]}, grads)


def make_local_step(model, lora_scale: float, train_cfg: TrainConfig,
                    freeze_a: bool = False) -> Callable:
    """The jitted local step: ``(lora, opt_state, loss, stats)``. ``stats``
    holds ``grad_norm`` and, for a model with routed-expert layers, the
    loss's ``metrics["moe"]`` routing counts."""
    @jax.jit
    def step(params, lora, opt_state, batch, lr):
        def loss_fn(l):
            return model.loss(params, batch, lora=l, lora_scale=lora_scale)

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(lora)
        if freeze_a:
            grads = _freeze_a(grads)
        grads, gnorm = clip_by_global_norm(grads, train_cfg.grad_clip)
        lora, opt_state = adamw_update(
            grads, opt_state, lora, learning_rate=lr,
            beta1=train_cfg.beta1, beta2=train_cfg.beta2, eps=train_cfg.eps,
            weight_decay=train_cfg.weight_decay)
        return lora, opt_state, loss, dict(metrics.get("moe", {}),
                                           grad_norm=gnorm)

    return step


def make_eval_fn(model, lora_scale: float) -> Callable:
    @jax.jit
    def ev(params, lora, batch):
        loss, metrics = model.loss(params, batch, lora=lora, lora_scale=lora_scale)
        return metrics["loss"], metrics["accuracy"]

    return ev


@dataclass
class RoundRecord:
    round: int
    client_losses: List[float]
    eval_loss: float
    eval_acc: float
    # FedIT-vs-ideal deviation of this round's adapters. On the engine path
    # this briefly holds a core/engine.DeferredDivergence device handle; the
    # trainer swaps in the float at the next round boundary (run() never
    # returns records with unresolved handles).
    divergence_scaled: float
    lr: float


def evaluate_on_batches(eval_fn, params, lora, batches, recorder=NULL,
                        round_id=None) -> tuple[float, float]:
    """Mean (loss, accuracy) of ``eval_fn`` over ``batches`` (NaNs when
    empty). Shared by the host and mesh trainers; ``recorder`` spans each
    batch's dispatch and its two-scalar host sync."""
    if not batches:
        return float("nan"), float("nan")
    ls, accs = [], []
    for b in batches:
        with recorder.span("eval.dispatch", cat="trainer"):
            l, a = eval_fn(params, lora, b)
        with recorder.span("eval.sync", cat="trainer"):
            ls.append(float(l))
            accs.append(float(a))
        if recorder.enabled:
            recorder.host_sync("eval", 2, round_id)
    return sum(ls) / len(ls), sum(accs) / len(accs)


def resolve_divergences(history: List["RoundRecord"]) -> None:
    """Round-boundary host sync: swap any DeferredDivergence handles in the
    history for their float values. This is the ONLY place a trainer blocks
    on a close's device scalar — the close itself returns without a host
    transfer, so the ring's next-round uplink decoding overlaps the
    in-flight close on accelerators. Shared by the host and mesh trainers."""
    from repro.core.engine import DeferredDivergence

    for rec in history:
        if isinstance(rec.divergence_scaled, DeferredDivergence):
            rec.divergence_scaled = rec.divergence_scaled.resolve()


@dataclass
class FederatedTrainer:
    model: Any
    lora_cfg: LoRAConfig
    fed_cfg: FedConfig
    train_cfg: TrainConfig
    client_loaders: List[Any]
    eval_batches: List[Dict] = field(default_factory=list)
    seed: int = 0
    # obs recorder (repro.obs). None → built from fed_cfg.obs; pass a shared
    # Recorder to collect several trainer runs into one trace/metrics stream
    # (examples/coordinator_sim.py does this, one run label per scenario).
    recorder: Any = None

    def __post_init__(self):
        import dataclasses as _dc

        if self.recorder is None:
            from repro.obs import make_recorder
            self.recorder = make_recorder(self.fed_cfg.obs)

        rng = jax.random.key(self.seed)
        rp, rl = jax.random.split(rng)
        self.params = self.model.init(rp)
        self.global_lora = init_lora(rl, self.params, self.model.cfg, self.lora_cfg)
        if not self.global_lora:
            raise ValueError("no LoRA targets matched — check target_modules")
        self.scale = self.lora_cfg.scale
        self.method = self.fed_cfg.method
        freeze = self.method == "ffa"
        self.local_step = make_local_step(self.model, self.scale, self.train_cfg,
                                          freeze_a=freeze)
        # step.saved_residual_bytes, reckoned at the first step's shapes
        self._residuals_gauged = False
        self.eval_fn = make_eval_fn(self.model, self.scale)
        self.history: List[RoundRecord] = []
        # fedsrv RoundOutcome per standard round; adapter payloads are kept
        # only on the LAST entry (older rounds have delivery.lora stripped)
        self.outcomes: List[Any] = []
        # keep_local assignment needs per-client frozen bases
        self.client_params: Optional[List] = None
        if self.fed_cfg.assignment == "keep_local" and self.method == "fedex":
            self.client_params = [self.params for _ in range(self.fed_cfg.num_clients)]
        self._global_step = 0
        self._total_steps = self.fed_cfg.rounds * self.fed_cfg.local_steps
        self._last_div = 0.0
        self._start_round = 0  # advanced by load_state (crash-safe resume)
        # heterogeneous ranks (beyond-paper; core/hetero.py + engine
        # method="hetero"): per-client adapters of rank rᵢ + per-client
        # frozen bases for the residual fold. ``method="hetero"`` without
        # explicit ranks runs every client at lora.rank (uniform hetero).
        self.hetero = bool(self.fed_cfg.client_ranks) or self.method == "hetero"
        if self.hetero:
            self.client_ranks = list(self.fed_cfg.client_ranks) or (
                [self.lora_cfg.rank] * self.fed_cfg.num_clients)
            assert len(self.client_ranks) == self.fed_cfg.num_clients
            self._client_lora = [
                init_lora(jax.random.fold_in(rl, i), self.params, self.model.cfg,
                          _dc.replace(self.lora_cfg, rank=r))
                for i, r in enumerate(self.client_ranks)]
            self.client_params = [self.params] * self.fed_cfg.num_clients
        from repro.configs.base import validate_fed_lora
        validate_fed_lora(self.fed_cfg, self.lora_cfg)
        self.coordinator = self._build_coordinator()
        # fused round-close engine (core/engine.py): every engine-covered
        # method — fedex with any §6 assignment (average / keep_local /
        # reinit), fedex_svd, and the ragged-rank hetero close — runs in ONE
        # jitted program over streamed (C_max, …) stacks. Everything else
        # (fedit/ffa/centralized) keeps the eager list-of-trees ground truth.
        self.engine = None
        eng_method = None
        if self.fed_cfg.engine != "off":
            if self.hetero:
                # ragged uplinks pad to r_max = lora.rank at ingest; the
                # close masks each lane back to its true rank
                eng_method = "hetero"
            elif self.method == "fedex":
                eng_method = {"average": "fedex",
                              "keep_local": "keep_local",
                              "reinit": "reinit"}[self.fed_cfg.assignment]
            elif self.method == "fedex_svd":
                # svd_rank=0 means exact (config contract) → the fedex close
                eng_method = "fedex_svd" if self.fed_cfg.svd_rank else "fedex"
        if eng_method is not None:
            from repro.core.engine import RoundCloseEngine
            self.engine = RoundCloseEngine(
                self.params, self.global_lora,
                c_max=self.fed_cfg.num_clients, scale=self.scale,
                method=eng_method, svd_rank=self.fed_cfg.svd_rank,
                backend=self.fed_cfg.engine,
                depth=self.fed_cfg.ring_depth,
                recorder=self.recorder,
                chunk=self.fed_cfg.close_chunk,
                client_ranks=self.client_ranks if self.hetero else None)
            self.coordinator.sink = self.engine.buffers

    def _build_coordinator(self):
        """fedsrv coordinator from FedConfig; defaults = the trivial policy
        (all clients, no deadline/dropout, uniform weights, fp32 transport),
        which reproduces the seed's hard-coded loop bit-for-bit."""
        from repro.fedsrv import (AdapterCodec, AsyncBufferCoordinator,
                                  BytesLedger, ClientInfo, ClientRegistry,
                                  RoundCoordinator, RoundPolicy,
                                  StragglerModel, ValidationPolicy)

        fc = self.fed_cfg
        clients = [
            ClientInfo(client_id=i, num_examples=len(
                self.client_loaders[i % len(self.client_loaders)].sequences))
            for i in range(fc.num_clients)]
        registry = ClientRegistry(clients, seed=fc.seed)
        policy = RoundPolicy(participation=fc.participation,
                             min_quorum=fc.min_quorum,
                             deadline=fc.round_deadline,
                             weighting=fc.weighting)
        stragglers = StragglerModel(
            mean_latency=fc.mean_latency, jitter=fc.latency_jitter,
            dropout_prob=fc.dropout_prob, straggler_prob=fc.straggler_prob,
            straggler_factor=fc.straggler_factor, seed=fc.seed)
        codec = AdapterCodec(fc.quantize_uplink,
                             validation=ValidationPolicy(
                                 enabled=fc.uplink_validation,
                                 max_norm=fc.uplink_max_norm))
        self.ledger = BytesLedger()
        # seeded fault-injection layer (fedsrv/faults.py): exercised only
        # when a fault plan is configured — the clean path carries a None
        # injector and is bitwise-unchanged.
        self._chaos = bool(fc.faults)
        self.fault_injector = None
        if self._chaos:
            from repro.fedsrv.faults import FaultInjector, FaultPlan
            self.fault_injector = FaultInjector(
                FaultPlan.parse(fc.faults, seed=fc.seed),
                recorder=self.recorder)
        if fc.async_buffer > 0:
            return AsyncBufferCoordinator(
                registry, policy, stragglers, codec, self.ledger,
                buffer_size=fc.async_buffer,
                staleness_alpha=fc.staleness_alpha,
                max_version_lag=fc.ring_max_lag,
                recorder=self.recorder, faults=self.fault_injector,
                uplink_retries=fc.uplink_retries,
                retry_backoff=fc.retry_backoff)
        return RoundCoordinator(registry, policy, stragglers, codec,
                                self.ledger, recorder=self.recorder,
                                faults=self.fault_injector,
                                uplink_retries=fc.uplink_retries,
                                retry_backoff=fc.retry_backoff)

    # ------------------------------------------------------------------
    def _close_round(self, rnd: int, outcome, client_loras: List, weights):
        """Method-specific round close over the delivered subset (weighted)."""
        if self.engine is not None:
            # fused single-dispatch close: weighted factor means + the
            # method-specific residual fold + divergence in one jitted
            # program over the streamed stacks (W0 leaves and stacks
            # donated). No dense m×n residual tree ever exists host-side —
            # the svd close truncates on the factored Grams, the assignment
            # closes fold through the signed/per-client kernels.
            rid = outcome.round_id
            if self.engine.method == "keep_local":
                new_cp, self._last_div = self.engine.close_keep_local(
                    self.client_params, outcome.client_ids, weights,
                    round_id=rid)
                for cid, lora_i in zip(outcome.client_ids, client_loras):
                    self._client_lora[cid] = lora_i
                    self.client_params[cid] = new_cp[cid]
                self.global_lora = client_loras[0]
                return
            rng = (jax.random.key(self.seed + rnd)
                   if self.engine.method == "reinit" else None)
            self.global_lora, self.params, self._last_div = self.engine.close(
                self.params, outcome.client_ids, weights, round_id=rid,
                rng=rng)
            # ledger the truncation rank clamped to the delivered subset's
            # bound k_d·r — singular triplets past it are identically zero
            # and never transmitted (mirrors the eager path's clamp)
            k_d = len(outcome.client_ids)
            self._ledger_residual(
                rnd, None, k_d,
                truncated_rank=(min(self.engine.svd_rank,
                                    self.lora_cfg.rank * k_d)
                                if self.engine.method == "fedex_svd" else 0),
                leaf_shapes=[s.w0_shape for s in self.engine.specs])
            return
        k_d = len(client_loras)
        if self.method == "fedit":
            self.global_lora = agg.fedit_aggregate(client_loras, weights)
        elif self.method == "ffa":
            self.global_lora = agg.ffa_aggregate(client_loras, weights)
        elif self.method == "fedex_svd":
            # clamp to the DELIVERED subset's rank bound k_d·r: config-time
            # validation bounds r' by k·r only, and ranks past the bound are
            # pure padding (fedex_svd_aggregate rejects them).
            svd_rank = min(self.fed_cfg.svd_rank or self.lora_cfg.rank * k_d,
                           self.lora_cfg.rank * k_d)
            self.global_lora, residual = agg.fedex_svd_aggregate(
                client_loras, svd_rank, weights)
            self.params = agg.apply_residual(self.params, residual, self.scale)
            self._ledger_residual(rnd, residual, k_d, truncated_rank=svd_rank)
        elif self.method == "fedex":
            if self.fed_cfg.assignment == "average":
                self.global_lora, residual = agg.fedex_aggregate(
                    client_loras, weights)
                self.params = agg.apply_residual(self.params, residual, self.scale)
                self._ledger_residual(rnd, residual, k_d)
            elif self.fed_cfg.assignment == "reinit":
                new_loras, residual = agg.assign_after_aggregation(
                    "reinit", client_loras, jax.random.key(self.seed + rnd),
                    weights)
                self.global_lora = new_loras[0]
                self.params = agg.apply_residual(self.params, residual, self.scale)
                self._ledger_residual(rnd, residual, k_d)
            elif self.fed_cfg.assignment == "keep_local":
                residuals = agg.per_client_residuals(client_loras, weights)
                for cid, lora_i, res_i in zip(outcome.client_ids, client_loras,
                                              residuals):
                    self._client_lora[cid] = lora_i
                    self.client_params[cid] = agg.apply_residual(
                        self.client_params[cid], res_i, self.scale)
                self.global_lora = client_loras[0]
            else:
                raise ValueError(self.fed_cfg.assignment)
        else:
            raise ValueError(f"unknown method {self.method!r}")

    def _ledger_residual(self, rnd: int, residual, k_delivered: int,
                         truncated_rank: int = 0,
                         leaf_shapes: Optional[List[tuple]] = None) -> None:
        """Account the server→client residual broadcast in the bytes ledger
        (factored form of core/decompose.py, never the dense m×n matrix).
        ``leaf_shapes`` replaces ``residual`` on the engine path, where no
        dense residual tree exists — only the adapted W0 leaf shapes."""
        import numpy as np

        from repro.core.decompose import (factored_residual_params,
                                          truncated_residual_params)

        if leaf_shapes is None:
            leaf_shapes = [leaf.shape for leaf in jax.tree.leaves(residual)]
        per_client = 0
        for shape in leaf_shapes:
            if len(shape) < 2:
                continue
            copies = int(np.prod(shape[:-2])) if len(shape) > 2 else 1
            m, n = int(shape[-2]), int(shape[-1])
            if truncated_rank:
                per_client += copies * truncated_residual_params(
                    m, n, truncated_rank)
            else:
                per_client += copies * factored_residual_params(
                    m, n, self.lora_cfg.rank, k_delivered)
        self.ledger.record_analytic(rnd, "downlink", per_client * k_delivered,
                                    note="factored-residual broadcast")

    # ------------------------------------------------------------------
    def _reconcile_comm(self, rnd: int, outcome) -> None:
        """Surface the round's measured ledger totals as round metrics and —
        where the analytic table applies — reconcile the measured param
        counts against ``core/comm.round_comm_params`` pinned to the
        OBSERVED delivered-client count. The measured ledger and the closed
        form are independent accountings of the same round; ``comm_match``
        is the per-round witness that they agree."""
        rec = self.recorder
        tot = self.ledger.round_totals(rnd)
        rec.round_set(rnd,
                      uplink_params=tot["uplink_params"],
                      uplink_bytes=tot["uplink_bytes"],
                      downlink_params=tot["downlink_params"],
                      downlink_bytes=tot["downlink_bytes"])
        k_d = len(outcome.delivered)
        if k_d == 0:
            return
        method = self.method
        if method == "fedex" and self.fed_cfg.assignment != "average":
            return  # keep_local/reinit ledger differs from the table's fedex
        if method not in ("fedex", "fedit", "fedex_svd"):
            return
        from repro.core.comm import adapted_matrices, round_comm_params
        from repro.util.tree import count_params
        try:
            mats = adapted_matrices(self.model.cfg, self.lora_cfg)
        except (AttributeError, TypeError):
            return  # model without a decoder-style config: no analytic twin
        r = self.lora_cfg.rank
        if count_params(self.global_lora) != sum(ms.m * r + r * ms.n
                                                 for ms in mats):
            return  # adapter layout ≠ the table's matrix set (e.g. subset)
        eff, svd = method, self.fed_cfg.svd_rank
        if method == "fedex_svd" and not svd:
            eff = "fedex"  # svd_rank=0 → the exact close (config contract)
        analytic = round_comm_params(
            eff, mats, r, self.fed_cfg.num_clients,
            svd_rank=min(svd, r * k_d) if svd else 0,
            participants=k_d)
        recon = self.ledger.reconcile(rnd, analytic)
        rec.round_set(rnd, comm_match=int(recon["ok"]))
        rec.counter(f"comm.reconcile_{'ok' if recon['ok'] else 'mismatch'}"
                    ).inc()
        if not recon["ok"]:
            rec.event("comm.mismatch", cat="trainer", round=rnd,
                      uplink=recon["uplink"], downlink=recon["downlink"])

    # ------------------------------------------------------------------
    def _client_round(self, client: int, params, lora, rnd: int):
        rec = self.recorder
        loader = self.client_loaders[client % len(self.client_loaders)]
        with rec.span("client.opt_init", cat="trainer"):
            opt_state = init_adamw(lora)
        losses = []
        # uneven budgets: client c stops after its own step count (mesh mode
        # expresses the same schedule as masked scan iterations)
        steps = (self.fed_cfg.client_local_steps[client]
                 if self.fed_cfg.client_local_steps
                 else self.fed_cfg.local_steps)
        for s in range(steps):
            with (rec.span("client.step", cat="trainer", round=rnd,
                           client=client, step=s)
                  if rec.tracing else NULL_SPAN):
                with rec.span("step.batch", cat="trainer"):
                    batch = loader.next_batch()
                with rec.span("step.lr", cat="trainer"):
                    lr = lr_at(self._global_step + s,
                               base_lr=self.train_cfg.learning_rate,
                               total_steps=self._total_steps,
                               warmup_ratio=self.train_cfg.warmup_ratio,
                               kind=self.train_cfg.schedule)
                if not self._residuals_gauged:
                    self._gauge_saved_residuals(params, lora, batch, rnd)
                with rec.span("step.dispatch", cat="trainer"):
                    lora, opt_state, loss, stats = self.local_step(
                        params, lora, opt_state, batch, lr)
                with rec.span("step.sync", cat="trainer"):
                    if rec.enabled and "held_rows" in stats:
                        # the routing counts come with the loss, in one fetch
                        loss, stats = jax.device_get((loss, stats))
                        rec.counter("moe.held_rows").inc(int(stats["held_rows"]))
                        rec.hist("moe.max_expert_rows").observe(
                            int(stats["max_expert_rows"]))
                    losses.append(float(loss))
            if rec.enabled:
                rec.host_sync("step", 1, rnd)
        return lora, losses

    def _gauge_saved_residuals(self, params, lora, batch, rnd: int) -> None:
        """Record the bytes the step's train scan keeps for the backward
        instead of recomputing them (0 under full remat): read from the
        loss's traced program at the step's shapes, so it is the decision the
        compiled step makes. Traced in every obs mode, so a recorded run
        traces and compiles exactly what an unrecorded one does."""
        from repro.models.transformer import saved_residual_bytes

        jaxpr = jax.make_jaxpr(
            lambda p, l, b: self.model.loss(p, b, lora=l, lora_scale=self.scale)
        )(params, lora, batch)
        n = saved_residual_bytes(jaxpr.jaxpr)
        self.recorder.gauge("step.saved_residual_bytes").set(n)
        self.recorder.round_set(rnd, saved_residual_bytes=n)
        cfg = self.model.cfg
        if getattr(cfg, "is_moe", False):
            # the expert layer's shape: experts held here, the router's
            # width, and the rows each grouped GEMM is given (tokens × k)
            tokens = batch["tokens"].size
            self.recorder.gauge("moe.experts_held").set(cfg.held_experts)
            self.recorder.gauge("moe.router_width").set(cfg.num_experts)
            self.recorder.gauge("moe.sorted_rows").set(
                tokens * cfg.num_experts_per_tok)
        self._residuals_gauged = True

    def _evaluate(self, params, lora, rnd: int) -> tuple[float, float]:
        return evaluate_on_batches(self.eval_fn, params, lora,
                                   self.eval_batches, self.recorder, rnd)

    def _resolve_divergences(self) -> None:
        resolve_divergences(self.history)

    # ------------------------------------------------------------------
    def run(self, until: Optional[int] = None) -> List[RoundRecord]:
        """Run rounds ``[_start_round, until)`` (default: all configured
        rounds). ``until`` gives tests a deterministic kill point: run part
        of a checkpointed schedule, then resume a fresh trainer via
        :meth:`load_state` — the LR schedule and every seeded draw key off
        absolute round/step indices, so the resumed half replays bitwise."""
        k = self.fed_cfg.num_clients
        stop = self.fed_cfg.rounds if until is None else until
        from repro.core.engine import DeferredDivergence

        for rnd in range(self._start_round, stop):
            with self.recorder.span("round.lr", cat="trainer", round=rnd):
                lr_now = float(lr_at(self._global_step,
                                     base_lr=self.train_cfg.learning_rate,
                                     total_steps=self._total_steps,
                                     kind=self.train_cfg.schedule,
                                     warmup_ratio=self.train_cfg.warmup_ratio))
            if self.recorder.enabled:
                self.recorder.host_sync("schedule", 1, rnd)

            if self.hetero and self.engine is not None:
                from repro.core.hetero import pad_adapters

                # engine-side ragged close: every client's rank-rᵢ adapter
                # pads to the r_max template at ingest (exact — zero columns)
                # and streams into the ring with its TRUE rank riding the
                # slot's rank vector; close_hetero masks each lane back to
                # rᵢ inside the jitted program and folds each client's own
                # residual into ITS frozen base.
                rid = self.engine.buffers.begin_round(
                    {c: c for c in range(k)}, rnd)
                client_losses = []
                delivered = []
                if self.fault_injector is not None:
                    # chaos: ragged uplinks ride the SAME defended codec
                    # path as the uniform methods — encode → corrupt →
                    # decode_into — so crashes DROP the lane and validation
                    # failures QUARANTINE it; the close runs over the
                    # surviving subset and a lost lane contributes nothing.
                    self.coordinator._ensure_spec(self.global_lora)
                for c in range(k):
                    lora_c, losses = self._client_round(
                        c, self.client_params[c], self._client_lora[c], rnd)
                    client_losses.append(losses[-1])
                    padded = pad_adapters(lora_c, self.lora_cfg.rank)
                    if self.fault_injector is None:
                        self.engine.buffers.write(
                            c, padded, round_id=rid,
                            rank=self.client_ranks[c])
                        delivered.append(c)
                        continue
                    res = self.coordinator._uplink(
                        padded, rid, c, rank=self.client_ranks[c])
                    if res.ok:
                        delivered.append(c)
                # round boundary: previous rounds' deferred divergences
                # resolve only after this round's uplinks streamed in
                self._resolve_divergences()
                with self.recorder.span("round.close", cat="trainer",
                                        round=rnd, engine=True):
                    new_cp, new_loras, self.global_lora, div = \
                        self.engine.close_hetero(
                            self.client_params, delivered,
                            round_id=rid)
                for c in delivered:
                    self.client_params[c] = new_cp[c]
                    self._client_lora[c] = new_loras[c]
                self._last_div = div
                if self.recorder.enabled:
                    # closed-round comm fields (obs_report --check): under
                    # chaos the defended path ledgers real uplink bytes;
                    # the direct ring path transmits nothing measurable
                    tot = self.ledger.round_totals(rnd)
                    self.recorder.round_set(
                        rnd,
                        uplink_params=tot["uplink_params"],
                        uplink_bytes=tot["uplink_bytes"],
                        downlink_params=tot["downlink_params"],
                        downlink_bytes=tot["downlink_bytes"])
            elif self.hetero:
                from repro.core.hetero import hetero_fedex_aggregate

                client_loras = []
                client_losses = []
                for c in range(k):
                    lora_c, losses = self._client_round(
                        c, self.client_params[c], self._client_lora[c], rnd)
                    client_loras.append(lora_c)
                    client_losses.append(losses[-1])
                new_loras, residuals = hetero_fedex_aggregate(
                    client_loras, list(self.client_ranks),
                    r_max=self.lora_cfg.rank)
                self._client_lora = new_loras
                self.client_params = [
                    agg.apply_residual(p, r_i, self.scale)
                    for p, r_i in zip(self.client_params, residuals)]
                self.global_lora = new_loras[0]
                # pre-agg deviation is rank-heterogeneous → report dispersion
                # of client PRODUCTS around their mean instead
                prods = [agg.product_mean([l]) for l in client_loras]
                mean_prod = jax.tree.map(lambda *xs: sum(xs) / k, *prods)
                div = float(sum(
                    float(jnp.sqrt(jnp.mean(jnp.square(a - b))))
                    for a, b in zip(jax.tree.leaves(prods[0]),
                                    jax.tree.leaves(mean_prod))))
            elif self.method == "centralized":
                # single worker sees every client's stream round-robin
                lora, losses = self._client_round(rnd % k, self.params,
                                                  self.global_lora, rnd)
                self.global_lora = lora
                div = 0.0
                client_losses = [losses[-1]]
            else:
                keep_local = (self.fed_cfg.assignment == "keep_local"
                              and self.method == "fedex")
                if keep_local and not hasattr(self, "_client_lora"):
                    self._client_lora = [self.global_lora] * k
                round_losses: Dict[int, float] = {}

                def train_fn(client, start_lora, round_id, _losses=round_losses):
                    c = client.client_id
                    base = (self.client_params[c]
                            if self.client_params is not None else self.params)
                    start = self._client_lora[c] if keep_local else start_lora
                    lora_c, losses = self._client_round(c, base, start,
                                                        round_id)
                    if self.fed_cfg.dp_clip > 0:
                        from repro.core.privacy import privatize_upload
                        lora_c = privatize_upload(
                            jax.random.key(hash((self.seed, round_id, c)) % 2**31),
                            lora_c, start, clip=self.fed_cfg.dp_clip,
                            noise_multiplier=self.fed_cfg.dp_noise_multiplier)
                    _losses[c] = losses[-1]
                    return lora_c

                outcome = self.coordinator.run_round(rnd, train_fn,
                                                     self.global_lora)
                # round boundary: the PREVIOUS round's deferred divergence
                # resolves only now — after this round's uplinks have already
                # streamed into the ring — so its ring.write spans genuinely
                # overlap the in-flight close's [dispatch, resolve] window
                # (the invariant scripts/obs_report.py --check proves).
                self._resolve_divergences()
                self.outcomes.append(outcome)
                # keep adapter payloads only for the latest round — otherwise
                # history retains O(rounds · k · adapter_size) of fp32 trees
                if len(self.outcomes) > 1:
                    for d in self.outcomes[-2].delivered:
                        d.lora = None
                client_loras = [d.lora for d in outcome.delivered]
                client_losses = [round_losses[c] for c in outcome.client_ids]
                weights = outcome.weights

                if not outcome.delivered or outcome.degraded:
                    # zero deliveries, or quorum failed after quarantine
                    # (degraded): carry the previous global forward — the
                    # coordinator already evicted the round's ring set.
                    logger.warning("round=%d: %s; global carried forward",
                                   rnd, "degraded" if outcome.degraded
                                   else "no deliveries")
                    div = 0.0
                    if not client_losses:
                        client_losses = [float("nan")]
                elif self.engine is not None:
                    # fused close over the streamed stacks; it also computes
                    # the divergence metric inside the same jitted program
                    # (factored Grams — no dense deviation matrix, and no
                    # eager mean_deviation tree-walk per round)
                    with self.recorder.span("round.close", cat="trainer",
                                            round=rnd, engine=True):
                        self._close_round(rnd, outcome, client_loras, weights)
                    div = self._last_div
                else:
                    div = mean_deviation(client_loras)
                    with self.recorder.span("round.close", cat="trainer",
                                            round=rnd, engine=False):
                        self._close_round(rnd, outcome, client_loras, weights)
                if self.recorder.enabled:
                    self._reconcile_comm(rnd, outcome)

            self._global_step += self.fed_cfg.local_steps
            eval_params = (self.client_params[0] if self.client_params is not None
                           else self.params)
            eval_lora = (self._client_lora[0] if hasattr(self, "_client_lora")
                         else self.global_lora)
            with self.recorder.span("round.eval", cat="trainer", round=rnd,
                                    batches=len(self.eval_batches)):
                ev_loss, ev_acc = self._evaluate(eval_params, eval_lora, rnd)
            if self.recorder.enabled:
                self.recorder.round_set(rnd, eval_loss=round(ev_loss, 6),
                                        eval_acc=round(ev_acc, 6))
            if self.recorder.enabled and self._chaos:
                # chaos witness: the quarantine wall held — no poisoned
                # uplink leaked a non-finite value into the served adapter
                import numpy as _np
                finite = all(
                    bool(_np.isfinite(_np.asarray(x, _np.float32)).all())
                    for x in jax.tree.leaves(eval_lora))
                self.recorder.round_set(rnd, global_finite=int(finite))
            rec = RoundRecord(round=rnd, client_losses=client_losses,
                              eval_loss=ev_loss, eval_acc=ev_acc,
                              divergence_scaled=div, lr=lr_now)
            self.history.append(rec)
            deferred = (isinstance(div, DeferredDivergence)
                        and not div.resolved)
            logger.info(
                "round=%d method=%s eval_loss=%.4f eval_acc=%.4f div=%s "
                "client_loss=%.4f", rnd, self.method, ev_loss, ev_acc,
                "deferred" if deferred else f"{float(div):.3e}",
                sum(client_losses) / len(client_losses))
            if (self.fed_cfg.checkpoint_dir
                    and (rnd + 1) % self.fed_cfg.checkpoint_every == 0):
                from repro.checkpoint import round_state_path
                self.save_state(
                    round_state_path(self.fed_cfg.checkpoint_dir))
            # a completed round never re-runs: run(until=k) then run()
            # continues in-process exactly where load_state would resume
            self._start_round = rnd + 1
        # final boundary: no record leaves run() with an unresolved handle
        self._resolve_divergences()
        return self.history

    # ------------------------------------------------------------------
    # crash-safe round state (checkpoint/): a run killed between rounds
    # resumes from the last saved boundary and replays the remaining rounds
    # BITWISE against an uninterrupted run (tests/test_checkpoint_resume.py).
    def save_state(self, path: str) -> None:
        """Snapshot the full round boundary: model + adapters, coordinator
        clock, bytes ledger, loader iterator states, ring contents, and the
        async buffer (version / in-flight / snapshots). Forces the
        round-boundary host sync first — no deferred divergence handle
        survives into the file."""
        import dataclasses as _dc

        from repro.checkpoint import save_checkpoint

        self._resolve_divergences()
        tree: Dict[str, Any] = {"params": self.params,
                                "global": self.global_lora}
        if self.client_params is not None:
            tree["cparams"] = {str(i): p
                               for i, p in enumerate(self.client_params)}
        if hasattr(self, "_client_lora"):
            tree["clora"] = {str(i): l
                             for i, l in enumerate(self._client_lora)}
        meta: Dict[str, Any] = {
            "next_round": len(self.history),
            "global_step": self._global_step,
            "last_div": float(self._last_div),
            "clock": self.coordinator.clock.state_dict(),
            "ledger": self.ledger.state_dict(),
            "loaders": [ld.state_dict() for ld in self.client_loaders],
            "history": [_dc.asdict(r) for r in self.history],
        }
        if self.engine is not None:
            ring_meta, ring_arrays = self.engine.buffers.state_dict()
            meta["ring"] = ring_meta
            if ring_arrays:
                tree["ringarr"] = ring_arrays
        co = self.coordinator
        if hasattr(co, "_version"):  # FedBuff async buffered coordinator
            meta["async"] = {
                "version": co._version,
                "inflight": [[c.client_id, t, v] for t, c, v in co._inflight],
                "snapshot_versions": sorted(co._snapshots),
            }
            tree["snap"] = {str(v): co._snapshots[v] for v in co._snapshots}
        save_checkpoint(path, tree, meta)
        logger.info("round state saved: %s (next_round=%d)", path,
                    meta["next_round"])

    def load_state(self, path: str) -> None:
        """Restore a :meth:`save_state` snapshot into a freshly-constructed
        trainer (same configs). ``run()`` then continues from the saved
        boundary. RoundOutcome payloads are deliberately not checkpointed —
        ``outcomes`` restarts empty on a resumed run."""
        from repro.checkpoint import load_checkpoint
        from repro.util.tree import flatten_with_paths

        tree, meta = load_checkpoint(path)
        self.params = tree["params"]
        self.global_lora = tree["global"]
        if "cparams" in tree:
            cp = tree["cparams"]
            self.client_params = [cp[str(i)] for i in range(len(cp))]
        if "clora" in tree:
            cl = tree["clora"]
            self._client_lora = [cl[str(i)] for i in range(len(cl))]
        self._start_round = int(meta["next_round"])
        self._global_step = int(meta["global_step"])
        self._last_div = float(meta["last_div"])
        self.coordinator.clock.load_state(meta["clock"])
        self.ledger.load_state(meta["ledger"])
        for ld, st in zip(self.client_loaders, meta["loaders"]):
            ld.load_state(st)
        self.history = [RoundRecord(**r) for r in meta["history"]]
        self.outcomes = []
        if self.engine is not None and "ring" in meta:
            ring_arrays = (flatten_with_paths(tree["ringarr"])
                           if "ringarr" in tree else {})
            self.engine.buffers.load_state(meta["ring"], ring_arrays)
        if "async" in meta:
            co, st = self.coordinator, meta["async"]
            co._version = int(st["version"])
            co._inflight = [(float(t), co.registry.get(int(cid)), int(v))
                            for cid, t, v in st["inflight"]]
            snap = tree.get("snap", {})
            co._snapshots = {int(v): snap[str(v)]
                             for v in st["snapshot_versions"]}
        logger.info("round state loaded: %s (resuming at round %d)", path,
                    self._start_round)
