"""Routed-expert spans and counters in the host trainer.

* Gauges, once at the first step's shapes: ``moe.experts_held``,
  ``moe.router_width`` and ``moe.sorted_rows`` (tokens × k, the rows each
  grouped GEMM is given).
* Per step, read with the loss in the one ``step.sync`` fetch:
  ``moe.held_rows`` (counter) and ``moe.max_expert_rows`` (histogram); the
  host-sync count is what a dense model's is.
* The step's op metadata names ``moe.route``, ``moe.experts`` and
  ``mla.attn``; every model's step returns four outputs, the fourth a
  ``stats`` dict (``grad_norm``, and the routing counts where there are
  routed experts).
"""

import dataclasses

import jax
import pytest

from repro.configs import FedConfig, LoRAConfig, TrainConfig, get_config
from repro.core import FederatedTrainer
from repro.core.federated import make_local_step
from repro.data import ClientLoader, SyntheticLM
from repro.models import build_model
from repro.optim import init_adamw

CLIENTS, ROUNDS, STEPS, BATCH, SEQ = 2, 2, 2, 2, 16


def _cfg():
    # 8 routed experts in 4 groups, the layer holding group 1's two
    return dataclasses.replace(
        get_config("deepseek-v2-236b").reduced(), dtype="float32",
        vocab_size=32, num_experts=8, num_experts_per_tok=3, n_group=4,
        topk_group=2, local_experts=2, expert_offset=2)


def _trainer(cfg, obs):
    ds = SyntheticLM(vocab=cfg.vocab_size, num_tasks=CLIENTS, seed=0)
    loaders = [ClientLoader(ds.sample(task=t, num_sequences=8, seq_len=SEQ,
                                      seed=t), batch_size=BATCH, seed=t)
               for t in range(CLIENTS)]
    return FederatedTrainer(
        model=build_model(cfg),
        lora_cfg=LoRAConfig(rank=2, alpha=4, lora_experts=True,
                            target_modules=("q_up", "o_proj")),
        fed_cfg=FedConfig(num_clients=CLIENTS, rounds=ROUNDS,
                          local_steps=STEPS, method="fedex", obs=obs),
        train_cfg=TrainConfig(learning_rate=1e-2, schedule="constant"),
        client_loaders=loaders, seed=0)


@pytest.fixture(scope="module")
def traced():
    tr = _trainer(_cfg(), "trace")
    tr.run()
    return tr


def test_gauges_at_the_first_steps_shapes(traced):
    gauges = traced.recorder.metrics.snapshot()["gauges"]
    assert gauges["moe.experts_held"] == 2
    assert gauges["moe.router_width"] == 8
    assert gauges["moe.sorted_rows"] == BATCH * SEQ * 3


def test_per_step_counts_without_a_new_sync(traced):
    snap = traced.recorder.metrics.snapshot()
    steps = CLIENTS * ROUNDS * STEPS
    # one expert layer (the reduced config: 1 dense + 1 MoE)
    held = snap["counters"]["moe.held_rows"]
    assert 0 < held <= steps * BATCH * SEQ * 3
    hist = snap["histograms"]["moe.max_expert_rows"]
    assert hist["count"] == steps
    assert 0 < hist["max"] <= BATCH * SEQ
    assert snap["counters"]["host.syncs[step]"] == steps


def test_named_scopes_in_the_step():
    cfg = _cfg()
    model = build_model(cfg)
    tr = _trainer(cfg, "off")
    step = make_local_step(model, 2.0, TrainConfig())
    batch = tr.client_loaders[0].next_batch()
    text = step.lower(tr.params, tr.global_lora, init_adamw(tr.global_lora),
                      batch, 1e-3).as_text(debug_info=True)
    for scope in ("moe.route", "moe.experts", "mla.attn"):
        assert scope in text, scope
    out = step(tr.params, tr.global_lora, init_adamw(tr.global_lora), batch,
               1e-3)
    assert len(out) == 4
    assert set(out[3]) == {"held_rows", "max_expert_rows", "grad_norm"}


def test_dense_step_keeps_four_outputs():
    cfg = dataclasses.replace(get_config("paper-tiny"), dtype="float32",
                              vocab_size=16)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    from repro.core import init_lora
    lora = init_lora(jax.random.key(1), params, cfg, LoRAConfig(rank=2))
    batch = {"tokens": jax.numpy.zeros((2, 8), jax.numpy.int32),
             "targets": jax.numpy.zeros((2, 8), jax.numpy.int32)}
    out = make_local_step(model, 1.0, TrainConfig())(
        params, lora, init_adamw(lora), batch, 1e-3)
    assert len(out) == 4 and set(out[3]) == {"grad_norm"}
