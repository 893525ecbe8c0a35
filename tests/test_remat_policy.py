"""The train layer scan's remat policy (models/transformer.py).

Contracts under test, on a tiny dense config with GQA and q/k/v bias and
rank-r adapters on all seven projections:

* Same numbers: the loss and the adapter gradients under the named policy
  equal full remat's bit for bit (float32 on the CPU).
* No base-weight matmul is recomputed under the policy: each base-weight
  ``dot_general`` of the layer runs once forward and once transposed, as it
  would with no remat at all; full remat runs more.
* The selector keeps the named residuals only when a device limit is given
  and they fit beside the parameters and logits; otherwise (and on the CPU,
  which reports no limit) the scan is exactly ``jax.checkpoint(body)``.
  The lanes of a vmapped forward multiply the reckoning.
* Without autodiff the policy changes nothing: the eval program lowers to
  the same text with and without a limit.
* The trainer's ``step.saved_residual_bytes`` gauge: 0 on the CPU path, the
  reckoned bytes with a limit.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import FedConfig, LoRAConfig, ModelConfig, TrainConfig
from repro.core import FederatedTrainer, init_lora
from repro.core.federated import make_eval_fn
from repro.data import ClientLoader, SyntheticLM
from repro.models import build_model
from repro.models import transformer

B, S = 2, 16
CFG = ModelConfig(name="remat-tiny", family="dense", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, d_ff=96, vocab_size=80,
                  qkv_bias=True, tie_embeddings=True, dtype="float32")
LORA = LoRAConfig(rank=3, alpha=6.0, target_modules=(
    "q_proj", "k_proj", "v_proj", "o_proj", "gate_proj", "up_proj", "down_proj"))
BIG = 1 << 40


@pytest.fixture(scope="module")
def setup():
    model = build_model(CFG)
    params = model.init(jax.random.key(0))
    # non-zero b factors, so every adapter branch reaches the loss
    lora = jax.tree.map(lambda x: x + 0.02,
                        init_lora(jax.random.key(1), params, CFG, LORA))
    tok = jax.random.randint(jax.random.key(2), (B, S + 1), 0, CFG.vocab_size)
    batch = {"tokens": tok[:, :-1], "targets": tok[:, 1:],
             "loss_mask": jnp.ones((B, S), jnp.float32)}
    return model, params, lora, batch


def _limit(monkeypatch, limit):
    monkeypatch.setattr(transformer, "device_bytes_limit", lambda: limit)


def _grad_fn(model, lanes=1):
    # a fresh function per call, so no trace is shared between limits
    def loss(lora, params, batch):
        return model.loss(params, batch, lora=lora, lora_scale=LORA.scale,
                          lanes=lanes)[0]
    return jax.value_and_grad(loss)


def _loss_jaxpr(model, params, lora, batch, lanes=1):
    return jax.make_jaxpr(lambda p, l, b: model.loss(
        p, b, lora=l, lora_scale=LORA.scale, lanes=lanes))(params, lora, batch)


def _base_dots(jaxpr, kernel_shapes) -> int:
    """dot_generals with a base-weight operand, inner jaxprs counted once."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and any(
                tuple(v.aval.shape) in kernel_shapes for v in eqn.invars):
            n += 1
        n += sum(_base_dots(j, kernel_shapes)
                 for j in transformer._sub_jaxprs(eqn))
    return n


def _named_per_layer() -> int:
    """q, k, v, attention output, post-attention residual, gate, up."""
    hd = CFG.d_model // CFG.num_heads
    width = (2 * CFG.num_heads * hd + 2 * CFG.num_kv_heads * hd + CFG.d_model
             + 2 * CFG.d_ff)
    return B * S * width * jnp.dtype(CFG.dtype).itemsize


class TestSameNumbers:
    def test_loss_and_adapter_grads_bitwise_equal_full_remat(self, setup,
                                                             monkeypatch):
        model, params, lora, batch = setup
        out = {}
        for limit in (None, BIG):
            _limit(monkeypatch, limit)
            out[limit] = jax.jit(_grad_fn(model))(lora, params, batch)
        (l_full, g_full), (l_kept, g_kept) = out[None], out[BIG]
        assert float(l_full) == float(l_kept)
        for a, b in zip(jax.tree.leaves(g_full), jax.tree.leaves(g_kept)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(g_kept))


class TestNoBaseRecompute:
    def test_each_base_dot_runs_once_forward_once_transposed(self, setup,
                                                             monkeypatch):
        model, params, lora, batch = setup
        kernels = {tuple(x.shape[1:]) for x in jax.tree.leaves(params["layers"])
                   if x.ndim == 3}
        forward = _base_dots(_loss_jaxpr(model, params, lora, batch).jaxpr,
                             kernels)
        counts = {}
        for limit in (None, BIG):
            _limit(monkeypatch, limit)
            counts[limit] = _base_dots(
                jax.make_jaxpr(_grad_fn(model))(lora, params, batch).jaxpr,
                kernels)
        assert forward == 7  # q, k, v, o, gate, up, down in the scan body
        assert counts[BIG] == 2 * forward
        # full remat recomputes every projection but down_proj
        assert counts[None] == 2 * forward + 6


class TestSelector:
    @pytest.mark.parametrize("saved,fixed,limit,kept", [
        (100, 0, None, 0),        # no device limit: full remat
        (100, 0, 0, 0),
        (0, 0, 1000, 0),          # nothing named
        (100, 0, 1000, 100),
        (75, 900, 1000, 75),      # exactly the headroom share
        (76, 900, 1000, 0),       # one byte over it
        (100, 1000, 1000, 0),     # no headroom
    ])
    def test_residual_bytes_kept(self, saved, fixed, limit, kept):
        assert transformer.residual_bytes_kept(saved, fixed, limit) == kept

    @pytest.mark.parametrize("case", ["no_limit", "too_small", "fits"])
    def test_forward_keeps_the_reckoned_bytes_or_nothing(self, setup,
                                                         monkeypatch, case):
        model, params, lora, batch = setup
        need = _named_per_layer() * CFG.num_layers
        fixed = (transformer._tree_bytes(params) + transformer._tree_bytes(lora)
                 + 2 * B * S * CFG.vocab_size * 4)
        fits = fixed + int(need / transformer.HEADROOM_SHARE) + 1
        limit = {"no_limit": None, "too_small": fits - 2, "fits": fits}[case]
        _limit(monkeypatch, limit)
        got = transformer.saved_residual_bytes(
            _loss_jaxpr(model, params, lora, batch).jaxpr)
        assert got == (need if case == "fits" else 0)

    @pytest.mark.parametrize("lanes,kept", [(1, True), (4, False)])
    def test_lanes_multiply_the_reckoning(self, setup, monkeypatch, lanes,
                                          kept):
        model, params, lora, batch = setup
        need = _named_per_layer() * CFG.num_layers
        fixed = (transformer._tree_bytes(params) + transformer._tree_bytes(lora)
                 + 2 * B * S * CFG.vocab_size * 4)
        _limit(monkeypatch, fixed + int(need / transformer.HEADROOM_SHARE) + 1)
        got = transformer.saved_residual_bytes(
            _loss_jaxpr(model, params, lora, batch, lanes=lanes).jaxpr)
        assert got == (need * lanes if kept else 0)


def test_eval_program_is_the_same_with_and_without_a_limit(setup,
                                                           monkeypatch):
    model, params, lora, batch = setup
    texts = []
    for limit in (None, BIG):
        _limit(monkeypatch, limit)
        texts.append(make_eval_fn(model, LORA.scale).lower(
            params, lora, batch).as_text())
    assert texts[0] == texts[1]


@pytest.mark.parametrize("limit", [None, BIG])
def test_trainer_records_the_saved_residual_gauge(monkeypatch, limit):
    _limit(monkeypatch, limit)
    cfg = dataclasses.replace(CFG, vocab_size=16)
    ds = SyntheticLM(vocab=16, num_tasks=2, seed=0)
    loaders = [ClientLoader(ds.sample(task=t, num_sequences=4, seq_len=S,
                                      seed=t), batch_size=B, seed=t)
               for t in range(2)]
    tr = FederatedTrainer(
        model=build_model(cfg), lora_cfg=LORA,
        fed_cfg=FedConfig(num_clients=2, rounds=1, local_steps=1,
                          method="fedex", obs="basic"),
        train_cfg=TrainConfig(learning_rate=1e-2, schedule="constant"),
        client_loaders=loaders, seed=0)
    tr.run()
    gauge = tr.recorder.metrics.snapshot()["gauges"]["step.saved_residual_bytes"]
    want = 0 if limit is None else _named_per_layer() * CFG.num_layers
    assert gauge == want
    assert tr.recorder.round_records()[0]["saved_residual_bytes"] == want
