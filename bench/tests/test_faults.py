"""A run with the timed path broken underneath comes out not correct.

Each test drives a whole tiny run (set-up, window, reference comparison)
with one fault planted in the program through the generators' hooks, and
checks the cell's limits (``bench/limits/<cell>.json``) refuse it; the
sound run passes them.
"""

import jax
import jax.numpy as jnp
import pytest

from conftest import tiny_run

TRAIN = "qwen2.5-3b.train.silo3"
SERVED = ["granite-8b.l8.serve.c16", "granite-8b.l8.serve.c4"]


def _correct(out):
    return out.failed == 0 and all(c.ok for c in out.checks)


@pytest.mark.parametrize("name", [TRAIN] + SERVED)
def test_sound_run_is_correct(name):
    _, out = tiny_run(name)
    assert _correct(out), [(c.name, c.value, c.limit) for c in out.checks]


def _step_fault(kind):
    def plant(name, obj):
        if name != "local_step":
            return obj

        def step(params, lora, opt_state, batch, lr):
            new, opt, loss, gnorm = obj(params, lora, opt_state, batch, lr)
            if kind == "unchanged":       # the step returns its state as it was
                return lora, opt_state, loss, gnorm
            # an answer altered where it is produced: one adapter value
            leaf = sorted(new["layers"]["attn"])[0]
            a = new["layers"]["attn"][leaf]["a"]
            new = jax.tree.map(lambda x: x, new)
            new["layers"]["attn"][leaf] = dict(
                new["layers"]["attn"][leaf], a=a.at[(0,) * a.ndim].add(1.0))
            return new, opt, loss, gnorm
        return step
    return plant


def _half_batch(name, obj):
    """The loss is the mean over the first half of the batch only."""
    if name != "model":
        return obj
    import dataclasses
    loss = obj.loss

    def half(params, batch, **kw):
        n = batch["tokens"].shape[0] // 2
        return loss(params, {k: v[:n] for k, v in batch.items()}, **kw)
    return dataclasses.replace(obj, loss=half)


def _close_fault(kind):
    """The trainer's round close broken underneath: it leaves W0 as it
    was, or folds the first half of the delivered clients only."""
    def plant(name, trainer):
        if name != "trainer":
            return trainer
        eng = trainer.engine
        close = eng.close

        def faulty(params, client_ids, weights=None, **kw):
            if kind == "unchanged":
                keep = jax.tree.map(jnp.copy, params)
                glob, _, div = close(params, client_ids, weights, **kw)
                return glob, keep, div
            half = max(1, len(client_ids) // 2)
            return close(params, client_ids[:half],
                         None if weights is None else weights[:half], **kw)
        eng.close = faulty
        return trainer
    return plant


@pytest.mark.parametrize("plant", [_step_fault("unchanged"),
                                   _step_fault("altered"), _half_batch,
                                   _close_fault("unchanged"),
                                   _close_fault("half_batch")],
                         ids=["unchanged", "altered", "half_batch",
                              "close_unchanged", "close_half_batch"])
def test_trainer_fault_is_refused(plant):
    _, out = tiny_run(TRAIN, plant=plant)
    assert not _correct(out)


def _server_fault(kind):
    def plant(name, fed):
        if name != "server":
            return fed
        eng = fed.engine
        close = eng.close
        if kind == "half_batch":
            submit = fed.handle_submit
            half = fed.fed_cfg.num_clients // 2

            def drop_half(path_round, body, header_len, token, examples):
                from repro.fedsrv.wire import payload_from_wire
                cid = payload_from_wire(body).client_id
                return submit(path_round, body, header_len, token,
                              examples if cid < half else 0.0)
            fed.handle_submit = drop_half
            return fed

        def faulty(params, client_ids, weights=None, **kw):
            if kind == "unchanged":    # the close returns W0 as it was
                keep = jax.tree.map(jnp.copy, params)
                glob, _, div = close(params, client_ids, weights, **kw)
                return glob, keep, div
            from harness.weights import flatten, unflatten
            glob, new, div = close(params, client_ids, weights, **kw)
            flat = flatten(glob)       # an answer altered where produced
            key = sorted(flat)[0]
            flat[key] = flat[key].at[(0,) * flat[key].ndim].add(1.0)
            return unflatten(flat), new, div
        eng.close = faulty
        return fed
    return plant


@pytest.mark.parametrize("name", SERVED)
@pytest.mark.parametrize("kind", ["unchanged", "half_batch", "altered"])
def test_served_fault_is_refused(name, kind):
    _, out = tiny_run(name, plant=_server_fault(kind))
    assert not _correct(out), [(c.name, c.value) for c in out.checks]
