"""The trace reduction (``harness/xtrace.py``): busy and idle time over the
marked window, device time per jitted program, idle gaps named by the host
span open, on a hand-made trace and on one recorded on a TPU v5e."""

import os

import pytest

from harness import xtrace

HAND = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 3000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_step(7)" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.1" } }
  event_metadata { key: 3 value { id: 3 name: "convolution.2" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 2500000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 5500000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 500000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "codec.decode" } }
  event_metadata { key: 3 value { id: 3 name: "bench.pull" } }
}
'''


def _hand(tmp_path):
    from jax.profiler import ProfileData
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(HAND))
    return xtrace.reduce(str(path), ["codec.decode", "bench.pull"])


def test_busy_and_window(tmp_path):
    red = _hand(tmp_path)
    assert red.window_s == pytest.approx(10e-6)
    # the program runs [1,6) us; its ops cover only [1,3) and [4,6), and
    # the third op starts at 11 us, past the window
    assert red.busy_s == pytest.approx(5e-6)
    assert red.devices == 1


def test_program_time(tmp_path):
    red = _hand(tmp_path)
    assert red.program_seconds(["step"]) == (pytest.approx(5e-6), 1)
    assert red.program_seconds(["close"]) == (0.0, 0)


def test_gaps_named_by_host_span(tmp_path):
    red = _hand(tmp_path)
    # [0,1) nothing open; [6,10) is split at the span edges: codec.decode
    # (listed first) [6,6.5), bench.pull [6.5,8.5), nothing [8.5,10)
    assert red.gaps == {"none": pytest.approx(2.5e-6),
                        "codec.decode": pytest.approx(0.5e-6),
                        "bench.pull": pytest.approx(2e-6)}
    assert red.spans == {"codec.decode": [pytest.approx(1e-6),
                                          pytest.approx(0.5e-6)],
                         "bench.pull": [pytest.approx(3e-6)]}
    bd = red.breakdown()
    assert bd["idle_gaps"][0][0] == "none"
    assert [k for k, _ in bd["device_ops"]] == ["fusion.1", "convolution.2"]


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "qwen2.5-3b.train.silo3.trimmed.xplane.pb")


def test_recorded_trainer_round():
    """One traced round of ``qwen2.5-3b.train.silo3`` on a TPU v5e (seed
    1001), trimmed to the program runs, the close's ops and the benchmark's
    host spans. Expected values were read from the untrimmed trace."""
    from harness import spec
    drv = spec.load_module(os.path.join(spec.BENCH, "traffic", "trainer.py"),
                           "trainer_for_trace_test")
    red = xtrace.reduce(RECORDED, drv.SPANS)
    assert red.devices == 1
    assert red.window_s == pytest.approx(4.083527439)
    assert red.busy_s == pytest.approx(3.58933617)
    assert red.program_seconds(["step"]) == (pytest.approx(2.975229425), 12)
    assert red.program_seconds(["_close"]) == (pytest.approx(0.101805502), 1)
    assert red.program_seconds(["ev"]) == (pytest.approx(0.510641811), 3)
    gaps = red.breakdown()["idle_gaps"]
    assert gaps[0] == ["codec.decode", pytest.approx(0.215051742)]
    assert gaps[1][0] == "client.train"
    top = red.breakdown()["device_ops"][0][0]
    assert top.startswith("%vmap_jit_fedex_residual_apply")
