"""Traffic generator: the HTTP federation server (``repro.fedsrv.server``) in this
process, under closed-loop clients in a child process.

Traffic parameters (``bench/traffic/<mix>.json``): clients per round,
``close_chunk``, the log-normal spread of the clients' example counts,
warm-up rounds and traced rounds.

Set-up: the server gets the benchmark's base weights (drawn from the seed on
the device, in the configuration's dtype) behind ``start_http_server``; the
child (``served_clients.py``, no JAX) builds the seeded deltas, runs the
warm-up rounds, then whole rounds until ``--seconds`` have passed. Every
round closes through the server's own handler path: decode, validation, ring
scatter, partial folds or the stacked fold, finalize, and the encode of each
pull.

After the window: W0 as the server holds it and the adapter it serves are
compared with the plain reference replaying every round from the seed.
"""

from __future__ import annotations

import gc
import http.client
import json
import os
import struct
import subprocess
import sys
from typing import Any, Dict, List

import numpy as np

from harness import flops, weights
from harness.weights import jnp_dtype
from harness.device import device_stamp, log
from harness.runner import Check, Outcome, Run, reduce_trace
from program import seeded_state, w0_keys, w0_leaf, w0_path

HERE = os.path.dirname(os.path.abspath(__file__))
# host spans that can name an idle gap, most specific first
SPANS = ("bench.digest", "codec.encode", "codec.decode", "ring.write",
         "close.partial_fold", "close.chunk_flush", "close.dispatch",
         "divergence.resolve", "bench.submit", "bench.pull", "http.request")


def _load_clients():
    from harness.spec import load_module
    return load_module(os.path.join(HERE, "served_clients.py"),
                       "bench_served_clients")


def examples(tr: Dict[str, Any], seed: int) -> List[int]:
    rng = np.random.default_rng(weights.seed_words(seed, 4))
    n = np.exp(rng.normal(np.log(tr["examples_median"]), tr["examples_sigma"],
                          tr["clients"]))
    return [int(max(1, round(x))) for x in n]


def build_server(r: Run, wshape, lshape):
    from repro.configs import FedConfig
    from repro.configs.base import ServeConfig
    from repro.fedsrv.server import FederationServer, start_http_server

    cfg, tr = r.cell.config, r.cell.traffic
    params = weights.unflatten(weights.make_tree(wshape, r.seed, 1))
    lora = weights.unflatten(weights.make_tree(lshape, r.seed, 2))
    fed_cfg = FedConfig(num_clients=tr["clients"], rounds=10**6,
                        method="fedex", weighting="examples", engine="auto",
                        close_chunk=tr["close_chunk"],
                        seed=weights.program_seed(r.seed),
                        obs="trace" if r.trace else "off")
    serve = ServeConfig(host="127.0.0.1", port=0,
                        max_concurrent=tr["clients"], quota_per_round=4)
    fed = FederationServer(params, lora, scale=cfg["lora_alpha"] /
                           cfg["lora_rank"], fed_cfg=fed_cfg, serve_cfg=serve)
    del params
    fed = r.hook("server", fed)
    httpd = start_http_server(fed, host="127.0.0.1", port=0)
    return fed, httpd


def _read(proc, tag: str) -> Dict[str, Any]:
    while True:
        line = proc.stdout.readline()
        if not line:
            raise RuntimeError(f"client process ended before {tag!r} "
                               f"(exit {proc.wait()})")
        head, _, body = line.decode().partition(" ")
        if head == tag:
            return json.loads(body)


def pull(port: int) -> Dict[str, np.ndarray]:
    """GET the served adapter and parse the frame (FDX1, u32 length, JSON
    header, raw buffers)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    conn.request("GET", "/v1/adapters/latest")
    resp = conn.getresponse()
    data = resp.read()
    version = int(resp.getheader("X-Fed-Version", "-1"))
    conn.close()
    (hlen,) = struct.unpack_from(">I", data, 4)
    hdr = json.loads(data[8:8 + hlen])
    out, off = {}, 8 + hlen
    for d in hdr["tensors"]:
        n = int(np.prod(d["shape"]))
        out[d["path"]] = np.frombuffer(data, np.dtype(d["dtype"]), n,
                                       off).reshape(d["shape"])
        off += d["nbytes"]
    return {"version": version, "lora": out}


def _annotate(fed):
    """Benchmark host spans around the server's handlers (traced runs)."""
    import jax

    def wrap(fn, name):
        def wrapped(*a, **kw):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **kw)
        return wrapped

    fed.handle_submit = wrap(fed.handle_submit, "bench.submit")
    fed.handle_latest = wrap(fed.handle_latest, "bench.pull")
    fed._current_digest = wrap(fed._current_digest, "bench.digest")


def run(r: Run) -> Outcome:
    import jax
    from harness import xtrace
    cfg, tr = r.cell.config, r.cell.traffic
    _, wshape, lshape, _ = seeded_state(cfg)
    n_ex = examples(tr, r.seed)
    child = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "served_clients.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    try:
        fed, httpd = build_server(r, wshape, lshape)
        if r.trace:
            _annotate(fed)
        spec = {"host": "127.0.0.1", "port": httpd.server_address[1],
                "seed": r.seed, "clients": tr["clients"], "examples": n_ex,
                "shapes": {p: list(s.shape) for p, s in lshape.items()},
                "warmup_rounds": tr["warmup_rounds"], "seconds": r.seconds,
                "trace_rounds": tr["trace_rounds"] if r.trace else 0}
        child.stdin.write((json.dumps(spec) + "\n").encode())
        child.stdin.flush()
        _read(child, "ready")
        win = _read(child, "window")
        if win["errors"]:
            raise RuntimeError(f"clients failed: {win['errors'][:3]}")
        log(f"window: {win['rounds']} rounds, {win['posts']} posts in "
            f"{win['t1'] - win['t0']:.3f}s")
        reduced, traced = None, None
        if r.trace:
            d = r.trace_dir()
            jax.profiler.start_trace(d)
            try:
                with jax.profiler.TraceAnnotation(xtrace.WINDOW):
                    child.stdin.write(b"go\n")
                    child.stdin.flush()
                    traced = _read(child, "traced")
            finally:
                jax.profiler.stop_trace()
            reduced = reduce_trace(r, d, SPANS)
        child.wait(timeout=60)
        last = (traced or win)["last_round"]
        got = pull(httpd.server_address[1])
        httpd.shutdown()
        httpd.server_close()
        fed.finalize()
        dev = device_stamp(r.devices)
        # W0 as the server holds it, to the host; then the server's device
        # state (base, ring, accumulators) is freed for the reference
        w0 = {key: np.asarray(w0_leaf(fed.params, key))
              for key in w0_keys(lshape)}
        httpd.fed = None
        del fed, httpd
        gc.collect()
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    failed = win["failed"] + (traced or {}).get("failed", 0)
    if got["version"] != last + 1:
        failed += 1
        log(f"served version {got['version']}, expected {last + 1}")
    checks = compare(r, w0, got["lora"], n_ex, last + 1, lshape)
    e2e = {"setup_s": win["t0"] - r.t_start,
           "rounds_per_s": win["rounds_per_s"],
           "close_wait_ms": win["close_wait_ms"]}
    w0_bytes = int(jnp_dtype(cfg["base_dtype"]).itemsize)
    fl, nb = flops.close_work(cfg, tr["clients"], w0_bytes)
    ctx = {"peaks": r.peaks(), "close_flops": fl, "close_bytes": nb,
           "close_programs": ("_close", "_fold", "_fin"),
           "traced_rounds": (traced or {}).get("rounds", 0),
           "replay": {"n_ex": n_ex, "rounds": last + 1, "lshape": lshape}}
    return Outcome(metrics=e2e, attempted=win["posts"], failed=failed,
                   checks=checks, device=dev, window=(win["t0"], win["t1"]),
                   context=ctx, reduced=reduced)


def compare(r: Run, w0_prog: Dict[str, Any], lora_prog: Dict[str, Any],
            n_ex: List[int], rounds: int, lshape) -> List[Check]:
    want = reference_outputs(r.cell.config, r.cell.traffic, r.seed, n_ex,
                             rounds, lshape)
    got = gaps({"w0": w0_prog, "lora": lora_prog}, want)
    lim = r.limits()
    log(f"readings: {got}")
    return [Check("round_gap", got["round_gap"], lim["round_gap"])]


def reference_outputs(cfg, tr, seed, n_ex, rounds, lshape, mode="f32",
                      fault: str = "") -> Dict[str, Dict[str, Any]]:
    """W0 after ``rounds`` closes and the adapter served after the last,
    replayed from the seed by the plain reference. ``mode="3pass"`` gives
    the control; ``fault`` plants one in the reference put in the program's
    place: ``unchanged`` (closes leave W0 as it was), ``half_batch`` (the
    first half of the clients only, weights renormalized), ``altered`` (one
    served value changed)."""
    import jax.numpy as jnp
    from harness import spec

    ref = spec.reference("fold")
    clients = _load_clients()
    shapes = {p: list(s.shape) for p, s in lshape.items()}
    deltas = [[clients.make_delta(shapes, seed, which, c)
               for c in range(tr["clients"])] for which in (0, 1)]
    n = np.asarray(n_ex, np.float64)
    if fault == "half_batch":
        n[len(n) // 2:] = 0.0
    w = jnp.asarray((n / n.sum()).astype(np.float32))
    scale = jnp.float32(cfg["lora_alpha"] / cfg["lora_rank"])
    _, wshape, _, _ = seeded_state(cfg)
    out: Dict[str, Dict[str, Any]] = {"w0": {}, "lora": {}}
    last = (rounds - 1) % 2
    for key in w0_keys(lshape):
        res = []
        for which in (0, 1):
            a = jnp.asarray(np.stack([d[key + "/a"] for d in deltas[which]]))
            b = jnp.asarray(np.stack([d[key + "/b"] for d in deltas[which]]))
            res.append(ref.residual(a, b, w, mode))
            if which == last:
                abar, bbar = ref.means(a, b, w, mode)
                out["lora"][key + "/a"] = np.asarray(abar)
                out["lora"][key + "/b"] = np.asarray(bbar)
            del a, b
        w0 = weights.make_leaf(wshape, seed, 1, w0_path(wshape, key))
        done = 0 if fault == "unchanged" else rounds
        out["w0"][key] = np.asarray(ref.replay(w0, res[0], res[1], scale,
                                               done))
        del res, w0
    if fault == "altered":
        k = sorted(out["lora"])[0]
        out["lora"][k] = out["lora"][k].copy()
        out["lora"][k].flat[0] += 1.0
    return out


def gaps(prog: Dict[str, Dict[str, Any]], ref: Dict[str, Dict[str, Any]]
         ) -> Dict[str, float]:
    """The compared number ``round_gap``: the worse of the share of
    adapted W0 elements whose bf16 value differs from the reference's
    (``w0_mismatch_share``) and the served adapter's worst leaf of
    max|prog − ref| / max|ref| (``adapter_gap``), both reported beside it.

    One number for both, because each alone misses what the other sees:
    the control (three bfloat16 passes) moves the adapter's weighted means
    by no more than float32 summation order does (2e-7 either way), so
    only the share separates it, while one altered value in the served
    adapter moves the share by nothing."""
    differ, total = 0, 0
    for key, want in ref["w0"].items():
        differ += int(np.count_nonzero(prog["w0"][key] != want))
        total += int(want.size)
    gap = 0.0
    for key, want in ref["lora"].items():
        have = np.asarray(prog["lora"][key], np.float32)
        gap = max(gap, float(np.max(np.abs(have - want))
                             / np.max(np.abs(want))))
    share = differ / total
    return {"round_gap": max(share, gap), "w0_mismatch_share": share,
            "adapter_gap": gap}
