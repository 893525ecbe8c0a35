"""Closed-loop federation clients, run as a child process of the served
generator. Imports neither JAX nor the program: deltas come from the seed
(``numpy``), frames are built here in the wire format the server reads
(``FDX1`` · u32 header length · JSON header · raw float32 buffers), and HTTP
goes through ``http.client`` on one kept-alive connection per client.

Protocol, per client and round (the synchronous FedEx-LoRA round): POST the
round's delta, wait until the server's version passes the round (the client
whose POST closed the round knows at once; the others poll ``/v1/healthz``),
``GET /v1/adapters/latest``, and POST the next round's delta. Zero think
time. Round r sends delta set r % 2.

Input: one JSON line on stdin (url, seed, clients, shapes, examples, rounds
to warm up, seconds, rounds to trace). Output on stdout: ``ready`` once the
deltas are built, ``window`` with the window's numbers, then (if tracing)
waits for a ``go`` line on stdin, runs the traced rounds and prints
``traced``. Every line is ``<tag> <json>``.
"""

from __future__ import annotations

import http.client
import json
import struct
import sys
import threading
import time
from typing import Dict, List

import numpy as np

MAGIC = b"FDX1"
POLL_S = 0.1
WAIT_S = 300.0          # a round that does not close by then has failed


def make_delta(shapes: Dict[str, List[int]], seed: int, which: int,
               client: int) -> Dict[str, np.ndarray]:
    """Delta set ``which`` of ``client``: N(0, 0.05²) float32 factors."""
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(seed) & (2**64 - 1), 7, which, client]))
    return {p: (0.05 * rng.standard_normal(tuple(s), np.float32))
            for p, s in sorted(shapes.items())}


def frame_parts(delta: Dict[str, np.ndarray], client: int):
    """(tensor descriptors, body bytes): only the header, which carries the
    round id, is built per POST."""
    descs = [{"path": p, "dtype": "float32", "shape": list(a.shape),
              "declared": None, "scale": None, "nbytes": int(a.nbytes)}
             for p, a in delta.items()]
    body = b"".join(np.ascontiguousarray(a).tobytes() for a in delta.values())
    return descs, body


def header(descs, client: int, rnd: int) -> bytes:
    hdr = json.dumps({"round_id": rnd, "client_id": client,
                      "direction": "uplink", "codec": "none",
                      "tensors": descs}, separators=(",", ":")).encode()
    return MAGIC + struct.pack(">I", len(hdr)) + hdr


class Control:
    """The shared stop rule: after pulling round r, the first client to ask
    decides whether r was the last; every client then keeps to it, so all
    of them post the same rounds."""

    def __init__(self, first: int, seconds: float, last: int = -1):
        self.first, self.seconds, self.last = first, seconds, last
        self.t0 = None
        self._lock = threading.Lock()
        self._decided: Dict[int, bool] = {}

    def sent(self, rnd: int, t: float) -> None:
        with self._lock:
            if rnd == self.first and (self.t0 is None or t < self.t0):
                self.t0 = t

    def stop_after(self, rnd: int, t: float) -> bool:
        with self._lock:
            if rnd not in self._decided:
                if self.last >= 0:
                    stop = rnd >= self.last
                else:
                    stop = (rnd >= self.first and self.t0 is not None
                            and t - self.t0 >= self.seconds)
                self._decided[rnd] = stop
            return self._decided[rnd]


class Client(threading.Thread):
    def __init__(self, cid, host, port, parts, examples, log, start_at, ctl):
        super().__init__(daemon=True)
        self.cid, self.parts, self.examples = cid, parts, examples
        self.log, self.start_at, self.ctl = log, start_at, ctl
        self.conn = http.client.HTTPConnection(host, port, timeout=600)
        self.error = None

    def _get(self, path):
        self.conn.request("GET", path)
        resp = self.conn.getresponse()
        return resp.status, resp.read()

    def run(self):
        rnd = self.start_at
        try:
            while True:
                t_pull = self._round(rnd)
                if self.ctl.stop_after(rnd, t_pull):
                    return
                rnd += 1
        except Exception as e:  # reported by the parent as a failure
            self.error = repr(e)

    def _round(self, rnd) -> float:
        descs, body = self.parts[rnd % 2]
        head = header(descs, self.cid, rnd)
        t_send = time.time()
        self.ctl.sent(rnd, t_send)
        self.conn.putrequest("POST", f"/v1/rounds/{rnd}/deltas")
        self.conn.putheader("Content-Type", "application/octet-stream")
        self.conn.putheader("Content-Length", str(len(head) + len(body)))
        self.conn.putheader("X-Fed-Examples", str(self.examples))
        self.conn.endheaders()
        self.conn.send(head)
        self.conn.send(body)
        resp = self.conn.getresponse()
        reply = json.loads(resp.read() or b"{}")
        t_resp = time.time()
        ok = resp.status == 200
        version = int(reply.get("version", -1))
        while ok and version < rnd + 1:
            if time.time() - t_resp > WAIT_S:
                raise TimeoutError(f"round {rnd} did not close")
            time.sleep(POLL_S)
            status, data = self._get("/v1/healthz")
            version = int(json.loads(data)["version"]) if status == 200 else -1
        status, data = self._get("/v1/adapters/latest")
        t_pull = time.time()
        self.log.append((rnd, self.cid, t_send, t_resp, t_pull,
                         int(ok and status == 200 and len(data) > 0)))
        if not ok:
            raise RuntimeError(f"POST round {rnd} -> {resp.status} {reply}")
        return t_pull


def drive(cfg, parts, start_at: int, ctl: Control, log) -> List[str]:
    """Run every client from ``start_at`` until the stop rule says; the
    errors of those that failed."""
    host, port = cfg["host"], cfg["port"]
    clients = [Client(c, host, port, parts[c], cfg["examples"][c], log,
                      start_at, ctl) for c in range(cfg["clients"])]
    for c in clients:
        c.start()
    for c in clients:
        c.join()
    return [c.error for c in clients if c.error]


def summarize(log, first: int, last: int) -> Dict[str, float]:
    """Numbers of rounds [first, last]: the close wait (last uplink sent →
    first pull of the new version back) and the round rate."""
    rows = [x for x in log if first <= x[0] <= last]
    waits = []
    for rnd in range(first, last + 1):
        mine = [x for x in rows if x[0] == rnd]
        waits.append((min(x[4] for x in mine) - max(x[2] for x in mine)) * 1e3)
    t0 = min(x[2] for x in rows)
    t1 = max(x[4] for x in rows)
    return {"rounds": last - first + 1, "posts": len(rows),
            "failed": sum(1 for x in rows if not x[5]), "t0": t0, "t1": t1,
            "close_wait_ms": float(np.mean(waits)),
            "rounds_per_s": (last - first + 1) / (t1 - t0)}


def main() -> int:
    cfg = json.loads(sys.stdin.readline())
    parts = [[frame_parts(make_delta(cfg["shapes"], cfg["seed"], w, c), c)
              for w in (0, 1)] for c in range(cfg["clients"])]
    print("ready {}", flush=True)
    log: List[tuple] = []
    first = cfg["warmup_rounds"]
    ctl = Control(first, cfg["seconds"])
    errors = drive(cfg, parts, 0, ctl, log)
    last = max((x[0] for x in log), default=-1)
    out = summarize(log, first, last) if not errors else {}
    out.update(errors=errors, last_round=last)
    print("window " + json.dumps(out), flush=True)
    if cfg["trace_rounds"] and not errors:
        sys.stdin.readline()
        start = last + 1
        end = start + cfg["trace_rounds"] - 1
        errors = drive(cfg, parts, start, Control(start, 0.0, end), log)
        tr = summarize(log, start, end) if not errors else {}
        tr.update(errors=errors, last_round=end)
        print("traced " + json.dumps(tr), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
