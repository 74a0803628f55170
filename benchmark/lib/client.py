"""The read client: a child process that speaks only HTTP.

    python benchmark/lib/client.py --port P --per-s 20 --kinds status,block --seed S

It imports no JAX and nothing of the program, so the reads and the clock
that judges the node do not share its interpreter. Protocol, over its
own stdin/stdout, one line each way:

    parent -> "go <seconds>"     the window opens now
    client -> {"reads": [...]}   when the window has closed and every
                                 read issued in it has answered or failed

The loop is open: read i is due at i / per_s seconds after "go", whatever
became of read i - 1, and its latency runs from when it was due. Kinds
alternate in the order given. A `block` read asks for a height drawn from
the seed among those the last `status` answer said were applied.
"""

from __future__ import annotations

import argparse
import http.client
import json
import random
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

READ_TIMEOUT_S = 10.0  # a read fails when it errors or outlasts this; a slow answer is a latency
WORKERS = 32  # enough that a stalled node never makes the generator late


class Reader:
    def __init__(self, port: int) -> None:
        self._port = port
        self._local = threading.local()

    def get(self, path: str) -> dict:
        conn = getattr(self._local, "conn", None)
        for attempt in (0, 1):
            if conn is None:
                conn = http.client.HTTPConnection("127.0.0.1", self._port, timeout=READ_TIMEOUT_S)
                self._local.conn = conn
            try:
                conn.request("GET", path)
                resp = conn.getresponse()
                body = resp.read()
                return json.loads(body)
            except (http.client.HTTPException, OSError):
                conn.close()
                conn = self._local.conn = None
                if attempt:
                    raise
        raise AssertionError("unreachable")


def run_window(port: int, seconds: float, per_s: float, kinds: list[str], seed: int) -> dict:
    reader = Reader(port)
    rng = random.Random(seed)
    applied = [1]
    lock = threading.Lock()
    reads: list[dict] = []
    t0 = time.monotonic()
    wall0 = time.time()

    def one(i: int, kind: str, due: float, height: int) -> None:
        start = time.monotonic() - t0
        rec = {"i": i, "kind": kind, "due": due, "start": start, "ok": False}
        try:
            if kind == "status":
                doc = reader.get("/status")
                h = int(doc["result"]["sync_info"]["latest_block_height"])
                rec["height"] = h
                with lock:
                    applied[0] = max(applied[0], h)
            elif kind == "block":
                doc = reader.get(f"/block?height={height}")
                rec["asked"] = height
                rec["height"] = int(doc["result"]["block"]["header"]["height"])
                if rec["height"] != height:
                    raise ValueError("another block was served")
            else:
                raise ValueError(f"unknown read kind {kind!r}")
            rec["ok"] = True
        except Exception as e:  # noqa: BLE001 - a failed read is a datum, not a crash
            rec["error"] = f"{type(e).__name__}: {e}"[:200]
        rec["end"] = time.monotonic() - t0
        with lock:
            reads.append(rec)

    n = int(seconds * per_s)
    with ThreadPoolExecutor(max_workers=WORKERS) as pool:
        for i in range(n):
            due = i / per_s
            wait = due - (time.monotonic() - t0)
            if wait > 0:
                time.sleep(wait)
            with lock:
                top = applied[0]
            pool.submit(one, i, kinds[i % len(kinds)], due, rng.randint(1, max(1, top)))
    reads.sort(key=lambda r: r["i"])
    return {"wall0": wall0, "seconds": seconds, "per_s": per_s, "reads": reads}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--per-s", type=float, required=True)
    ap.add_argument("--kinds", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    print("client ready", flush=True)
    for line in sys.stdin:
        words = line.split()
        if not words:
            continue
        if words[0] == "go":
            out = run_window(
                args.port, float(words[1]), args.per_s, args.kinds.split(","), args.seed
            )
            print(json.dumps(out), flush=True)
        elif words[0] == "quit":
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
