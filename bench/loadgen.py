#!/usr/bin/env python3
"""The benchmark's load generator: a process of its own that never imports JAX.

    python bench/loadgen.py '<job json>'

It draws the run's record stream from the seed (:mod:`stream`) and sends it
as ``D4MF`` insert frames over TCP.  The harness drives it over stdin and
stdout, one JSON object per line:

* stdin ``{"op": "warmup", "port": P, "records": N}``: send the first ``N``
  records on one connection, close it, answer ``{"op": "warmed", ...}``;
* stdin ``{"op": "window", "port": P, "seconds": S}``: send the stream on
  from where the warm-up stopped for ``S`` seconds on one connection, then
  close it and answer ``{"op": "done", ...}`` with the count sent, how far
  behind its schedule a paced send went, and every chunk's send stamp.

The job holds the traffic file (``bench/traffic/<name>.json``), the chunk
size and the seed.  The traffic's ``rate`` (records/s) paces the window open
loop, chunk ``i`` falling due ``i * chunk / rate`` seconds after the window
starts; a ``rate`` of ``null`` sends as fast as the server reads, so the
server's own backpressure (a bounded routed-batch queue under ``block``, then
the TCP window) bounds the backlog.
"""
from __future__ import annotations

import json
import os
import socket
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import stream  # noqa: E402  (the benchmark's own module, beside this file)


def reply(obj) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


class Generator:
    def __init__(self, job):
        traffic = job["traffic"]
        self.stream = stream.Stream(job["seed"], traffic["rmat"], job["chunk_records"])
        rate = traffic.get("rate")
        self.period = None if rate is None else job["chunk_records"] / float(rate)
        self.next_chunk = 0
        self._ahead = None  # the next chunk's frame, drawn before it is due

    def _frame(self) -> bytes:
        if self._ahead is None:
            self._ahead = stream.encode_insert(*self.stream.chunk(self.next_chunk))
        frame, self._ahead = self._ahead, None
        self.next_chunk += 1
        return frame

    def warmup(self, port: int, records: int) -> dict:
        n = self.stream.chunk_records
        if records % n:
            raise ValueError(f"warm-up of {records} records is not whole chunks of {n}")
        with socket.create_connection(("127.0.0.1", port), timeout=600) as sock:
            for _ in range(records // n):
                sock.sendall(self._frame())
        self._ahead = stream.encode_insert(*self.stream.chunk(self.next_chunk))
        return {"op": "warmed", "records": records}

    def window(self, port: int, seconds: float) -> dict:
        """Send on from where the warm-up stopped until ``seconds`` pass."""
        n = self.stream.chunk_records
        stamps = []
        late = 0.0  # how far behind its schedule an open-loop send went
        with socket.create_connection(("127.0.0.1", port), timeout=600) as sock:
            t0 = time.monotonic()
            end = t0 + seconds
            i = 0
            while True:
                now = time.monotonic()
                if self.period is not None:
                    due = t0 + i * self.period
                    if due >= end:
                        break
                    if due > now:
                        time.sleep(due - now)
                    else:
                        late = max(late, now - due)
                elif now >= end:
                    break
                frame = self._frame()
                stamps.append(time.monotonic() - t0)
                sock.sendall(frame)
                i += 1
                if self._ahead is None and time.monotonic() < end:
                    self._ahead = stream.encode_insert(*self.stream.chunk(self.next_chunk))
        return {
            "op": "done",
            "records": i * n,
            "chunks": i,
            "seconds": time.monotonic() - t0,
            "late_s": late,
            "stamps_s": stamps,
        }


def main(argv) -> int:
    gen = Generator(json.loads(argv[1]))
    reply({"op": "ready"})
    for line in sys.stdin:
        cmd = json.loads(line)
        op = cmd["op"]
        try:
            if op == "warmup":
                reply(gen.warmup(int(cmd["port"]), int(cmd["records"])))
            elif op == "window":
                reply(gen.window(int(cmd["port"]), float(cmd["seconds"])))
            elif op == "exit":
                break
            else:
                raise ValueError(f"unknown command {op!r}")
        except Exception as e:  # reported to the harness, which fails the run
            reply({"op": "error", "error": f"{type(e).__name__}: {e}"})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
