#!/usr/bin/env python3
"""The load generator: a process of its own that never touches the chip.

Started by the serving drivers with ``JAX_PLATFORMS=cpu`` in its environment,
it talks to the broker over its socket through ``GenerationClient``, as a
user's clients do, so that generator and scheduler share no interpreter lock.
Its first input line is one JSON object (the job); it answers ``READY``, waits
for ``GO <monotonic zero>``, runs, and prints one JSON record per request and
then ``DONE``. A record holds the arrival instant and size of every frame and
the tokens served, which the correctness check reads once the window has
closed. ``time.monotonic()`` is one clock for every process of a Linux
machine, so due instants and arrival times are comparable with the parent's.

job: ``port``, ``mix`` (the traffic file), ``vocab``, ``timeout_s``, and
``requests`` with ``due_s`` (open loop: each is sent at zero + due_s, by a
thread of its own) or ``clients`` = [which of the mix's clients] and ``seed``
(closed loop: each client sends its next request when the last one ended,
until ``STOP`` arrives on the input).
"""

from __future__ import annotations

import json
import os
import queue
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import traffic_gen as traffic  # noqa: E402


class Generator:
    def __init__(self, job):
        self.job = job
        self.mix = job["mix"]
        self.clients: "queue.SimpleQueue" = queue.SimpleQueue()
        self.lock = threading.Lock()
        self.stop = threading.Event()

    def client(self):
        from analytics_zoo_tpu.serving.generation import GenerationClient

        try:
            return self.clients.get_nowait()
        except queue.Empty:
            return GenerationClient(port=self.job["port"])

    def one(self, request, t_due):
        """Send one request and read its stream to the end."""
        ids = traffic.prompt_tokens(self.mix, request, self.job["vocab"])
        record = {"prompt_len": request["prompt_len"],
                  "output_len": request["output_len"],
                  "prefix": request["prefix"],
                  "token_seed": request["token_seed"], "t_due": t_due,
                  "t_send": None, "frames": [], "tokens": [],
                  "outcome": "unsent"}
        client = self.client()
        try:
            record["t_send"] = time.monotonic()
            uri = client.submit(ids, max_new_tokens=request["output_len"])
            record["outcome"] = "unfinished"
            for chunk in client.stream(uri, timeout_s=self.job["timeout_s"]):
                record["frames"].append([time.monotonic(), int(chunk.size)])
                record["tokens"].extend(chunk.tolist())
            got = sum(k for _, k in record["frames"])
            record["outcome"] = "ok" if got == request["output_len"] \
                else f"short:{got}"
            self.clients.put(client)
        except Exception as e:          # a failed request is a record too
            record["outcome"] = f"error:{type(e).__name__}:{e}"[:200]
            client.close()
        with self.lock:
            print(json.dumps(record), flush=True)

    def open_loop(self, zero):
        threads = []
        for request in self.job["requests"]:
            t_due = zero + request["due_s"]
            delay = t_due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            t = threading.Thread(target=self.one, args=(request, t_due),
                                 daemon=True)
            t.start()
            threads.append(t)
        for t in threads:
            t.join()

    def closed_loop(self, zero):
        def run(client):
            i = 0
            while not self.stop.is_set():
                self.one(traffic.closed_loop_request(
                    self.mix, self.job["seed"], client, i), time.monotonic())
                i += 1

        threads = [threading.Thread(target=run, args=(s,), daemon=True)
                   for s in self.job["clients"]]
        delay = zero - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        for t in threads:
            t.start()
        for line in sys.stdin:
            if line.strip() == "STOP":
                break
        self.stop.set()
        for t in threads:
            t.join()


def main() -> int:
    job = json.loads(sys.stdin.readline())
    gen = Generator(job)
    warm = [gen.client() for _ in range(job.get("connections", 4))]
    for c in warm:
        gen.clients.put(c)
    print("READY", flush=True)
    zero = float(sys.stdin.readline().split()[1])
    if "clients" in job:
        gen.closed_loop(zero)
    else:
        gen.open_loop(zero)
    print("DONE", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
