"""The load generator: a process of its own that never imports JAX.

It talks HTTP to the server in the process that holds the chip, so that
client threads do not share the engine's interpreter lock.  Clients ride
persistent HTTP/1.1 connections opened before the first request, as a
gateway in front of a model server does.

    stdin:  one line, the JSON job (host, port, traffic, seed, seconds,
            template_tokens); then, after this process has answered
            "ready", one line {"t0": ...}: the instant the lead-in starts
    stdout: one line {"ready": true, "connect_ms": [...]} when every
            connection is open, and at the end one line {"records": [...]}

Every instant is ``time.monotonic()`` of this machine, which the server's
process shares, so the parent can lay its counters beside the records.
"""

from __future__ import annotations

import http.client
import json
import queue
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import traffic as traffic_mod  # noqa: E402

PATH = "/v1/chat/completions"


class Client:
    """One persistent connection; ``send`` runs one streamed request and
    returns its record."""

    def __init__(self, host: str, port: int, timeout_s: float):
        self.addr, self.timeout_s = (host, port), timeout_s
        t = time.monotonic()
        self.conn = http.client.HTTPConnection(*self.addr,
                                               timeout=self.timeout_s)
        self.conn.connect()
        self.connect_ms = (time.monotonic() - t) * 1e3

    def send(self, body: dict, rec: dict) -> dict:
        rec["sent"] = time.monotonic()
        try:
            self.conn.request("POST", PATH, json.dumps(body).encode(),
                              {"Content-Type": "application/json"})
            resp = self.conn.getresponse()
            if resp.status != 200:
                resp.read()
                rec["status"] = f"http_{resp.status}"
                return rec
            events = []
            for line in resp:  # chunked framing is undone by http.client
                if not line.startswith(b"data:"):
                    continue
                if line[5:].strip() == b"[DONE]":
                    rec["done"] = time.monotonic()
                    resp.read()  # the closing chunk: the connection is reused
                    break
                if b'"error"' in line:
                    rec["status"] = "error_event"
                if b'"content"' in line:
                    events.append(time.monotonic())
            rec["events"] = events
            if "done" in rec and events and "status" not in rec:
                rec["status"] = "ok"
            rec.setdefault("status", "no_done")
        except (OSError, http.client.HTTPException) as e:
            rec["status"] = f"error_{type(e).__name__}"
            self.conn.close()
            self.conn = http.client.HTTPConnection(*self.addr,
                                                   timeout=self.timeout_s)
        return rec

    def close(self):
        self.conn.close()


def run_closed(job: dict, reqs: list[dict], clients: list[Client]) -> list:
    """Each client sends its next request when the last completes, until
    the window ends; what is in flight then runs to its end, so that every
    request sent is accounted for."""
    tr, t0 = job["traffic"], job["t0"]
    t_end = t0 + tr["lead_in_s"] + job["seconds"]
    lock, records, counter = threading.Lock(), [], [0]

    def client(c: int):
        # the clients start spread over the stagger, so that the batch
        # does not begin as one burst of prefills
        wait = t0 + tr["stagger_s"] * c / len(clients) - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        while time.monotonic() < t_end:
            with lock:
                k = counter[0]
                counter[0] += 1
            req = reqs[k % len(reqs)]
            rec = clients[c].send(
                traffic_mod.body(tr, job["seed"], k, req,
                                 job["template_tokens"]),
                {"k": k, **req})
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(len(clients))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return records


def run_open(job: dict, reqs: list[dict], clients: list[Client]) -> list:
    """Requests are sent when they are due, whether or not earlier ones
    have finished, over a pool of connections; one that finds no free
    connection waits for one, and its lateness says so."""
    tr, t0 = job["traffic"], job["t0"]
    todo: queue.Queue = queue.Queue()
    lock, records = threading.Lock(), []

    def worker(c: int):
        while True:
            item = todo.get()
            if item is None:
                return
            k, req = item
            rec = clients[c].send(
                traffic_mod.body(tr, job["seed"], k, req,
                                 job["template_tokens"]),
                {"k": k, **req, "due": t0 + req["due_s"]})
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=worker, args=(c,))
               for c in range(len(clients))]
    for t in threads:
        t.start()
    for k, req in enumerate(reqs):
        wait = t0 + req["due_s"] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        todo.put((k, req))
    for _ in threads:
        todo.put(None)
    for t in threads:
        t.join()
    return records


#: pause between two connects: the server's listen queue holds 5 (the
#: stdlib's request_queue_size), and a SYN that finds it full is dropped
#: and sent again a second later
CONNECT_PAUSE_S = 0.005


def main() -> None:
    job = json.loads(sys.stdin.readline())
    tr = job["traffic"]
    reqs = traffic_mod.requests_in_order(tr, job["seconds"])
    n_conn = tr["clients"] if tr["loop"] == "closed" else tr["connections"]
    clients = []
    try:
        for _ in range(n_conn):
            clients.append(Client(job["host"], job["port"],
                                  tr["request_timeout_s"]))
            time.sleep(CONNECT_PAUSE_S)
        print(json.dumps({"ready": True,
                          "connect_ms": [c.connect_ms for c in clients]}),
              flush=True)
        job["t0"] = json.loads(sys.stdin.readline())["t0"]
        run = run_closed if tr["loop"] == "closed" else run_open
        records = run(job, reqs, clients)
    finally:
        for c in clients:
            c.close()
    print(json.dumps({"records": records}), flush=True)


if __name__ == "__main__":
    main()
