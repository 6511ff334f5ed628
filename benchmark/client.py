"""The wire client and the load drivers, on the client's own clock.

One streaming ``requests`` payload of one request per connection. An
``overloaded`` reply is retried after the server's ``retry_after_s``
(capped at 30 s; exponential backoff with jitter when the hint is
absent), as the program's own ``serving.server.request`` loop does. A
request with no summary frame, a status other than ``ok``, or its
retries exhausted is ``failed``.
"""

from __future__ import annotations

import json
import random
import socket
import threading
import time

from benchmark.stats import Record


def ask(host: str, port: int, payload: dict, timeout: float = 600.0) -> dict:
    """One non-streaming round trip (probe verbs, warm-up payloads)."""
    with socket.create_connection((host, port), timeout=timeout) as s, \
            s.makefile("rwb") as f:
        f.write(json.dumps(payload).encode() + b"\n")
        f.flush()
        line = f.readline()
    if not line:
        raise ConnectionError("server closed the connection without a reply")
    resp = json.loads(line)
    if isinstance(resp, dict) and resp.get("error") is not None:
        raise RuntimeError(f"server error: {resp['error']}")
    return resp


def _backoff(attempt: int, base: float = 0.25, cap: float = 5.0) -> float:
    return min(base * 2 ** attempt, cap) * random.uniform(0.8, 1.2)


def stream_one(host: str, port: int, prompt, gen_len: int, rec: Record, *,
               max_retries: int = 200, timeout: float = 300.0) -> Record:
    """Send one request and stamp every token frame as it is read."""
    data = json.dumps({"requests": [list(prompt)], "gen_lens": [gen_len],
                       "stream": True}).encode() + b"\n"
    rec.prompt_len, rec.gen_len = len(prompt), gen_len
    retry = 0
    while True:
        rec.attempts += 1
        rec.token_ts.clear()
        rec.tokens.clear()
        try:
            with socket.create_connection((host, port), timeout=timeout) \
                    as s, s.makefile("rwb") as f:
                if rec.sent is None:
                    rec.sent = time.monotonic()
                f.write(data)
                f.flush()
                shed_hint = None
                while True:
                    line = f.readline()
                    t = time.monotonic()
                    if not line:
                        raise ConnectionError("closed before the summary")
                    obj = json.loads(line)
                    err = obj.get("error")
                    if err is not None:
                        if err.get("status") != "overloaded":
                            rec.status = f"failed:{err.get('status')}"
                            rec.done = t
                            return rec
                        rec.shed += 1
                        shed_hint = err.get("retry_after_s") or 0
                        break
                    if obj.get("frame") == "token":
                        rec.token_ts.append(t)
                        rec.tokens.append(int(obj["token"]))
                        continue
                    # The summary frame ends the stream.
                    res = obj["results"][0]
                    out = [int(x) for x in obj["outputs"][0]]
                    rec.server = (obj.get("wire") or [None])[0]
                    rec.done = t
                    if res["status"] != "ok":
                        rec.status = (f"failed:{res['status']}:"
                                      f"{str(res.get('reason'))[:300]}")
                    elif out != rec.tokens or len(out) != gen_len:
                        rec.status = "failed:frames_differ_from_summary"
                    else:
                        rec.status = "ok"
                    return rec
        except (OSError, ValueError) as e:  # dropped or garbled stream
            rec.status = f"failed:{type(e).__name__}"
            rec.done = time.monotonic()
            return rec
        if retry >= max_retries:
            rec.status = "failed:retries_exhausted"
            rec.done = time.monotonic()
            return rec
        hint = shed_hint
        time.sleep(min(float(hint), 30.0)
                   if isinstance(hint, (int, float)) and hint > 0
                   else _backoff(retry))
        retry += 1


def _callers(host, port, reqs, clients: int, end: float, records: list,
             kw: dict, start: float | None = None,
             stagger_s: float = 0.0) -> list:
    """Start ``clients`` threads that each send the deck's next request
    when their last one ends, until ``end``. Caller k sends its first at
    ``start + k * stagger_s`` (at once where ``start`` is None)."""
    deck = iter(reqs)
    lock = threading.Lock()

    def caller(k):
        if start is not None:
            time.sleep(max(start + k * stagger_s - time.monotonic(), 0))
        while True:
            due = time.monotonic()
            if due >= end:
                return
            with lock:
                r = next(deck, None)
                if r is None:
                    return
                rec = Record(i=r.i, due=due)
                records.append(rec)
            stream_one(host, port, r.prompt, r.gen_len, rec, **kw)

    threads = [threading.Thread(target=caller, args=(k,), daemon=True)
               for k in range(clients)]
    for th in threads:
        th.start()
    return threads


def run_deck(host: str, port: int, reqs: list, clients: int,
             limit_s: float = 600.0) -> list:
    """Send every request of ``reqs`` from ``clients`` callers and wait
    for all of them (the warm-up's driver)."""
    records: list = []
    end = time.monotonic() + limit_s
    for th in _callers(host, port, reqs, clients, end, records, {}):
        th.join(max(end - time.monotonic(), 0.0))
    return records


def drive(host: str, port: int, reqs: list, traffic: dict, seconds: float,
          t0: float, *, drain_s: float = 90.0) -> list:
    """Offer the window's load from ``t0`` and wait for every request
    that was started. Returns the records, in sending order."""
    kw = {"max_retries": int(traffic.get("max_retries", 200))}
    records: list = []
    threads: list = []
    if traffic["loop"] == "open":
        for r in reqs:
            due = t0 + r.t
            delay = due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            rec = Record(i=r.i, due=due)
            records.append(rec)
            th = threading.Thread(
                target=stream_one, daemon=True,
                args=(host, port, r.prompt, r.gen_len, rec), kwargs=kw)
            th.start()
            threads.append(th)
    else:
        threads = _callers(host, port, reqs, int(traffic["clients"]),
                           t0 + seconds, records, kw, start=t0,
                           stagger_s=float(traffic.get("stagger_ms", 0)) / 1e3)
    deadline = t0 + seconds + drain_s
    for th in threads:
        th.join(max(deadline - time.monotonic(), 0.0))
    for rec in records:
        if rec.status == "pending":
            rec.status = "failed:no_summary_before_drain_limit"
    return records
