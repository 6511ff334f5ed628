"""Simulation of `qwen3-4b.chat-closed8`'s `ttft_p50_ms` as a function of the
decode round: the replica worker runs whatever is queued to completion as one
batch on 4 slots, every request of a run gets its summary when the run ends, and
each of the 8 callers sends the deck's next request when its last one ends.
A count on the sandbox's CPU from the fixed deck, not a measurement."""
import json, statistics, sys
sys.path.insert(0, ".")
from benchmark import traffic

cell = json.load(open("benchmark/workloads/qwen3-4b.chat-closed8.json"))
deck = [(len(r.prompt), r.gen_len) for r in traffic.generate(cell, 1, 50.0, 1000)]


def prefill_ms(prompt):
    return 10.0 + 0.05 * prompt


def simulate(round_ms, first=1, seconds=50.0, slots=4):
    nxt = iter(range(len(deck)))
    due, ttft = {}, {}
    queue = []
    t = 0.0
    start = [next(nxt) for _ in range(8)]
    for i in start:
        due[i] = 0.0
    queue = start[:]
    take = first
    pending = []  # sent at a run's end: too late for the run that starts then
    while queue or pending:
        if not queue:
            queue, pending = pending, []
        batch, queue = queue[:take], queue[take:] + pending
        pending = []
        take = 64
        # one engine.run(batch)
        live = {}  # i -> tokens left after the first
        waiting = batch[:]
        def admit(t):
            while waiting and len(live) < slots:
                i = waiting.pop(0)
                t += prefill_ms(deck[i][0])
                ttft[i] = t - due[i]
                if deck[i][1] > 1:
                    live[i] = deck[i][1] - 1
            return t
        t = admit(t)
        while live or waiting:
            if not live:
                t = admit(t)
                continue
            t += round_ms
            done = [i for i in live if live[i] == 1]
            for i in live:
                live[i] -= 1
            for i in done:
                del live[i]
            if done:
                t = admit(t)
        # the run ends: its callers send their next requests
        for _ in batch:
            if t / 1e3 < seconds:
                j = next(nxt, None)
                if j is not None:
                    due[j] = t
                    pending.append(j)
    vals = sorted(ttft.values())
    return statistics.median(vals), len(vals)


if __name__ == "__main__":
    for first in (1, 2, 3, 4):
        print("first run takes", first, {r: tuple(round(x) for x in simulate(r, first))
                                         for r in (31.2, 19.4, 17.5, 17.0, 16.0, 15.5, 15.0, 14.5, 14.0)})
