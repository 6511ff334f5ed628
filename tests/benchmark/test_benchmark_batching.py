"""Finding 1 of ISSUE 25, as a test: requests from independent
connections share an engine batch under `--continuous --replicas 1` and
do not under `--continuous` alone. Read from the program's own counter
of decode steps against the tokens generated."""

import threading
import time

import numpy as np

from benchmark import client, server
from benchmark.stats import Record

STEPS = "tdt_engine_decode_steps_total"


def decode_steps_for_two_waiting_requests(flags, workdir):
    """Request A occupies the engine; B and C arrive on connections of
    their own while it runs. Returns the decode steps the three took."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 256, size=12).tolist() for _ in range(3)]
    with server.running_server(["--model", "tiny", *flags],
                               workdir) as (host, port, _):
        # Compile first, so that A's run is not mostly compilation.
        client.stream_one(host, port, prompts[0], 4, Record(i=9, due=0.0))
        before = server.counters(host, port)[STEPS]
        recs = [Record(i=i, due=time.monotonic()) for i in range(3)]
        ths = [threading.Thread(target=client.stream_one, args=(
            host, port, prompts[i], g, recs[i]))
            for i, g in enumerate((10, 12, 12))]
        ths[0].start()
        while not recs[0].token_ts and ths[0].is_alive():
            time.sleep(0.005)
        ths[1].start()
        ths[2].start()
        for th in ths:
            th.join(120)
        assert all(r.ok for r in recs), [r.status for r in recs]
        return server.counters(host, port)[STEPS] - before


def test_independent_connections_share_a_batch_only_behind_a_replica(tmp_path):
    shared = decode_steps_for_two_waiting_requests(
        ["--continuous", "--replicas", "1", "--max-batch", "4"],
        str(tmp_path / "a"))
    alone = decode_steps_for_two_waiting_requests(
        ["--continuous", "--max-batch", "4"], str(tmp_path / "b"))
    # A: 9 steps after its prefill's token. B and C: 11 each when each
    # runs alone, 11 together when they share the batch.
    assert alone >= 9 + 11 + 11 - 2
    assert shared <= 9 + 11 + 2
