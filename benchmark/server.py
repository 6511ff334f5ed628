"""``serving.run_server.main`` on a thread of the one process that
holds the chip, as a user would start it, and the probes around it."""

from __future__ import annotations

import contextlib
import os
import threading
import time

from benchmark import client


@contextlib.contextmanager
def running_server(argv: list, workdir: str):
    """Start the server; yield ``(host, port, listening_after_s)``; on
    exit send ``shutdown``, join the thread and raise unless ``main``
    returned 0."""
    from triton_distributed_tpu.serving import run_server

    os.makedirs(workdir, exist_ok=True)
    # By process id: runs in one checkout at a time are the rule, but
    # the tests drive several at once.
    port_file = os.path.join(workdir, f"port.{os.getpid()}")
    if os.path.exists(port_file):
        os.remove(port_file)
    argv = [*argv, "--port", "0", "--port-file", port_file]
    outcome: dict = {}

    def serve():
        try:
            outcome["rc"] = run_server.main(argv)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            outcome["error"] = e

    t0 = time.monotonic()
    th = threading.Thread(target=serve, daemon=True, name="run_server")
    th.start()
    while not os.path.exists(port_file):
        if not th.is_alive():
            raise RuntimeError(
                f"run_server {' '.join(argv)} ended before it listened"
            ) from outcome.get("error")
        time.sleep(0.02)
    with open(port_file) as f:
        host, port = f.read().strip().rsplit(":", 1)
    port = int(port)
    try:
        yield host, port, time.monotonic() - t0
    finally:
        if th.is_alive():
            try:
                client.ask(host, port, {"cmd": "shutdown"}, timeout=60)
            except (OSError, RuntimeError):
                pass
        th.join(timeout=120)
        os.remove(port_file)
    if th.is_alive():
        raise RuntimeError("the server thread did not stop")
    if "error" in outcome:
        raise RuntimeError("run_server raised") from outcome["error"]
    if outcome.get("rc") != 0:
        raise RuntimeError(f"run_server returned {outcome}")


def release_program_state() -> int:
    """Free what the stopped server still holds on the device, so that
    the reference fits. ``run_server``'s ``shutdown`` verb ends the
    accept loop but never drains the router, whose replica worker
    threads keep every engine (weights, KV pool) alive: drain the routers
    here, drop the process-global mesh context and JAX's caches. Returns
    the bytes of device arrays still alive."""
    import gc

    import jax

    from triton_distributed_tpu.runtime import mesh

    for obj in gc.get_objects():
        kind = type(obj)
        if (kind.__name__ == "Router"
                and kind.__module__.startswith("triton_distributed_tpu")):
            obj.shutdown()
    getattr(mesh, "finalize_distributed", lambda: None)()
    del obj
    gc.collect()
    jax.clear_caches()
    gc.collect()
    return sum(x.nbytes for x in jax.live_arrays())


def counters(host: str, port: int) -> dict:
    """The program's live counters, cumulative since it started: every
    unlabeled counter and gauge of its metrics registry by name
    (``tdt_engine_decode_steps_total``, ...), each histogram as
    ``{"count", "sum", "edges", "counts"}``, and the front door's own
    counts as ``server.<name>`` (``server.shed``, ...)."""
    snap = client.ask(host, port, {"cmd": "metrics"})["metrics"]
    out: dict = {}
    for name, fam in snap.items():
        for s in fam["series"]:
            key = name + "".join(f"[{k}={v}]" for k, v in
                                 sorted(s["labels"].items()))
            if fam["type"] == "histogram":
                out[key] = {"count": s["count"], "sum": s["sum"],
                            "edges": s["buckets"]["edges"],
                            "counts": s["buckets"]["counts"]}
            else:
                out[key] = s["value"]
    front = client.ask(host, port, {"cmd": "stats"})["stats"].get("server", {})
    for k, v in front.items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[f"server.{k}"] = v
    return out


def delta(after: dict, before: dict) -> dict:
    """``after - before``, histograms bucket by bucket."""
    out = {}
    for k, a in after.items():
        b = before.get(k)
        if isinstance(a, dict):
            bc = b["counts"] if b else [0] * len(a["counts"])
            out[k] = {"count": a["count"] - (b["count"] if b else 0),
                      "sum": a["sum"] - (b["sum"] if b else 0.0),
                      "edges": a["edges"],
                      "counts": [x - y for x, y in zip(a["counts"], bc)]}
        else:
            out[k] = a - (b or 0)
    return out


def histogram_quantile(h: dict, q: float) -> float | None:
    """Quantile of a bucketed histogram, linear inside the bucket; None
    when it holds nothing. The overflow bucket reads as its lower edge."""
    n = h["count"]
    if n <= 0:
        return None
    want, cum, lo = q * n, 0.0, 0.0
    for edge, c in zip(h["edges"] + [None], h["counts"]):
        if c > 0 and cum + c >= want:
            if edge is None:
                return lo
            return lo + (edge - lo) * (want - cum) / c
        cum += c
        if edge is not None:
            lo = edge
    return lo
