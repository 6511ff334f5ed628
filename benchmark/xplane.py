"""From a profiler trace (``*.xplane.pb``) to busy and idle time, time
by program and by operation, and the idle gaps with what the host was
doing in each.

The reduction works on a neutral form, so that it can be checked on a
small recorded trace kept as JSON:

    {"devices": {"0": {"ops": [[name, start_ns, dur_ns], ...],
                       "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[thread, name, start_ns, dur_ns], ...]}

``ops`` is the device's line of single operations, ``modules`` its line
of whole compiled programs (one event per launch). ``from_xplane``
fills the form from a file with nothing but JAX.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def from_xplane(path: str, chips: int) -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    form: dict = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < chips:
            dev = form["devices"].setdefault(m.group(1),
                                             {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] += [[e.name, float(e.start_ns),
                                  float(e.duration_ns)] for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                form["host"] += [[line.name, e.name, float(e.start_ns),
                                  float(e.duration_ns)] for e in line.events
                                 if e.duration_ns > 0]
    return form


def op_key(name: str) -> str:
    """An operation's short name: ``%fusion.129 = bf16[...] fusion(...)``
    reads ``fusion.129 bf16[4,19456]``; a custom call keeps its target."""
    m = re.match(r"%?([\w.\-]+) = \(?(\w+\[[\d,]*\])", name)
    key = f"{m.group(1)} {m.group(2)}" if m else name[:60]
    t = re.search(r'custom_call_target="([^"]+)"', name)
    return f"{key} {t.group(1)}" if t else key


def near(launches: int, counted: int) -> bool:
    """A count of launches in a trace against a counter read at its
    edges: within a quarter, or within two where the counts are small (a
    step at each edge of the span may fall on either side)."""
    return abs(launches - counted) <= max(2, 0.25 * counted)


def union_ns(intervals) -> float:
    """Total length of the union of ``(start, dur)`` intervals."""
    total, end = 0.0, None
    for s, d in sorted(intervals):
        e = s + d
        if end is None or s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


@dataclasses.dataclass
class Trace:
    form: dict
    t0_ns: float
    t1_ns: float

    @classmethod
    def of(cls, form: dict) -> "Trace":
        starts, ends = [], []
        for dev in form["devices"].values():
            for ev in dev["ops"] + dev["modules"]:
                starts.append(ev[1])
                ends.append(ev[1] + ev[2])
        for ev in form["host"]:
            starts.append(ev[2])
            ends.append(ev[2] + ev[3])
        if not starts:
            raise ValueError("the trace holds no event")
        return cls(form, min(starts), max(ends))

    @property
    def window_s(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9

    def device_busy_s(self, dev: str) -> float:
        d = self.form["devices"][dev]
        lines = d["ops"] or d["modules"]
        return union_ns((s, dur) for _, s, dur in lines) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        devs = self.form["devices"]
        if not devs:
            return 0.0
        return sum(self.device_busy_s(k) for k in devs) / len(devs)

    def first_device(self) -> str:
        return min(self.form["devices"], key=int)

    def modules(self, pattern: str | None = None, dev: str | None = None):
        """Program launches on one device (the first by default) whose
        name matches ``pattern``, in time order."""
        evs = self.form["devices"][dev or self.first_device()]["modules"]
        rx = re.compile(pattern) if pattern else None
        return sorted((e for e in evs if rx is None or rx.search(e[0])),
                      key=lambda e: e[1])

    def programs(self, min_mean_ms: float = 0.0) -> dict:
        """Launches of the first device by full program name (XLA puts
        the program's fingerprint in it): name -> [(start, dur), ...],
        only programs whose mean launch lasts ``min_mean_ms`` or more."""
        out: dict = {}
        for name, s, d in self.modules():
            out.setdefault(name, []).append((s, d))
        return {k: v for k, v in out.items()
                if sum(d for _, d in v) / len(v) >= min_mean_ms * 1e6}

    def program_launched(self, times: int,
                         min_mean_ms: float = 1.0) -> str | None:
        """The program whose launch count is nearest to ``times``, if
        the two are ``near``: how a counted step is found where the
        program gives its jitted steps no name of their own."""
        best = None
        for name, evs in self.programs(min_mean_ms).items():
            off = abs(len(evs) - times)
            if near(len(evs), times) and (best is None or off < best[0]):
                best = (off, name)
        return best[1] if best else None

    def gaps_before(self, starts) -> list:
        """Idle nanoseconds between each launch that starts at one of
        ``starts`` and the launch before it (whatever that was), on the
        first device."""
        starts = set(starts)
        evs = self.modules()
        return [max(cur[1] - (prev[1] + prev[2]), 0.0)
                for prev, cur in zip(evs, evs[1:]) if cur[1] in starts]

    def self_seconds(self) -> dict:
        """Device time by operation, each operation's time less that of
        the operations nested in it (a `while` holds its body's)."""
        d = self.form["devices"][self.first_device()]
        evs = sorted(d["ops"] or d["modules"], key=lambda e: (e[1], -e[2]))
        out: dict = {}
        stack: list = []  # [name, end, self_ns]

        def close(upto):
            while stack and stack[-1][1] <= upto:
                name, _, own = stack.pop()
                out[name] = out.get(name, 0.0) + max(own, 0.0) / 1e9

        for name, s, dur in evs:
            close(s)
            if stack:
                stack[-1][2] -= dur
            stack.append([op_key(name), s + dur, dur])
        close(float("inf"))
        return out

    def idle_gaps(self) -> list:
        """Every idle interval of the first device, ``(start, dur)``."""
        d = self.form["devices"][self.first_device()]
        evs = sorted((s, dur) for _, s, dur in (d["ops"] or d["modules"]))
        out, end = [], self.t0_ns
        for s, dur in evs:
            if s > end:
                out.append((end, s - end))
            end = max(end, s + dur)
        if self.t1_ns > end:
            out.append((end, self.t1_ns - end))
        return out

    def host_span_at(self, t_ns: float) -> str:
        """The innermost host span that covers ``t_ns``."""
        best = None
        for thread, name, s, dur in self.form["host"]:
            if s <= t_ns <= s + dur and (best is None or dur < best[1]):
                best = (name, dur)
        return best[0] if best else "(no host span)"

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time, and the longest
        idle gaps by what the host was doing."""
        by_op = self.self_seconds()
        by_gap: dict = {}
        for s, dur in sorted(self.idle_gaps(), key=lambda g: -g[1])[:200]:
            key = self.host_span_at(s + dur / 2)
            by_gap[key] = by_gap.get(key, 0.0) + dur / 1e9

        def first(d):
            return [[k, v] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:top]]

        return {"device_ops": first(by_op), "idle_gaps": first(by_gap)}

    def describe(self) -> dict:
        """For an earlier output line: what the trace held."""
        progs = {k: (len(v), sum(d for _, d in v) / 1e9)
                 for k, v in self.programs().items()}
        top = sorted(progs.items(), key=lambda kv: -kv[1][1])[:12]
        d = self.form["devices"][self.first_device()]
        kernels = sorted({re.sub(r"\{[^}]*\}", "", n)[:400]
                          for n, _, _ in d["ops"] if "custom-call" in n})
        return {"devices": sorted(self.form["devices"], key=int),
                "window_s": self.window_s, "busy_s": self.busy_s,
                "programs": [[k, n, t] for k, (n, t) in top],
                "custom_calls": kernels[:12],
                "host_events": len(self.form["host"])}


def sample(form: dict, seconds: float) -> dict:
    """A piece of a trace for the tests: what the first device and the
    host did in ``seconds`` from the middle of the trace on, starting at
    a program launch, with operation names cut to 64 characters."""
    tr = Trace.of(form)
    dev = tr.first_device()
    mid = (tr.t0_ns + tr.t1_ns) / 2
    a = min((m[1] for m in tr.modules() if m[1] >= mid), default=mid)
    b = a + seconds * 1e9

    def cut(evs, i):
        return [[*e[:i - 1], e[i - 1][:64], *e[i:]]
                for e in evs if a <= e[i] and e[i] + e[i + 1] <= b]

    d = form["devices"][dev]
    return {"devices": {dev: {"ops": cut(d["ops"], 1),
                              "modules": cut(d["modules"], 1)}},
            "host": cut(form["host"], 2)}


def reduce_dir(trace_dir: str, chips: int) -> Trace:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    return Trace.of(from_xplane(max(paths, key=os.path.getmtime), chips))
