"""Profiler trace -> the numbers the per-layer readers and the breakdown
need.

A trace is reduced to two lists of intervals on the profiler's clock
(nanoseconds): device operations (the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane) and the benchmark's own host spans
(``TraceAnnotation``s by name). The reduction
works on that plain form, so it is tested on a small recorded trace
saved as JSON (``tests/data/trace_small.json``).

Kernels are found by their HLO signature: a Pallas kernel is a
``tpu_custom_call`` whose instruction is named after the jit that wraps
it, so only its result types tell the kernels apart. Each kernel
roofline reader states the pattern of its kernel (``SIGNATURE``).
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import re
from collections import defaultdict

# host spans the benchmark writes; the innermost one covering a device
# idle gap says what the host was doing in it
HOST_SPANS = ("bench.window", "engine.step", "index.query_batch",
              "client.wait")
_LAYOUT = re.compile(r"\{[^{}]*\}")


@dataclasses.dataclass
class Event:
    name: str
    start: float      # ns
    end: float        # ns


@dataclasses.dataclass
class Trace:
    ops: dict          # device -> [Event] (XLA Ops)
    host: list         # [Event] benchmark host spans

    # ---------------------------------------------------------- loading
    @classmethod
    def from_xplane(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        ops, host = {}, []
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        ops[plane.name] = [Event(e.name, e.start_ns,
                                                 e.start_ns + e.duration_ns)
                                           for e in line.events]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host += [Event(e.name, e.start_ns,
                                   e.start_ns + e.duration_ns)
                             for e in line.events if e.name in HOST_SPANS]
        return cls(ops, host)

    @classmethod
    def from_dir(cls, log_dir: str) -> "Trace":
        found = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
        if len(found) != 1:
            raise RuntimeError(f"expected one trace under {log_dir}, "
                               f"found {len(found)}")
        return cls.from_xplane(found[0])

    def to_json(self) -> dict:
        ev = lambda es: [[e.name, e.start, e.end] for e in es]  # noqa: E731
        return {"ops": {d: ev(v) for d, v in self.ops.items()},
                "host": ev(self.host)}

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        ev = lambda rows: [Event(*r) for r in rows]  # noqa: E731
        return cls({d: ev(v) for d, v in obj["ops"].items()},
                   ev(obj["host"]))

    # ------------------------------------------------------- reductions
    def window(self) -> tuple[float, float]:
        """The measured window: the ``bench.window`` host span."""
        spans = [e for e in self.host if e.name == "bench.window"]
        if len(spans) != 1:
            raise RuntimeError(f"{len(spans)} bench.window spans in trace")
        return spans[0].start, spans[0].end

    def spans(self, name: str) -> list[Event]:
        return sorted((e for e in self.host if e.name == name),
                      key=lambda e: e.start)

    @functools.cached_property
    def _busy(self) -> dict:
        return {d: _union(evs) for d, evs in self.ops.items()}

    @functools.cached_property
    def _host_index(self) -> dict:
        """name -> (starts, spans) sorted by start; spans of one name do
        not overlap (one thread writes them, none nests in its own)."""
        out = {}
        for name in {e.name for e in self.host}:
            spans = self.spans(name)
            out[name] = ([e.start for e in spans], spans)
        return out

    def busy_intervals(self, device: str) -> list[tuple[float, float]]:
        """Union of the device's operation intervals, sorted, disjoint."""
        return self._busy[device]

    @functools.cached_property
    def _busy_index(self) -> dict:
        """device -> (starts, ends, cumulative busy ns before each)."""
        out = {}
        for d, iv in self._busy.items():
            cum = [0.0]
            for a, b in iv:
                cum.append(cum[-1] + (b - a))
            out[d] = ([a for a, _ in iv], [b for _, b in iv], cum)
        return out

    def busy_ns(self, device: str, lo: float, hi: float) -> float:
        """Busy time in [lo, hi]: whole intervals by prefix sums, the two
        that straddle an end clipped."""
        starts, ends, cum = self._busy_index[device]
        i = bisect.bisect_right(ends, lo)        # first interval ending > lo
        j = bisect.bisect_left(starts, hi)       # intervals starting < hi
        if i >= j:
            return 0.0
        total = cum[j] - cum[i]
        total -= max(0.0, lo - starts[i])
        total -= max(0.0, ends[j - 1] - hi)
        return total

    def mean_busy_ns(self, lo: float, hi: float) -> float:
        """Busy time in [lo, hi], averaged over the traced devices."""
        return sum(self.busy_ns(d, lo, hi) for d in self.ops) / len(self.ops)

    def kernel_ns(self, signature: str, lo: float, hi: float) -> float:
        """Summed device time of ops matching a kernel signature that
        start inside [lo, hi], averaged over devices."""
        pat = re.compile(signature)
        total = sum(e.end - e.start for d in self.ops for e in self.ops[d]
                    if lo <= e.start <= hi and pat.search(e.name))
        return total / len(self.ops)

    def idle_gaps(self, device: str, lo: float, hi: float
                  ) -> list[tuple[float, float]]:
        gaps, cur = [], lo
        for a, b in self.busy_intervals(device):
            if b <= lo or a >= hi:
                continue
            if a > cur:
                gaps.append((cur, a))
            cur = max(cur, b)
        if cur < hi:
            gaps.append((cur, hi))
        return gaps

    def host_doing(self, t: float) -> str:
        """Name of the innermost benchmark span covering time t."""
        best = None
        for starts, spans in self._host_index.values():
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and spans[i].end >= t and (
                    best is None or spans[i].end - spans[i].start
                    < best.end - best.start):
                best = spans[i]
        return "none" if best is None else best.name

    def idle_by_host(self, lo: float, hi: float) -> dict[str, float]:
        """Device idle ns in [lo, hi] by what the host was doing at each
        gap's midpoint, averaged over devices."""
        out: dict[str, float] = defaultdict(float)
        for d in self.ops:
            for a, b in self.idle_gaps(d, lo, hi):
                out[self.host_doing((a + b) / 2)] += (b - a) / len(self.ops)
        return dict(out)

    def top_ops(self, lo: float, hi: float, labels: dict[str, str],
                n: int = 10) -> list[tuple[str, float]]:
        """Device ops taking most time in [lo, hi], in ns, averaged over
        devices; a kernel matching one of ``labels`` (name -> signature)
        is named for it."""
        pats = {name: re.compile(sig) for name, sig in labels.items()}
        out: dict[str, float] = defaultdict(float)
        for d in self.ops:
            for e in self.ops[d]:
                if lo <= e.start <= hi:
                    out[op_label(e.name, pats)] += (
                        (e.end - e.start) / len(self.ops))
        return sorted(out.items(), key=lambda kv: -kv[1])[:n]


def _union(events: list) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return [(a, b) for a, b in out]


def op_label(hlo: str, pats: dict) -> str:
    """A short name for an HLO instruction: the kernel it is, else its
    instruction name and result type without layouts."""
    for name, pat in pats.items():
        if pat.search(hlo):
            return name
    head = hlo.split(" = ", 1)
    if len(head) == 2:
        result = _LAYOUT.sub("", head[1].split(" ", 1)[0]
                             if not head[1].startswith("(")
                             else head[1].split(") ", 1)[0] + ")")
        return f"{head[0].lstrip('%')} {result}"
    return hlo[:80]
