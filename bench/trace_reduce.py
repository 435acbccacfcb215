"""From a profiler trace (`.xplane.pb`) to the numbers the per-layer
readers use: device busy time, time per jitted module, time per device
op, collective time inside a module, and idle gaps named by the host span
that was open.

Only `jax.profiler.ProfileData` is used.  A TPU trace has one plane per
chip (`/device:TPU:<n>`) with an `XLA Modules` line (one event per
executable run, named `<jit name>(<id>)`), an `XLA Ops` line (one event
per HLO op run, its name the op's HLO text) and an `Async XLA Ops` line
(copies and other asynchronous ops, which overlap the others).  A
control-flow op (`while`, ...) spans the ops of its body and is left out:
busy time is the union of the other ops.  The host's spans (`jax.profiler.TraceAnnotation`) are
events on the host plane's lines; the benchmark names its own `bench.*`.
Device and host events share one clock in the trace.
"""
from __future__ import annotations

import bisect
import collections
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


ASYNC_OPS_LINE = "Async XLA Ops"
# control-flow ops span the ops of their bodies; they are not work
CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")


def module_name(event_name: str) -> str:
    """`jit_step(123)` -> `step`."""
    name = event_name.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """`%fusion.12 = bf16[8,128]{1,0} fusion(...)` -> `fusion.12
    bf16[8,128]`: the HLO op and the shape it produces."""
    name, _, rest = event_name.lstrip("%").partition(" = ")
    return f"{name} {rest.split('{')[0].split(' ')[0]}".strip()


def union_ns(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


class Chip:
    """One chip's events inside the window."""

    def __init__(self, plane, lo: float, hi: float):
        self.modules: List[Tuple[float, float, str]] = []
        self.ops: List[Tuple[float, float, str]] = []        # leaf ops
        self.async_ops: List[Tuple[float, float, str]] = []  # DMAs etc.
        lines = {MODULES_LINE: (self.modules, module_name),
                 OPS_LINE: (self.ops, op_name),
                 ASYNC_OPS_LINE: (self.async_ops, op_name)}
        for line in plane.lines:
            if line.name not in lines:
                continue
            dst, name_of = lines[line.name]
            for ev in line.events:
                s, e = ev.start_ns, ev.end_ns
                if e > lo and s < hi:
                    n = name_of(ev.name)
                    if dst is self.modules or not CONTAINER.match(n):
                        dst.append((max(s, lo), min(e, hi), n))
        for dst, _ in lines.values():
            dst.sort()
        self.busy = union_ns([(s, e) for s, e, _ in self.ops])

    def busy_ns(self) -> float:
        return sum(e - s for s, e in self.busy)

    def module_time(self) -> Dict[str, Tuple[float, int]]:
        """module -> (total ns, runs)."""
        out: Dict[str, List[float]] = collections.defaultdict(lambda: [0, 0])
        for s, e, n in self.modules:
            out[n][0] += e - s
            out[n][1] += 1
        return {k: (v[0], int(v[1])) for k, v in out.items()}

    def op_time(self) -> Dict[str, float]:
        out: Dict[str, float] = collections.defaultdict(float)
        for s, e, n in self.ops:
            out[n] += e - s
        return dict(out)

    def ops_inside(self, module: str, pattern: re.Pattern) -> float:
        """ns of ops, synchronous or async, matching ``pattern`` that run
        inside runs of ``module`` (by time)."""
        runs = [(s, e) for s, e, n in self.modules if n == module]
        starts = [s for s, _ in runs]
        total = 0.0
        for s, e, n in self.ops + self.async_ops:
            if not pattern.search(n):
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < runs[i][1]:
                total += min(e, runs[i][1]) - s
        return total


class Trace:
    """The reduction of one trace over the benchmark's window span."""

    def __init__(self, profile, chips: Optional[int] = None):
        planes = {}
        host_spans = []
        for plane in profile.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                planes[int(m.group(1))] = plane
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIX):
                            host_spans.append(
                                (ev.start_ns, ev.end_ns, ev.name))
        win = [(s, e) for s, e, n in host_spans if n == WINDOW_SPAN]
        if not win:
            raise ValueError(f"no {WINDOW_SPAN} span in the trace")
        if not planes:
            raise ValueError("no TPU device plane in the trace")
        self.lo, self.hi = win[0]
        ids = sorted(planes)[:chips] if chips else sorted(planes)
        self.chips = [Chip(planes[i], self.lo, self.hi) for i in ids]
        self.spans = sorted((s, e, n[len(SPAN_PREFIX):])
                            for s, e, n in host_spans if n != WINDOW_SPAN)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self) -> float:
        """Seconds with an op running, averaged over the chips."""
        return sum(c.busy_ns() for c in self.chips) / len(self.chips) * 1e-9

    def module_time(self, chip: int = 0):
        return self.chips[chip].module_time()

    def top_ops(self, n: int = 10, chip: int = 0) -> List[list]:
        t = sorted(self.chips[chip].op_time().items(), key=lambda kv: -kv[1])
        return [[k, v * 1e-9] for k, v in t[:n]]

    def span_at(self, t: float) -> str:
        """Innermost benchmark span open at host time ``t``."""
        best = None
        for s, e, n in self.spans:
            if s > t:
                break
            if e >= t and (best is None or e - s < best[1] - best[0]):
                best = (s, e, n)
        return best[2] if best else "no span"

    def idle_gaps(self, chip: int = 0) -> List[Tuple[float, float]]:
        busy = self.chips[chip].busy
        edges = [self.lo] + [x for iv in busy for x in iv] + [self.hi]
        return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]

    def longest_gaps(self, n: int = 10, chip: int = 0) -> List[list]:
        gaps = sorted(self.idle_gaps(chip), key=lambda g: g[0] - g[1])[:n]
        return [[self.span_at((s + e) / 2), (e - s) * 1e-9] for s, e in gaps]


def load(path: str, chips: Optional[int] = None) -> Trace:
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            return Trace(ProfileData.from_serialized_xspace(f.read()), chips)
    return Trace(ProfileData.from_file(path), chips)


def describe(path: str) -> None:
    """Print a trace's planes and lines, with event counts and samples:
    what to look at before matching names against it."""
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(path)
    for plane in prof.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            print(f"  line {line.name!r}: {len(evs)} events, "
                  f"{len(names)} names; top {names.most_common(6)}")
            if evs:
                e = evs[len(evs) // 2]
                print(f"    e.g. {e.name!r} start {e.start_ns} dur "
                      f"{e.duration_ns} stats {list(e.stats)[:8]}")


if __name__ == "__main__":
    import sys
    if len(sys.argv) > 2 and sys.argv[2] == "--describe":
        describe(sys.argv[1])
    else:
        tr = load(sys.argv[1])
        print(f"window {tr.window_s:.6f} s, busy {tr.busy_s():.6f} s")
        print("modules", tr.module_time())
        print("top ops", tr.top_ops())
        print("longest gaps", tr.longest_gaps())
