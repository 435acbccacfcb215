"""Device time by the program's model scope, and idle time by the
program's host spans, from a profiler trace of the window.

A trace names each device op by its HLO instruction (`fusion.170`) and
carries no `op_name`; `trace_reduce.Trace` keeps only the harness's own
`bench.*` spans.  The program names both sides itself:

- `ContinuousBatcher.op_scopes()` gives, per jitted module, one map
  {instruction: scope} for each of its executables, from the compiled
  text's `jax.named_scope` metadata (`hlo_analysis.SCOPES`);
- the scheduler opens `serve.*` spans (`serve.tick`, `serve.step.readback`,
  `serve.admit#rid=3,chunks=4#`, ...) inside the harness's `bench.*` ones.

`scope_ns` groups the synchronous ops inside each run of a module by
scope, reading each run with the map of the executable that knows most of
its ops (both admission-chunk executables are `admit_chunk`).  The
per-layer readers `<scope>_ms.decode` use it; `python3 bench/scopes.py
<trace.xplane.pb> [<op_scopes.json>]` prints the whole breakdown of a kept
trace.
"""
from __future__ import annotations

import bisect
import collections
import gc
import json
import re
import sys
import traceback
from typing import Dict, List, Optional, Tuple

import trace_reduce

UNSCOPED = "unscoped"
SPAN_PREFIXES = ("bench.", "serve.")
ARGS = re.compile(r"#.*#$")       # TraceAnnotation keywords: `name#k=v#`

Maps = Dict[str, List[Dict[str, str]]]


def span_name(event_name: str) -> str:
    """`serve.admit#rid=3,chunks=4#` -> `serve.admit`."""
    return ARGS.sub("", event_name)


def instruction(op: str) -> str:
    """`fusion.170 bf16[8192,16,4,128]` (trace_reduce.op_name) ->
    `fusion.170`."""
    return op.split(" ")[0]


def scoped_ops(chip: "trace_reduce.Chip", module: str,
               maps: List[Dict[str, str]]):
    """(ns, op, scope) of each synchronous op inside the runs of
    ``module`` (`unscoped` where no map names it), and the number of
    runs."""
    runs = [(s, e) for s, e, n in chip.modules if n == module]
    starts = [s for s, _ in runs]
    per_run: Dict[int, list] = collections.defaultdict(list)
    for s, e, n in chip.ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][1]:
            per_run[i].append((min(e, runs[i][1]) - s, n))
    out = []
    for ops in per_run.values():
        names = {instruction(n) for _, n in ops}
        m = max(maps or [{}], key=lambda m: (len(names & m.keys()), -len(m)))
        out += [(ns, n, m.get(instruction(n), UNSCOPED)) for ns, n in ops]
    return out, len(runs)


def scope_ns(chip: "trace_reduce.Chip", module: str,
             maps: List[Dict[str, str]]) -> Tuple[Dict[str, float], int]:
    """ns of synchronous ops inside runs of ``module`` by scope, and the
    number of runs."""
    ops, runs = scoped_ops(chip, module, maps)
    out: Dict[str, float] = collections.defaultdict(float)
    for ns, _, scope in ops:
        out[scope] += ns
    return dict(out), runs


def op_scopes(run) -> Optional[Maps]:
    """The op-scope maps of the run's executables: ``run.op_scopes`` if
    set, else read from a replica built anew on the run's chips once the
    window's state is gone, and kept on ``run``.  None where the program
    has no ``ContinuousBatcher.op_scopes`` or the build fails (reported
    on standard error; the run's other numbers stand)."""
    got = getattr(run, "op_scopes", None)
    if got is not None:
        return got or None
    from repro.inference.scheduler import ContinuousBatcher
    if not hasattr(ContinuousBatcher, "op_scopes") or run.cell is None:
        return None
    import jax
    import harness
    run.op_scopes = {}          # read once: a failure leaves nothing to read
    try:
        batcher = harness.build(run.cell, 0, jax.devices()[:run.chips])
        try:
            run.op_scopes = batcher.op_scopes()
        finally:
            for a in jax.tree.leaves((batcher.params, batcher.cache,
                                      batcher._state)):
                a.delete()
            del batcher
            gc.collect()
    except Exception:           # the run's other numbers stand without it
        harness.say("scope maps: the replica built anew failed\n"
                    + traceback.format_exc())
    return run.op_scopes or None


def reader(module: str, scope: str):
    """A per-layer reader: ms of ``scope``'s ops per run of ``module`` on
    the first chip."""

    def read(run):
        if run.trace is None:
            return None
        maps = op_scopes(run)
        if not maps or not any(maps.get(module, ())):
            return None     # no executable of the module names its ops
        by, runs = scope_ns(run.trace.chips[0], module, maps[module])
        return by.get(scope, 0.0) / runs * 1e-6 if runs else None

    return read


# ---------------------------------------------------------------------------
# the whole breakdown of a kept trace
# ---------------------------------------------------------------------------


def host_spans(profile) -> List[Tuple[float, float, str]]:
    """Every `bench.*` and `serve.*` span of the host plane but the
    window's, names without their keywords, sorted by start."""
    out = []
    for plane in profile.planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                name = span_name(ev.name)
                if name.startswith(SPAN_PREFIXES) \
                        and name != trace_reduce.WINDOW_SPAN:
                    out.append((ev.start_ns, ev.end_ns, name))
    return sorted(out)


def idle_by_span(tr: "trace_reduce.Trace", chip: int = 0
                 ) -> Dict[str, float]:
    """Idle ns of the window by the innermost span open at each gap's
    middle."""
    out: Dict[str, float] = collections.defaultdict(float)
    for s, e in tr.idle_gaps(chip):
        out[tr.span_at((s + e) / 2)] += e - s
    return dict(out)


def idle_inside(tr: "trace_reduce.Trace", spans, name: str,
                chip: int = 0) -> Tuple[float, int]:
    """Idle ns of the device inside spans called ``name``, and their
    count (spans clipped to the window)."""
    gaps = tr.idle_gaps(chip)
    total, n = 0.0, 0
    for s, e, sn in spans:
        if sn != name or e <= tr.lo or s >= tr.hi:
            continue
        n += 1
        for gs, ge in gaps:
            total += max(0.0, min(e, ge) - max(s, gs))
    return total, n


def load_profile(path: str):
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def breakdown(prof, maps: Optional[Maps] = None) -> dict:
    """Module times with their scope split, the top device ops with their
    scope, the longest idle gaps and the idle time by the innermost span
    (the program's where one is open), and the idle time per `serve.tick`."""
    tr = trace_reduce.Trace(prof)
    # gaps are named by the innermost span, the program's ones included
    tr.spans = spans = host_spans(prof)
    chip = tr.chips[0]
    out = {"window_s": tr.window_s, "busy_s": tr.busy_s(), "modules": {}}
    for mod, (ns, runs) in tr.module_time(0).items():
        row = {"runs": runs, "ms_per_run": ns / runs * 1e-6}
        if maps and mod in maps:
            by, _ = scope_ns(chip, mod, maps[mod])
            row["sync_ops_ms_per_run"] = sum(by.values()) / runs * 1e-6
            row["scopes_ms_per_run"] = {
                k: v / runs * 1e-6
                for k, v in sorted(by.items(), key=lambda kv: -kv[1])}
        out["modules"][mod] = row
    scope_of = {}
    for mod in (maps or {}):
        for _, op, scope in scoped_ops(chip, mod, maps[mod])[0]:
            scope_of.setdefault(op, scope)
    out["device_ops"] = [[f"{op} {scope_of.get(op, '')}".strip(), s]
                         for op, s in tr.top_ops(10)]
    out["idle_gaps"] = tr.longest_gaps(10)
    out["idle_s_by_span"] = {k: v * 1e-9 for k, v in sorted(
        idle_by_span(tr).items(), key=lambda kv: -kv[1])}
    ns, n = idle_inside(tr, spans, "serve.tick")
    out["idle_ms_per_serve_tick"] = ns / n * 1e-6 if n else None
    return out


if __name__ == "__main__":
    maps = json.load(open(sys.argv[2])) if len(sys.argv) > 2 else None
    print(json.dumps(breakdown(load_profile(sys.argv[1]), maps), indent=1))
