"""Time of a module's collectives on one chip, and the part of it that no
other op hides, from a profiler trace and the program's op-scope maps.

The ops of a scope (`tp_all_reduce`) are those that
`ContinuousBatcher.op_scopes()` puts in it (`scopes.op_scopes`), found on
both the `XLA Ops` and the `Async XLA Ops` lines, so that an all-reduce
lowered as one synchronous op and one lowered as a start/done pair both
count.  Inside each run of the module the scope's time is the union of
its ops' intervals; the exposed part is what of that union no
synchronous op outside the scope overlaps.  Asynchronous ops outside the
scope (the copies that stream weights in beside every op) do no work of
the step's own and hide nothing: counting them would call a collective
hidden on a chip whose profile records them and exposed on one whose
profile does not.  The readers `allreduce_ms.decode` and
`allreduce_exposed_ms.decode` report both per run of the decode step.
"""
from __future__ import annotations

import bisect
import collections
from typing import Dict, List, Tuple

import scopes
import trace_reduce


def _overlap_ns(a: List[Tuple[float, float]],
                b: List[Tuple[float, float]]) -> float:
    """ns that two sorted, disjoint interval lists share."""
    total, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total


def scope_split_ns(chip, module: str, maps: List[Dict[str, str]],
                   scope: str) -> Tuple[float, float, int]:
    """(ns of ``scope``'s ops, ns of them with no synchronous op of
    another scope running, runs of ``module``) over the runs of
    ``module`` on ``chip``; each run is read with the map of the
    executable that knows most of its ops."""
    runs = [(s, e) for s, e, n in chip.modules if n == module]
    starts = [s for s, _ in runs]
    per_run = collections.defaultdict(list)
    for line, evs in ((True, chip.ops), (False, chip.async_ops)):
        for s, e, n in evs:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < runs[i][1]:
                per_run[i].append((s, min(e, runs[i][1]),
                                   scopes.instruction(n), line))
    total = exposed = 0.0
    for ops in per_run.values():
        names = {n for _, _, n, _ in ops}
        m = max(maps or [{}], key=lambda m: (len(names & m.keys()), -len(m)))
        mine = trace_reduce.union_ns(
            [(s, e) for s, e, n, _ in ops if m.get(n) == scope])
        other = trace_reduce.union_ns(
            [(s, e) for s, e, n, sync in ops if sync and m.get(n) != scope])
        busy = sum(e - s for s, e in mine)
        total += busy
        exposed += busy - _overlap_ns(mine, other)
    return total, exposed, len(runs)


def reader(module: str, scope: str, exposed: bool):
    """A per-layer reader: ms per run of ``module`` on the first chip of
    ``scope``'s ops, or with ``exposed`` of the part that no synchronous
    op of another scope hides.  Nothing to read without a trace, without
    scope maps, or where no executable of the module has an op in the
    scope (tp = 1)."""

    def read(run):
        if run.trace is None:
            return None
        maps = scopes.op_scopes(run) or {}
        if not any(scope in m.values() for m in maps.get(module, ())):
            return None
        total, alone, runs = scope_split_ns(run.trace.chips[0], module,
                                            maps[module], scope)
        if not runs:
            return None
        return (alone if exposed else total) / runs * 1e-6

    return read
