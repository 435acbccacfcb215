"""Compiled-HLO analysis: collective-byte accounting, memory/cost capture,
and the program scope of each instruction.

``collective_bytes`` parses ``compiled.as_text()``, resolves every
collective's *operand* sizes (the payload each device injects), and splits
them into ICI (intra-pod) vs DCN (cross-pod) traffic by inspecting
replica_groups / source_target_pairs against the pod boundary.

``op_scopes`` maps each instruction of ``compiled.as_text()`` to the
innermost of the model's ``jax.named_scope`` names (:data:`SCOPES`) in its
``op_name`` metadata.  A profiler trace names a device op by its
instruction (``fusion.12``), so the map turns device time into time per
scope.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# the jax.named_scope names the serving path carries (models/layers.py,
# models/transformer.py, parallel/steps.py, core/hierarchical.py).
# ``kv_carry`` is how a layer reaches its part of the stacked cache: its
# slice out and back for a dense leaf, only the offset of its block table
# for a paged K/V pool.
SCOPES = ("kv_carry", "kv_write", "kv_gather", "attention", "qkv", "o_proj",
          "mlp", "norm", "embed", "head", "sample", "tp_all_reduce",
          "tp_reduce_scatter", "tp_all_gather")

# an instruction's name, its type and its opcode; a type is an array
# (`bf16[32,12288]`), with the layout a compiled text gives it
# (`{2,0,1:T(8,128)(2,1)S(1)}`), or a tuple of them
_DEF_RE = re.compile(r"(%?[\w.\-]+)\s*=\s*(\((?:[^()]|\([^()]*\))*\)|"
                     r"\w+\[[\d,]*\](?:\{[^}]*\})?)\s+([\w\-]+)\(")
_TYPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{(.*?)\}\}|replica_groups=\{(.*?)\}")
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)+)\}")


def _type_bytes(type_str: str) -> int:
    total = 0
    for m in _TYPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    ici_bytes: int = 0          # operand-sum convention (task spec)
    dcn_bytes: int = 0
    wire_ici_bytes: float = 0.0  # per-device wire traffic (ring model)
    wire_dcn_bytes: float = 0.0
    by_kind: Dict[str, int] = dataclasses.field(default_factory=dict)
    count: int = 0

    @property
    def total_bytes(self) -> int:
        return self.ici_bytes + self.dcn_bytes


def _group_size(line: str, n_devices: int) -> int:
    gm = re.search(r"replica_groups=\{\{(.*?)(?:\}|$)", line)
    if gm:
        return max(1, gm.group(1).count(",") + 1)
    gm = re.search(r"replica_groups=\[([\d,]+)\]<=\[(\d+)\]", line)
    if gm:
        dims = [int(x) for x in gm.group(1).split(",")]
        return max(1, dims[-1])
    if "source_target_pairs" in line:
        return 2
    return n_devices


def _wire_bytes(kind: str, operand_bytes: int, n: int) -> float:
    """Per-device wire traffic under the ring model.

    all-reduce: 2(n-1)/n * M;  reduce-scatter / all-to-all: (n-1)/n * M;
    all-gather: (n-1) * shard (operand IS the shard);
    collective-permute: M.
    """
    if n <= 1:
        return 0.0
    f = (n - 1) / n
    if kind == "all-reduce":
        return 2.0 * f * operand_bytes
    if kind == "all-gather":
        return (n - 1) * operand_bytes
    if kind == "collective-permute":
        return float(operand_bytes)
    return f * operand_bytes  # reduce-scatter, all-to-all


def _crosses_pod(line: str, pod_size: int) -> bool:
    """True if any communication edge crosses a pod boundary."""
    if pod_size <= 0:
        return False
    m = _PAIRS_RE.search(line)
    if m:
        pairs = re.findall(r"\{(\d+),(\d+)\}", m.group(1))
        return any(int(a) // pod_size != int(b) // pod_size
                   for a, b in pairs)
    gm = re.search(r"replica_groups=\{\{(.*?)\}\}", line)
    if gm:
        for grp in gm.group(1).split("},{"):
            ids = [int(x) for x in grp.split(",") if x.strip()]
            if ids and any(i // pod_size != ids[0] // pod_size
                           for i in ids):
                return True
        return False
    # iota/strided replica group formats: v2 "replica_groups=[2,256]<=[512]"
    gm = re.search(r"replica_groups=\[([\d,]+)\]<=\[(\d+)\]"
                   r"(?:T\(([\d,]+)\))?", line)
    if gm:
        dims = [int(x) for x in gm.group(1).split(",")]
        total = int(gm.group(2))
        # groups iterate the device range; a group spans pods when the
        # fastest-varying (within-group) extent crosses a pod boundary.
        group_size = dims[-1]
        # devices assigned contiguously (possibly transposed); conservative:
        if gm.group(3):  # transposed — groups stride across the range
            return group_size > 1 and total > pod_size
        return group_size > pod_size or (total > pod_size and
                                         group_size > pod_size)
    return False


def collective_bytes(hlo_text: str, n_devices: int,
                     n_pods: int = 1) -> CollectiveStats:
    """Sum collective operand bytes (per device) from compiled HLO text."""
    pod_size = n_devices // max(n_pods, 1)
    sizes: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.search(line)
        if m:
            sizes[m.group(1).lstrip("%")] = _type_bytes(m.group(2))
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = _DEF_RE.search(stripped)
        if not m:
            continue
        op = m.group(3)
        kind = None
        for c in _COLLECTIVES:
            if op == c or op.startswith(c + "-") or op == c + "-start":
                kind = c
                break
        if kind is None:
            continue
        if op.endswith("-done"):
            continue  # -start carries the operands
        # operand refs: everything inside the parens before the attributes
        paren = stripped[stripped.index(op) + len(op):]
        cut = paren.find("channel_id")
        if cut > 0:
            paren = paren[:cut]
        else:
            paren = paren.split(")", 1)[0]
        refs = [r.lstrip("%") for r in
                re.findall(r"%?[\w.\-]+", paren)]
        nbytes = sum(sizes.get(r, 0) for r in refs)
        if nbytes == 0:
            # fall back to the result type
            nbytes = _type_bytes(m.group(2))
        cross = n_pods > 1 and _crosses_pod(stripped, pod_size)
        wire = _wire_bytes(kind, nbytes, _group_size(stripped, n_devices))
        if cross:
            stats.dcn_bytes += nbytes
            stats.wire_dcn_bytes += wire
        else:
            stats.ici_bytes += nbytes
            stats.wire_ici_bytes += wire
        stats.by_kind[kind] = stats.by_kind.get(kind, 0) + nbytes
        stats.count += 1
    return stats


_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME_RE = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_MODULE_RE = re.compile(r"^HloModule\s+([^\s,]+)", re.M)


def module_name(hlo_text: str) -> str:
    """The module's jit name as a profiler trace gives it: ``jit_step``
    -> ``step``."""
    m = _MODULE_RE.search(hlo_text)
    name = m.group(1) if m else ""
    return name[4:] if name.startswith("jit_") else name


_NAME_RE = re.compile(r"%?[A-Za-z_][\w\-]*(?:\.[\w\-]+)*")
_DEBUG_SECTIONS = ("FileNames", "FunctionNames", "FileLocations",
                   "StackFrames")


def program_text(hlo_text: str, renumber: bool = False) -> str:
    """``hlo_text`` without what only names source places: each
    ``metadata={...}`` and the debug-info sections (``FileNames`` ...
    ``StackFrames``).  With ``renumber`` every numbered name (``%fusion.12``)
    becomes its order of first appearance, so two texts that differ only in
    how instructions were numbered compare equal."""
    out, skip = [], False
    for line in hlo_text.splitlines():
        if line in _DEBUG_SECTIONS:
            skip = True
            continue
        if skip and line.startswith(("%", "ENTRY")):
            skip = False
        if not skip:
            out.append(re.sub(r",? metadata=\{[^}]*\}", "", line))
    if renumber:
        ids: Dict[str, str] = {}

        def canon(m):
            t = m.group(0)
            return ids.setdefault(t, f"#{len(ids)}") \
                if re.search(r"\.\d", t) else t

        out = [_NAME_RE.sub(canon, line) for line in out]
    return "\n".join(out)


def op_scopes(hlo_text: str, scopes=SCOPES) -> Dict[str, str]:
    """{instruction name: scope} for every instruction whose ``op_name``
    holds one of ``scopes``; the innermost one counts."""
    wanted = set(scopes)
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        meta = _OP_NAME_RE.search(line)
        if not meta:
            continue
        for part in reversed(meta.group(1).split("/")):
            if part in wanted:
                out[m.group(1)] = part
                break
    return out


def summarize_compiled(compiled, mesh, lowered=None) -> Dict[str, object]:
    """memory_analysis + cost_analysis + collective stats for one
    executable.

    Collective bytes are parsed from the *lowered* (pre-optimization) HLO
    when available, because the CPU backend upcasts bf16 compute to f32
    during compilation, which would inflate payload sizes 2x; the lowered
    module carries the logical dtypes that real TPU lowering preserves.
    """
    n_dev = int(mesh.devices.size)
    n_pods = (mesh.devices.shape[0]
              if "pod" in mesh.axis_names else 1)
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):  # older jax returns [dict] per program
        ca = ca[0] if ca else {}
    if lowered is not None:
        txt = lowered.as_text(dialect="hlo")
    else:
        txt = compiled.as_text()
    coll = collective_bytes(txt, n_dev, n_pods)
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "ici_bytes": coll.ici_bytes,
        "dcn_bytes": coll.dcn_bytes,
        "wire_ici_bytes": coll.wire_ici_bytes,
        "wire_dcn_bytes": coll.wire_dcn_bytes,
        "collective_count": coll.count,
        "collectives_by_kind": coll.by_kind,
        "argument_bytes_per_device": ma.argument_size_in_bytes,
        "output_bytes_per_device": ma.output_size_in_bytes,
        "temp_bytes_per_device": ma.temp_size_in_bytes,
        "alias_bytes_per_device": ma.alias_size_in_bytes,
        "peak_bytes_per_device": (ma.argument_size_in_bytes
                                  + ma.output_size_in_bytes
                                  + ma.temp_size_in_bytes
                                  - ma.alias_size_in_bytes),
    }


__all__ = ["collective_bytes", "summarize_compiled", "CollectiveStats",
           "SCOPES", "module_name", "op_scopes", "program_text"]
