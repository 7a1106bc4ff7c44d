"""Post-SPMD HLO analysis: collective bytes per mesh axis + roofline terms.

``compiled.cost_analysis()`` reports FLOPs and HBM bytes but NOT collective
traffic, so we parse the partitioned HLO text: every all-reduce / all-gather /
reduce-scatter / all-to-all / collective-permute op contributes its tensor
bytes, attributed to the mesh axes its replica groups span (this is how we
separate the paper's client-axis traffic from tensor-parallel traffic).

Link-traffic factors (ring algorithms, N devices): all-reduce moves
2×(N-1)/N of its bytes over the busiest link; all-gather / reduce-scatter
(N-1)/N of the full tensor (a reduce-scatter's output is one shard of it);
all-to-all (N-1)/N; collective-permute 1×.
"""
from __future__ import annotations

import math
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import numpy as np

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_KIND_RE = re.compile(
    r"(?<!%)\b(?P<kind>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<start>-start)?(?P<done>-done)?(?:\.\d+)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{(.*?)\}\s*[,)]")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _first_group(line: str, n_devices: int) -> Optional[List[int]]:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        g, s = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            perm = [int(x) for x in m.group(4).split(",")]
            ids = ids.transpose(perm)
        ids = ids.reshape(g, s)
        return list(ids[0])
    m = _GROUPS_LIST_RE.search(line)
    if m:
        first = m.group(1).split("},{")[0].strip("{}")
        if not first:
            return None
        return [int(x) for x in first.split(",") if x.strip()]
    m = re.search(r"source_target_pairs=\{\{(\d+),(\d+)\}", line)
    if m:  # collective-permute: attribute by its first (src, dst) pair
        return [int(m.group(1)), int(m.group(2))]
    return None


def _axes_of_group(group: List[int], mesh_shape: Dict[str, int]) -> Tuple[str, ...]:
    """Which mesh axes vary within a replica group (device-id major order =
    mesh axis order, matching jax.make_mesh's default device assignment)."""
    names = list(mesh_shape)
    sizes = [mesh_shape[n] for n in names]
    strides = {}
    acc = 1
    for n, s in zip(reversed(names), reversed(sizes)):
        strides[n] = acc
        acc *= s
    coords = []
    for d in group:
        c = {}
        for n in names:
            c[n] = (d // strides[n]) % mesh_shape[n]
        coords.append(c)
    varying = tuple(n for n in names
                    if len({c[n] for c in coords}) > 1)
    return varying


def parse_collectives(hlo_text: str, mesh_shape: Dict[str, int]) -> List[dict]:
    """Per-collective {kind, bytes, link_bytes, axes} from partitioned HLO."""
    n_devices = math.prod(mesh_shape.values())
    out = []
    for line in hlo_text.splitlines():
        if " = " not in line:
            continue
        m = _KIND_RE.search(line)
        if not m or m.group("done"):
            continue
        kind = m.group("kind")
        # output type(s) = everything between '=' and the op keyword;
        # covers scalar and tuple-typed (variadic) collectives.
        outtype = line.split(" = ", 1)[1][: m.start() - line.index(" = ") - 3]
        nbytes = _shape_bytes(outtype)
        group = _first_group(line, n_devices)
        axes = _axes_of_group(group, mesh_shape) if group else ("unknown",)
        n = len(group) if group else 1
        if kind == "reduce-scatter":
            nbytes *= n   # its output is one shard; the full tensor is n
        if kind == "all-reduce":
            link = 2.0 * nbytes * (n - 1) / max(n, 1)
        elif kind in ("all-gather", "reduce-scatter"):
            link = nbytes * (n - 1) / max(n, 1)
        elif kind == "all-to-all":
            link = nbytes * (n - 1) / max(n, 1)
        else:
            link = float(nbytes)
        out.append({"kind": kind, "bytes": nbytes, "link_bytes": link,
                    "group_size": n, "axes": list(axes)})
    return out


# ---------------------------------------------------------------------------
# Loop-aware accounting.
#
# XLA's cost analysis (and a naive text scan) counts a while-loop body ONCE,
# but jax.lax.scan bodies execute trip-count times — layer stacks, microbatch
# accumulation and q-chunked attention all live in scans here. We therefore
# walk the HLO call graph: split the module into computations, find `while`
# ops, recover the trip count from the loop condition's comparison constant,
# and multiply everything inside by the product of enclosing trip counts.
# ---------------------------------------------------------------------------

_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*(?:\([^)]*\))?\s*->.*\{\s*$")
_WHILE_RE = re.compile(
    r"while\(.*?\)?.*?condition=%?([\w\.\-]+),\s*body=%?([\w\.\-]+)")
_CALL_RE = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w\.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _split_computations(hlo_text: str):
    comps: Dict[str, List[str]] = {}
    cur = None
    entry_name = None
    for line in hlo_text.splitlines():
        stripped = line.strip()
        is_header = (line and not line[0].isspace() and stripped.endswith("{")
                     and not line.startswith("HloModule"))
        if is_header:
            toks = stripped.split()
            is_entry = toks[0] == "ENTRY"
            name_tok = toks[1] if is_entry else toks[0]
            cur = name_tok.lstrip("%").split("(")[0].rstrip(",")
            comps[cur] = []
            if is_entry:
                entry_name = cur
            continue
        if stripped == "}":
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line)
    return comps, entry_name


def _trip_count(cond_lines: List[str]) -> int:
    consts = [int(c) for l in cond_lines for c in _CONST_RE.findall(l)]
    return max(consts) if consts else 1


def parse_collectives_nested(hlo_text: str, mesh_shape: Dict[str, int]
                             ) -> List[dict]:
    """Like parse_collectives but weighted by enclosing scan trip counts."""
    comps, entry = _split_computations(hlo_text)
    if entry is None:
        return parse_collectives(hlo_text, mesh_shape)

    multiplier: Dict[str, float] = {}

    def visit(name: str, mult: float):
        if name not in comps:
            return
        if multiplier.get(name, 0.0) >= mult:
            return  # already visited at >= multiplicity
        multiplier[name] = mult
        for line in comps[name]:
            wm = _WHILE_RE.search(line)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                n = _trip_count(comps.get(cond, []))
                visit(body, mult * n)
                visit(cond, mult * n)
            else:
                for callee in _CALL_RE.findall(line):
                    if callee in comps and callee != name:
                        visit(callee, mult)

    visit(entry, 1.0)

    out = []
    for cname, lines in comps.items():
        mult = multiplier.get(cname)
        if mult is None:
            continue
        for c in _collectives_in_lines(lines, mesh_shape):
            c = dict(c)
            c["bytes"] *= mult
            c["link_bytes"] *= mult
            c["trip_mult"] = mult
            out.append(c)
    return out


def _collectives_in_lines(lines: List[str], mesh_shape: Dict[str, int]):
    return parse_collectives("\n".join(lines), mesh_shape)


def collective_summary(colls: List[dict]) -> dict:
    by_axes = defaultdict(float)
    by_kind = defaultdict(float)
    for c in colls:
        by_axes["+".join(c["axes"]) or "none"] += c["link_bytes"]
        by_kind[c["kind"]] += c["link_bytes"]
    return {"total_link_bytes": sum(c["link_bytes"] for c in colls),
            "count": len(colls),
            "by_axes": dict(by_axes), "by_kind": dict(by_kind)}


def memory_summary(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
        alias = getattr(ma, "alias_size_in_bytes", 0) or 0
        return {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
            "output_bytes": getattr(ma, "output_size_in_bytes", None),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            "alias_bytes": alias,  # donated buffers (in-place update)
            "peak_bytes": (getattr(ma, "argument_size_in_bytes", 0) or 0)
                          + (getattr(ma, "temp_size_in_bytes", 0) or 0)
                          + (getattr(ma, "output_size_in_bytes", 0) or 0)
                          - alias,
        }
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}


def cost_summary(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return {"flops": float(ca.get("flops", 0.0)),
                "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
                "transcendentals": float(ca.get("transcendentals", 0.0))}
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
