"""Post-SPMD HLO text analysis: collective link-traffic / FLOP extraction
with while-loop (lax.scan) trip-count multiplication, plus the entry-point
facts the graph-contract auditor (repro/analysis) reads off a compiled
executable: input->output donation aliases and per-parameter byte sizes.

XLA cost analysis counts while bodies once; for the roofline's collective
term we expand them: each ``while`` instruction's body contributes
``trip_count x`` its collectives, where the trip count is recovered from
the largest integer constant in the loop's condition computation (exact for
lax.scan-generated loops).  Nested whiles multiply recursively.  The same
walker scales ``dot`` FLOPs (``hlo_flops``).

Traffic model per collective (bytes crossing links, per device):
  all-gather          (g-1)/g x result_bytes
  all-reduce          2 (g-1)/g x bytes
  reduce-scatter      (g-1) x result_bytes      (operand = g x result)
  all-to-all          (g-1)/g x bytes
  collective-permute  bytes
"""
from __future__ import annotations

import re
from typing import Dict, Set

# any dtype token followed by a dims block; unknown dtypes (token[],
# opaque[], future float formats we have no entry for) are SKIPPED by
# shape_bytes instead of crashing the parse — an analysis pass must
# degrade, not die, on a new XLA type
_SHAPE_RE = re.compile(r"\b([a-z][0-9a-z]*)\[([0-9,]*)\]")
_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1, "f64": 8, "s64": 8, "c64": 8, "s16": 2,
          "u16": 2, "u64": 8, "c128": 16,
          # fp8 formats land as 1-byte elements
          "f8e4m3": 1, "f8e5m2": 1, "f8e4m3fn": 1, "f8e5m2fnuz": 1,
          "f8e4m3fnuz": 1, "f8e4m3b11fnuz": 1, "f8e3m4": 1}
_COLL_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _BYTES:
            continue                     # unknown dtype: contributes 0
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _BYTES[dt]
    return total


def shape_dims(shape_str: str):
    """First ``dtype[dims]`` in ``shape_str`` -> (dtype, [dims]) or None."""
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return None
    dims = [int(d) for d in m.group(2).split(",")] if m.group(2) else []
    return m.group(1), dims


def split_computations(hlo: str) -> Dict[str, str]:
    """Map computation name -> body text (brace-balanced blocks)."""
    comps = {}
    header = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)[^\n{]*\{", re.M)
    for m in header.finditer(hlo):
        name = m.group(1)
        depth = 0
        j = m.end() - 1
        while j < len(hlo):
            if hlo[j] == "{":
                depth += 1
            elif hlo[j] == "}":
                depth -= 1
                if depth == 0:
                    break
            j += 1
        comps[name] = hlo[m.start():j + 1]
    return comps


# operands of a compiled while are tuple-typed — ``while((s32[], f32[..])
# %tuple)`` — so the operand list itself contains parens; match lazily up
# to the ``condition=`` attribute instead of the first close-paren
_WHILE_RE = re.compile(
    r"while\(.*?\),\s*condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_CONST_RE = re.compile(r"[su]32\[\]\s+constant\((\d+)\)")
_KNOWN_TRIPS_RE = re.compile(r"known_trip_count[^0-9]*(\d+)")


def trip_count(cond_text: str, while_line: str = "") -> int:
    """Loop trip count: XLA's ``known_trip_count`` backend config on the
    while instruction when present (exact), else the largest integer
    constant in the condition computation (exact for lax.scan loops)."""
    km = _KNOWN_TRIPS_RE.search(while_line)
    if km:
        return int(km.group(1))
    consts = [int(c) for c in _CONST_RE.findall(cond_text)]
    return max(consts) if consts else 1


def _line_collectives(text: str):
    """Yield (kind, result_shape_bytes, group_size) for collectives in a
    computation body (skips -done halves of async pairs)."""
    for line in text.splitlines():
        for kind in _COLL_KINDS:
            token = f" {kind}("
            token_start = f" {kind}-start("
            if token in line or token_start in line:
                m = re.search(r"=\s*(\([^)]*\)|[\w\[\],{}\s]*?)\s*" +
                              kind.replace("-", r"\-") + r"(?:-start)?\(",
                              line)
                shape_str = m.group(1) if m else line.split("=")[0]
                b = shape_bytes(shape_str)
                gm = re.search(r"replica_groups=\{\{([0-9,]+)\}", line)
                if gm:
                    g = len(gm.group(1).split(","))
                else:
                    gm2 = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
                    g = int(gm2.group(2)) if gm2 else 2
                yield kind, b, max(g, 2)
                break


def _traffic(kind: str, b: float, g: int) -> float:
    if kind == "all-gather":
        return b * (g - 1) / g
    if kind == "all-reduce":
        return 2.0 * b * (g - 1) / g
    if kind == "reduce-scatter":
        return b * (g - 1)
    if kind == "all-to-all":
        return b * (g - 1) / g
    return float(b)   # collective-permute


def _walk_scaled(hlo: str, line_fn) -> Dict[str, float]:
    """Accumulate ``line_fn(computation_text) -> yields (key, value)``
    over the entry computation, multiplying while bodies by their trip
    count and recursing into call/fusion computations (memoized) — the
    scan expansion both ``collective_traffic`` and ``hlo_flops`` share."""
    comps = split_computations(hlo)
    em = re.search(r"^ENTRY\s+%?([\w.\-]+)", hlo, re.M)
    entry = em.group(1) if em else None
    memo: Dict[str, Dict[str, float]] = {}

    def walk(name: str) -> Dict[str, float]:
        if name in memo:
            return memo[name]
        memo[name] = {}          # cycle guard
        text = comps.get(name, "")
        acc: Dict[str, float] = {}
        for k, v in line_fn(text):
            acc[k] = acc.get(k, 0.0) + v
        for wm in _WHILE_RE.finditer(text):
            cond, body = wm.group(1), wm.group(2)
            line = text[text.rfind("\n", 0, wm.start()) + 1:
                        max(text.find("\n", wm.end()), wm.end())]
            n = trip_count(comps.get(cond, ""), line)
            sub = walk(body)
            for k, v in sub.items():
                acc[k] = acc.get(k, 0.0) + v * n
        # calls / fusions that might contain the lines of interest
        for cm in re.finditer(r"(?:call|fusion)\(.*?\).*?"
                              r"(?:to_apply|calls)=%?([\w.\-]+)", text):
            sub = walk(cm.group(1))
            for k, v in sub.items():
                acc[k] = acc.get(k, 0.0) + v
        # conditionals (lax.cond): sum over branches — an upper bound,
        # since only one branch executes per step
        for bm in re.finditer(
                r"conditional\(.*?\).*?(?:"
                r"branch_computations=\{([^}]*)\}|"
                r"true_computation=%?([\w.\-]+).*?"
                r"false_computation=%?([\w.\-]+))", text):
            if bm.group(1):
                branches = [b.strip().lstrip("%")
                            for b in bm.group(1).split(",")]
            else:
                branches = [bm.group(2), bm.group(3)]
            for br in branches:
                sub = walk(br)
                for k, v in sub.items():
                    acc[k] = acc.get(k, 0.0) + v
        memo[name] = acc
        return acc

    return walk(entry) if entry else {}


def collective_traffic(hlo: str) -> Dict[str, float]:
    """Per-device collective traffic (bytes) by kind, scan-expanded."""
    def lines(text):
        for kind, b, g in _line_collectives(text):
            yield kind, _traffic(kind, b, g)
            yield "_n_" + kind, 1

    result = _walk_scaled(hlo, lines)
    result["total"] = sum(v for k, v in result.items()
                          if not k.startswith("_n_"))
    return result


# --------------------------------------------------------------------------
# dot FLOPs (repro/analysis/cost_audit.py)
# --------------------------------------------------------------------------

_DOT_RE = re.compile(r"=\s*([^=]*?)\s+dot\(([^)]*)\)(.*)$")
_RHS_CONTRACT_RE = re.compile(r"rhs_contracting_dims=\{([0-9,]*)\}")
# ``%name = dtype[dims]{layout} op(`` — an instruction's result shape,
# used to resolve operands that newer XLA prints by name only
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*"
                     r"([a-z][0-9a-z]*\[[0-9,]*\])", re.M)


def _operand_dims(operand: str, defs: Dict[str, str]):
    """Dims of one dot operand: its inline shape when the text carries
    one (``f32[16,4]{1,0} %b``), else the shape of the instruction that
    defines it (``%b``; instruction names are unique in a module)."""
    got = shape_dims(operand)
    if got is None:
        name = (operand.split() or [""])[-1].lstrip("%")
        got = shape_dims(defs.get(name, ""))
    return got[1] if got is not None else None


def _line_dot_flops(text: str):
    """Yield ("dot_flops", flops) per dot: 2 x result elements x
    contracted extent (from the rhs operand's contracting dims)."""
    defs = {m.group(1): m.group(2) for m in _DEF_RE.finditer(text)}
    for line in text.splitlines():
        if " dot(" not in line:
            continue
        m = _DOT_RE.search(line)
        if not m:
            continue
        res = shape_dims(m.group(1))
        if res is None:
            continue
        n_out = 1
        for d in res[1]:
            n_out *= d
        # operands: lhs, rhs — with or without inline shapes
        ops = m.group(2).split(",") if "[" not in m.group(2) else \
            [s.group(0) for s in _SHAPE_RE.finditer(m.group(2))]
        k = 1
        cm = _RHS_CONTRACT_RE.search(m.group(3))
        rdims = _operand_dims(ops[1], defs) if len(ops) >= 2 else None
        if cm and rdims is not None and cm.group(1):
            for ci in cm.group(1).split(","):
                ci = int(ci)
                if 0 <= ci < len(rdims):
                    k *= rdims[ci]
        yield "dot_flops", 2.0 * n_out * k
        yield "_n_dot", 1


def hlo_flops(hlo: str) -> Dict[str, float]:
    """Scan-expanded matmul FLOPs of an HLO module: ``{"dot_flops",
    "_n_dot"}`` with while bodies multiplied by their trip counts —
    the static twin of ``cost_analysis()['flops']`` that works on text
    and never counts a loop body once (the XLA default this module
    exists to correct)."""
    out = _walk_scaled(hlo, _line_dot_flops)
    out.setdefault("dot_flops", 0.0)
    out.setdefault("_n_dot", 0)
    return out


# --------------------------------------------------------------------------
# entry-point facts for the donation / transfer audits (repro/analysis)
# --------------------------------------------------------------------------

_ALIAS_ENTRY_RE = re.compile(r"\{[0-9,\s]*\}:\s*\((\d+)")


def donated_params(hlo: str) -> Set[int]:
    """Flat entry-parameter indices the compiled module actually aliases
    input->output (``input_output_alias`` in the module header).  A
    ``donate_argnums`` argument MISSING from this set was silently
    copied instead of donated — the drop the donation audit exists to
    catch."""
    start = hlo.find("input_output_alias={")
    if start < 0:
        return set()
    i = hlo.index("{", start)
    depth, j = 0, i
    while j < len(hlo):                  # nested {out}: (...) entries
        if hlo[j] == "{":
            depth += 1
        elif hlo[j] == "}":
            depth -= 1
            if depth == 0:
                break
        j += 1
    return {int(p) for p in _ALIAS_ENTRY_RE.findall(hlo[i + 1:j])}


def entry_param_bytes(hlo: str) -> Dict[int, int]:
    """Byte size of each entry-computation parameter, by parameter
    index — the per-dispatch transfer surface a host-resident caller
    ships (minus donated/aliased buffers, which stay on device)."""
    comps = split_computations(hlo)
    em = re.search(r"^ENTRY\s+%?([\w.\-]+)", hlo, re.M)
    if not em:
        return {}
    out: Dict[int, int] = {}
    for line in comps.get(em.group(1), "").splitlines():
        pm = re.search(r"=\s*(.*?)\s*parameter\((\d+)\)", line)
        if pm:
            out[int(pm.group(2))] = shape_bytes(pm.group(1))
    return out
