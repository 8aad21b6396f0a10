"""HVD7xx — resource/cost analysis rules over the compiled HLO.

The first three analysis tiers verify *correctness* (HVD1-4xx source,
HVD5xx IR, HVD6xx protocol); this family models *resources*: what the
compiled step will do to HBM before it ever touches a chip. From the
optimized HLO text of a real step function it computes, per top-level
instruction (fusion / dot / convolution / reduce / collective): bytes
read and written against HBM, flops, and the logical-vs-padded tile
footprint under the TPU (sublane x 128-lane) layout model — plus, via a
buffer-liveness pass over the scheduled entry computation, the peak
live per-device memory of the step. On top of the model, five rules:

- HVD701 padding amplification: a significant buffer whose padded tile
  bytes exceed its logical bytes by the threshold factor (the measured
  ResNet C=64 -> 128-lane 2x BN wall, reproduced statically).
- HVD702 projected per-device OOM: params + optimizer state +
  activations + collective/fusion buffers exceed the HBM budget — the
  model-scale gate that judges a multi-B-param config before any chip
  time.
- HVD703 re-streamed array: one HBM-resident intermediate read by >= N
  distinct non-overlapping fusions — the BN-wall signature (stats pass,
  normalize pass, backward passes) found by analysis, not a profiler.
- HVD704 large replicated optimizer state under a data-parallel mesh —
  the FSDP precursor finding.
- HVD705 roofline-vs-measured divergence: projected step time from the
  traffic/flop model and the committed SCALING.json rates vs the
  committed BENCH row — a drifted cost model fails loudly.

Like :mod:`rules_ir`, this module is stdlib-only: it takes HLO *text*
and plain dict/lists and never imports jax. Tracing/lowering/compiling
lives in :mod:`horovod_tpu.analysis.cost` (``hvd.cost_report``), the
only cost-tier code that needs the runtime installed. Semantics and the
calibration provenance of every rate live in docs/analysis.md.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from horovod_tpu.analysis.engine import Rule
from horovod_tpu.analysis.rules_ir import (
    _HLO_DTYPE_BYTES, HLO_COLLECTIVES, HLO_RESULT_TYPE, replica_group_size,
)


class CostRule(Rule):
    """Metadata carrier for an HVD7xx rule (the checks are driven by
    ``cost.cost_report``, not the per-file AST walk)."""

    def check_file(self, sf):
        return iter(())


class PaddingAmplification(CostRule):
    code = "HVD701"
    severity = "warning"
    summary = ("cost: buffer whose (sublane x 128-lane) tile-padded HBM "
               "footprint exceeds its logical bytes by the threshold "
               "factor — every pass over it streams the padding too "
               "(the measured C=64 -> 128-lane BN amplification)")


class ProjectedOom(CostRule):
    code = "HVD702"
    severity = "error"
    summary = ("cost: projected peak per-device memory (params + "
               "optimizer state + activations + collective/fusion "
               "buffers) exceeds the HBM budget for the mesh — the "
               "config cannot compile on the chip it is sized for")


class RestreamedArray(CostRule):
    code = "HVD703"
    severity = "warning"
    summary = ("cost: one HBM-resident intermediate read by >= N "
               "distinct non-overlapping fusions — multi-pass streaming "
               "of the same bytes (the ResNet BN-wall signature); "
               "remove traffic algorithmically or fuse the readers")


class ReplicatedState(CostRule):
    code = "HVD704"
    severity = "warning"
    summary = ("cost: large optimizer-state buffer replicated across a "
               "data-parallel mesh axis — every device holds the full "
               "copy (shard it over the data axis: the FSDP/ZeRO "
               "precursor finding)")


class RooflineDrift(CostRule):
    code = "HVD705"
    severity = "error"
    summary = ("cost: projected step time (bytes/flops roofline at the "
               "committed SCALING.json rates) diverges from the "
               "committed measured BENCH row beyond tolerance — the "
               "cost model or the measurement has drifted")


RULES = (PaddingAmplification(), ProjectedOom(), RestreamedArray(),
         ReplicatedState(), RooflineDrift())

RULES_BY_CODE = {r.code: r for r in RULES}


# ---------------------------------------------------------------------------
# TPU tile-padding model
# ---------------------------------------------------------------------------
#
# Vector memory moves (sublane, lane) = (S, 128) tiles where S scales
# inversely with element width so a tile stays 32 bytes deep per lane:
# 8 sublanes for 4-byte types, 16 for 2-byte, 32 for 1-byte. An array's
# last dim pads to a multiple of 128 lanes and its second-minor dim to a
# multiple of S; rank-1 arrays pad the lane dim only (XLA lays large
# flat buffers out linearly). PERF.md r3/r5: C=64 channels pad to 128
# lanes — 2x traffic on every BN pass, the measured reason the pure-BN
# Pallas kernel lost.

LANE = 128


def _itemsize(dtype: str) -> int:
    return _HLO_DTYPE_BYTES.get(dtype, 4)


def sublane(dtype: str) -> int:
    """Second-minor tile multiple for ``dtype``: 32 bytes per lane per
    tile row, floor 8 (f32 8, bf16 16, int8/fp8 32)."""
    return max(8, 32 // _itemsize(dtype))


def _round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult if n else 0


# Past this per-dim waste factor XLA's layout assignment relayouts or
# reshapes instead of paying tile padding (e.g. a huge s32[N,4] gather
# index buffer would be 32x under a naive minor-dim pad — no compiler
# keeps that layout); below it the padding is forced and real (conv
# layouts pin the feature dim minor, so C=64 -> 128 lanes IS a 2x,
# PERF.md r3).
RELAYOUT_FACTOR = 4.0


def padded_dims(dims: Tuple[int, ...], dtype: str) -> Tuple[int, ...]:
    if not dims:
        return dims
    if len(dims) >= 2 and dims[-1]:
        lane_factor = _round_up(dims[-1], LANE) / dims[-1]
        if lane_factor > RELAYOUT_FACTOR:
            # model the relayout: flat view, lane padding only
            return (_round_up(_prod(dims), LANE),)
    out = list(dims)
    out[-1] = _round_up(out[-1], LANE)
    if len(out) >= 2:
        out[-2] = _round_up(out[-2], sublane(dtype))
    return tuple(out)


def _prod(dims: Iterable[int]) -> int:
    n = 1
    for d in dims:
        n *= int(d)
    return n


def shape_bytes(dtype: str, dims: Tuple[int, ...]) -> int:
    return _prod(dims) * _itemsize(dtype)


def padded_bytes(dtype: str, dims: Tuple[int, ...]) -> int:
    return shape_bytes(dtype, padded_dims(dims, dtype))


# ---------------------------------------------------------------------------
# HLO text parsing (computations -> instructions)
# ---------------------------------------------------------------------------

_COMP_HEAD_RE = re.compile(
    r"^(ENTRY\s+)?%([\w.\-~]+)\s*\(.*\)\s*->\s*.*\{\s*$")
_INSTR_RE = re.compile(
    r"^\s*(ROOT\s+)?%([\w.\-~]+)\s+=\s+(" + HLO_RESULT_TYPE + r")\s+"
    r"([a-z][a-z0-9\-]*)\(")
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_OPERAND_RE = re.compile(r"%([\w.\-~]+)")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')

# Result/operand shapes never touch HBM through these: they rename or
# re-view an existing buffer, or are free scalars.
_ALIAS_OPS = frozenset((
    "parameter", "constant", "bitcast", "get-tuple-element", "tuple",
    "after-all", "partition-id", "replica-id", "iota", "rng-get-and-"
    "update-state", "opt-barrier",
))
# Callers whose interior computations are traversed separately — taking
# their operand/result bytes as traffic would double count.
_CALLER_OPS = frozenset(("call", "while", "conditional", "async-start",
                         "async-done", "async-update"))
# to_apply targets of these ops are scalar combiner lambdas (add/max),
# not real computations; fusion interiors (calls=) never touch HBM.
_APPLIER_OPS = frozenset(("reduce", "reduce-window", "all-reduce",
                          "all-reduce-start", "reduce-scatter", "scatter",
                          "select-and-scatter", "sort", "map"))
# Consumers that stream a buffer back out of HBM for HVD703 (reading it
# from a `while`/`call` is one logical pass of a traversed body, not an
# extra fusion over the bytes).
_STREAM_READERS = frozenset(("fusion", "reduce", "reduce-window",
                             "convolution", "dot"))

_COLLECTIVE_OPS = frozenset(HLO_COLLECTIVES) | frozenset(
    k + "-start" for k in HLO_COLLECTIVES)


@dataclasses.dataclass
class Instr:
    """One parsed HLO instruction of one computation."""
    name: str
    op: str
    index: int                        # position within the computation
    out: List[Tuple[str, Tuple[int, ...]]]
    operands: List[Tuple[str, Tuple[int, ...], str]]
    attrs: str                        # text after the operand list
    op_name: str
    is_root: bool

    def out_bytes(self) -> int:
        return sum(shape_bytes(d, s) for d, s in self.out)

    def out_padded(self) -> int:
        return sum(padded_bytes(d, s) for d, s in self.out)

    def read_bytes(self) -> int:
        return sum(shape_bytes(d, s) for d, s, _ in self.operands)

    def read_padded(self) -> int:
        return sum(padded_bytes(d, s) for d, s, _ in self.operands)


_SOURCE_TABLE_RE = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n"
    r"(?:.+\n)*", re.M)
_METADATA_RE = re.compile(r",? metadata=\{[^}]*\}")


def strip_source_info(hlo_text: str) -> str:
    """HLO text without the source-location tables and per-instruction
    ``metadata={...}`` — what identifies the executable. The tables
    record the Python call stack of the trace, so the same program built
    from two call sites prints two different texts."""
    return _METADATA_RE.sub("", _SOURCE_TABLE_RE.sub("", hlo_text))


def _dims(s: str) -> Tuple[int, ...]:
    return tuple(int(x) for x in s.split(",") if x)


def _operand_span(line: str, start: int) -> Tuple[str, str]:
    """Split ``line`` at the paren-balanced operand list opened at
    ``start`` (the index of the '('): returns (operand_text, attrs)."""
    depth = 0
    for i in range(start, len(line)):
        c = line[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return line[start + 1:i], line[i + 1:]
    return line[start + 1:], ""


def parse_computations(hlo_text: str) -> Tuple[Dict[str, List[Instr]], str]:
    """All computations of an HLO module as ordered instruction lists,
    plus the ENTRY computation's name. The module is scheduled
    (``is_scheduled=true`` on every compiled executable), so textual
    instruction order IS the execution schedule the liveness pass
    walks."""
    comps: Dict[str, List[Instr]] = {}
    entry = ""
    current: Optional[List[Instr]] = None
    # Operands are printed as bare ``%name`` references; their shapes come
    # from the producing instruction of the same computation (a scheduled
    # module defines before it uses).
    shapes: Dict[str, List[Tuple[str, Tuple[int, ...]]]] = {}
    for line in hlo_text.splitlines():
        head = _COMP_HEAD_RE.match(line)
        if head:
            current = comps.setdefault(head.group(2), [])
            shapes = {}
            if head.group(1):
                entry = head.group(2)
            continue
        if line.startswith("}"):
            current = None
            continue
        if current is None:
            continue
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        is_root, name, result, op = (bool(m.group(1)), m.group(2),
                                     m.group(3), m.group(4))
        out = [(d, _dims(s)) for d, s in _SHAPE_RE.findall(result)]
        if not result.startswith("("):
            # array-typed producers only: a tuple is consumed by alias
            # and caller ops, which move no bytes of their own
            shapes[name] = out
        opnd_text, attrs = _operand_span(line, m.end() - 1)
        operands = [shapes[n][0] + (n,)
                    for n in _OPERAND_RE.findall(opnd_text) if n in shapes]
        om = _OPNAME_RE.search(attrs)
        current.append(Instr(name, op, len(current), out, operands,
                             attrs, om.group(1) if om else "", is_root))
    return comps, entry


def _called_comps(instrs: Sequence[Instr], key: str) -> List[str]:
    out = []
    for ins in instrs:
        for m in re.finditer(key + r"=%([\w.\-~]+)", ins.attrs):
            out.append(m.group(1))
    return out


def traversed_computations(
        comps: Dict[str, List[Instr]], entry: str) -> List[str]:
    """The computations whose instructions are real schedule steps:
    ENTRY plus everything reachable through call/while/conditional
    bodies — NOT fusion interiors (calls=) or reduce combiner lambdas,
    whose instructions never touch HBM individually."""
    fused: set = set()
    for instrs in comps.values():
        for ins in instrs:
            if ins.op == "fusion":
                fused.update(_called_comps([ins], "calls"))
            if ins.op in _APPLIER_OPS:
                fused.update(_called_comps([ins], "to_apply"))
    seen: List[str] = []
    stack = [entry]
    while stack:
        name = stack.pop()
        if name in seen or name not in comps:
            continue
        seen.append(name)
        for ins in comps[name]:
            if ins.op in _CALLER_OPS or ins.op in ("custom-call",):
                for key in ("to_apply", "body", "condition", "calls",
                            "branch_computations"):
                    for c in _called_comps([ins], key):
                        if c not in fused:
                            stack.append(c)
    return seen


# ---------------------------------------------------------------------------
# per-instruction traffic/flop rows
# ---------------------------------------------------------------------------

def _dot_flops(ins: Instr) -> int:
    """2*M*N*K convention (one multiply + one add per MAC) — the same
    convention XLA's own cost analysis and PERF.md's realized-TF/s
    numbers use."""
    if not ins.operands:
        return 0
    lhs_dtype, lhs_dims, _ = ins.operands[0]
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", ins.attrs)
    contracting = _dims(m.group(1)) if m else ()
    k = _prod(lhs_dims[i] for i in contracting if i < len(lhs_dims)) \
        if contracting else (lhs_dims[-1] if lhs_dims else 1)
    out_elems = sum(_prod(s) for _, s in ins.out)
    return 2 * out_elems * k


def _conv_flops(ins: Instr) -> int:
    """2 * out_elements * (window * C_in / groups)."""
    m = re.search(r"window=\{size=([0-9x]+)", ins.attrs)
    window = _prod(int(x) for x in m.group(1).split("x")) if m else 1
    cin = 1
    dm = re.search(r"dim_labels=[^_]*_([0-9a-z]+)->", ins.attrs)
    if dm and len(ins.operands) >= 2:
        rhs_labels = dm.group(1)
        _, rhs_dims, _ = ins.operands[1]
        if "i" in rhs_labels and len(rhs_dims) == len(rhs_labels):
            cin = rhs_dims[rhs_labels.index("i")]
    gm = re.search(r"feature_group_count=(\d+)", ins.attrs)
    groups = int(gm.group(1)) if gm else 1
    out_elems = sum(_prod(s) for _, s in ins.out)
    return 2 * out_elems * window * max(1, cin // groups)


def _group_size(attrs: str) -> int:
    m = re.search(r"replica_groups=(\S+)", attrs)
    return replica_group_size(m.group(1)) if m else 1


def fusion_table(hlo_text: str,
                 dtype_scale: Optional[Dict[str, float]] = None,
                 ) -> Tuple[List[dict], dict]:
    """The cost model's instruction table: one row per HBM-touching
    top-level instruction across every traversed computation, with
    logical/padded read+write bytes, flops, and a roofline class —
    ``matmul`` (dot/convolution, and fusions whose interior carries
    one), ``collective``, or ``stream`` (everything bandwidth-bound:
    loop fusions, reduces, converts, copies).

    ``dtype_scale`` (e.g. ``{"f32": 0.5}`` when a bf16 step was
    legalized to f32 compute by the CPU backend) adds
    ``read_scaled``/``write_scaled`` per row — padded bytes at the
    declared on-chip width, which :func:`project_times` prefers.

    Loop bodies are counted ONCE per textual occurrence (HLO text does
    not carry trip counts); callers compare ``totals['flops']`` against
    the executable's own cost analysis and scale (see
    ``cost.cost_report``'s ``loop_scale``)."""
    scale = dtype_scale or {}

    def _scaled(shapes: Iterable[Tuple[str, Tuple[int, ...]]]) -> int:
        return int(sum(padded_bytes(d, s) * scale.get(d, 1.0)
                       for d, s in shapes))

    comps, entry = parse_computations(hlo_text)
    rows: List[dict] = []
    for comp in traversed_computations(comps, entry):
        for ins in comps[comp]:
            if ins.op in _ALIAS_OPS or ins.op in _CALLER_OPS:
                continue
            if ins.op.endswith("-done") or ins.op.endswith("-update"):
                continue
            if ins.op in _COLLECTIVE_OPS:
                klass = "collective"
                flops = 0
            elif ins.op in ("dot", "convolution"):
                klass = "matmul"
                flops = (_dot_flops(ins) if ins.op == "dot"
                         else _conv_flops(ins))
            elif ins.op == "fusion":
                called = _called_comps([ins], "calls")
                inner = [i for c in called for i in comps.get(c, ())
                         if i.op in ("dot", "convolution")]
                if inner:
                    klass = "matmul"
                    flops = sum(_dot_flops(i) if i.op == "dot"
                                else _conv_flops(i) for i in inner)
                else:
                    klass = "stream"
                    flops = sum(_prod(s) for _, s in ins.out)
            else:
                klass = "stream"
                flops = (ins.read_bytes() // max(1, _itemsize(
                    ins.operands[0][0])) if ins.op in
                    ("reduce", "reduce-window") and ins.operands
                    else sum(_prod(s) for _, s in ins.out))
            rows.append({
                "name": ins.name, "op": ins.op, "computation": comp,
                "class": klass, "flops": flops,
                "read_bytes": ins.read_bytes(),
                "read_padded": ins.read_padded(),
                "write_bytes": ins.out_bytes(),
                "write_padded": ins.out_padded(),
                "read_scaled": _scaled((d, s) for d, s, _ in ins.operands),
                "write_scaled": _scaled(ins.out),
                "group_size": (_group_size(ins.attrs)
                               if klass == "collective" else 0),
                "op_name": ins.op_name,
            })
    totals = {
        "flops": sum(r["flops"] for r in rows),
        "bytes_logical": sum(r["read_bytes"] + r["write_bytes"]
                             for r in rows),
        "bytes_padded": sum(r["read_padded"] + r["write_padded"]
                            for r in rows),
        "bytes_scaled": sum(r["read_scaled"] + r["write_scaled"]
                            for r in rows),
        "rows": len(rows),
    }
    return rows, totals


# ---------------------------------------------------------------------------
# buffer liveness over the scheduled entry computation
# ---------------------------------------------------------------------------

def liveness(instrs: Sequence[Instr],
             dtype_scale: Optional[Dict[str, float]] = None) -> dict:
    """Linear-scan liveness over one scheduled computation: every
    non-alias instruction result is live from its definition to its
    last textual use (the ROOT's operands to the end). Returns the peak
    transient bytes, where it happens, and the buffers live there.

    ``dtype_scale`` maps an HLO dtype to a byte-width correction factor
    (the driver passes ``{"f32": 0.5}`` when a bf16-declared step was
    legalized to f32 compute by the CPU backend, so transients are
    charged at their on-chip width).

    Parameters are excluded — argument memory is persistent and is
    accounted from the (exact) JAX-level shardings by the driver. Alias
    ops (bitcast/get-tuple-element/tuple) carry no bytes of their own.
    Reuse IS modeled (a dead buffer's bytes return to the pool), which
    is the same live-range model XLA's buffer assignment packs offsets
    from; what is NOT modeled is called-computation interiors, so a
    while-body's internal scratch is represented by its operand/result
    tuples only (documented in docs/analysis.md)."""
    sizes: Dict[str, int] = {}
    defined: Dict[str, int] = {}
    last_use: Dict[str, int] = {}
    n = len(instrs)
    for ins in instrs:
        if ins.op == "parameter":
            continue
        if ins.op in _ALIAS_OPS or ins.op in ("while", "conditional"):
            # while/conditional carries alias their operand tuples in
            # place (XLA buffer assignment updates the carry in situ);
            # the carried buffers are already live via last_use.
            sizes[ins.name] = 0
        else:
            scale = dtype_scale or {}
            sizes[ins.name] = int(sum(
                padded_bytes(d, s) * scale.get(d, 1.0) for d, s in ins.out))
        defined[ins.name] = ins.index
        for _, _, ref in ins.operands:
            if ref in defined:
                last_use[ref] = ins.index
        if ins.is_root:
            last_use[ins.name] = n - 1
    peak = live = 0
    peak_idx = 0
    expire: Dict[int, List[str]] = {}
    for name, idx in last_use.items():
        expire.setdefault(idx, []).append(name)
    live_set: Dict[str, int] = {}
    for ins in instrs:
        if ins.name in sizes:
            live += sizes[ins.name]
            live_set[ins.name] = sizes[ins.name]
        if live > peak:
            peak, peak_idx = live, ins.index
        for name in expire.get(ins.index, ()):
            live -= sizes.get(name, 0)
            live_set.pop(name, None)
    # second pass to capture the composition at the peak
    at_peak: List[Tuple[str, int]] = []
    live_set = {}
    for ins in instrs:
        if ins.name in sizes:
            live_set[ins.name] = sizes[ins.name]
        if ins.index == peak_idx:
            at_peak = sorted(live_set.items(), key=lambda kv: -kv[1])[:8]
            break
        for name in expire.get(ins.index, ()):
            live_set.pop(name, None)
    return {"peak_bytes": peak, "peak_index": peak_idx,
            "top_buffers": [{"name": k, "bytes": v} for k, v in at_peak]}


def restreamed(instrs: Sequence[Instr], min_bytes: int,
               min_reads: int) -> List[dict]:
    """HVD703 detector over one scheduled computation: intermediates
    (non-parameter results) above ``min_bytes`` padded, read back by
    >= ``min_reads`` distinct fusion-class consumers — each consumer is
    one full pass over the bytes (the BN chain: stats reduce, normalize
    fusion, backward reductions)."""
    produced: Dict[str, Instr] = {
        i.name: i for i in instrs
        if i.op not in _ALIAS_OPS and i.op != "parameter"
        and i.op not in _COLLECTIVE_OPS
        and any(len(s) >= 2 for _, s in i.out)}
    # rank-1 results (flat fused gradient buckets) and collective
    # results are read piecewise by the per-leaf apply fusions BY
    # DESIGN — that is the bucket mechanism, not the BN-wall multi-pass
    # signature, which lives on rank>=2 activation tensors.
    readers: Dict[str, List[str]] = {}
    for ins in instrs:
        if ins.op not in _STREAM_READERS:
            continue
        for _, _, ref in ins.operands:
            if ref in produced:
                lst = readers.setdefault(ref, [])
                if ins.name not in lst:
                    lst.append(ins.name)
    rows = []
    for name, consumers in readers.items():
        src = produced[name]
        nbytes = sum(padded_bytes(d, s) for d, s in src.out)
        if nbytes < min_bytes or len(consumers) < min_reads:
            continue
        rows.append({
            "name": name, "op": src.op,
            "shape": "/".join(f"{d}{list(s)}" for d, s in src.out),
            "bytes_padded": nbytes, "reads": len(consumers),
            "consumers": consumers[:8], "op_name": src.op_name,
        })
    rows.sort(key=lambda r: (-r["reads"] * r["bytes_padded"], r["name"]))
    return rows


# ---------------------------------------------------------------------------
# roofline projection
# ---------------------------------------------------------------------------

def project_times(rows: Sequence[dict], rates: Dict[str, float],
                  scale: float = 1.0) -> dict:
    """Projected per-class step time: matmul rows at
    max(flops/matmul_flop_s, padded bytes/hbm), stream rows
    bandwidth-bound at hbm_gb_s, collectives on a ring
    (2(n-1)/n * bytes / ici_gb_s). ``scale`` multiplies everything
    (the loop trip-count correction). Byte terms prefer the
    dtype-corrected ``read_scaled``/``write_scaled`` fields when
    :func:`fusion_table` produced them — EXCEPT collectives, whose wire
    payloads (f32 gradient buckets) are genuinely f32, not legalized."""
    hbm = float(rates["hbm_gb_s"]) * 1e9
    mxu = float(rates["matmul_flop_s"])
    ici = float(rates.get("ici_gb_s", 100.0)) * 1e9
    out = {k: {"ms": 0.0, "rows": 0, "bytes_padded": 0, "flops": 0}
           for k in ("matmul", "stream", "collective")}
    for r in rows:
        nbytes = (r.get("read_scaled", r["read_padded"])
                  + r.get("write_scaled", r["write_padded"]))
        if r["class"] == "matmul":
            t = max(r["flops"] / mxu, nbytes / hbm)
        elif r["class"] == "collective":
            n = max(1, r["group_size"])
            t = (2.0 * (n - 1) / n) * r["read_padded"] / ici
        else:
            t = nbytes / hbm
        c = out[r["class"]]
        c["ms"] += t * 1e3 * scale
        c["rows"] += 1
        c["bytes_padded"] += nbytes
        c["flops"] += r["flops"]
    total = sum(c["ms"] for c in out.values())
    for c in out.values():
        c["ms"] = round(c["ms"], 3)
    return {"classes": out, "total_ms": round(total, 3),
            "rates": dict(rates), "scale": round(scale, 4)}


# ---------------------------------------------------------------------------
# checks (driven by cost.cost_report; thresholds passed in from knobs)
# ---------------------------------------------------------------------------

def check_padding(rows: Sequence[dict], min_amplification: float,
                  min_waste_bytes: int) -> List[dict]:
    """HVD701: group significant rows by their dominant shape so one
    finding covers the 100 identical BN fusions it names."""
    groups: Dict[Tuple[str, float], dict] = {}
    for r in rows:
        if r["class"] == "collective":
            continue
        logical = r["read_bytes"] + r["write_bytes"]
        padded = r["read_padded"] + r["write_padded"]
        if not logical or padded - logical < min_waste_bytes:
            continue
        amp = padded / logical
        if amp < min_amplification:
            continue
        key = (r["op_name"].rsplit("/", 1)[-1] or r["op"],
               round(amp, 2))
        g = groups.setdefault(key, {"count": 0, "waste": 0,
                                    "example": r["name"]})
        g["count"] += 1
        g["waste"] += padded - logical
    problems = []
    for (label, amp), g in sorted(groups.items(),
                                  key=lambda kv: -kv[1]["waste"]):
        problems.append({
            "amplification": amp, "count": g["count"],
            "waste_bytes": g["waste"],
            "message": (
                f"{g['count']} instruction(s) ['{label}', e.g. "
                f"{g['example']}] stream {amp:.2f}x their logical bytes "
                f"({g['waste'] / 2 ** 20:.1f} MiB of tile padding per "
                f"step) — last-two-dims pad to (sublane x 128); pick "
                f"layout-friendly sizes or fold the padded axis "
                f"(PERF.md r3 lane-folded BN)"),
        })
    return problems


def _fmt_bytes(n: float) -> str:
    if n >= 2 ** 30:
        return f"{n / 2 ** 30:.2f} GiB"
    return f"{n / 2 ** 20:.1f} MiB"


def check_oom(accounting: Dict[str, Any],
              budget_bytes: int) -> List[dict]:
    """HVD702: projected peak per-device bytes vs the HBM budget."""
    peak = int(accounting["peak_bytes"])
    if peak <= budget_bytes:
        return []
    parts = ", ".join(
        f"{k.rsplit('_bytes', 1)[0]} {_fmt_bytes(accounting.get(k, 0))}"
        for k in ("params_bytes", "opt_state_bytes", "other_arg_bytes",
                  "transient_peak_bytes"))
    return [{
        "peak_bytes": peak, "budget_bytes": budget_bytes,
        "message": (
            f"projected peak per-device memory {_fmt_bytes(peak)} "
            f"exceeds the {_fmt_bytes(budget_bytes)} HBM budget "
            f"({parts}) — shard params/optimizer state over the data "
            f"axis (FSDP), remat activations, or grow the mesh"),
    }]


def check_restream(rows: Sequence[dict]) -> List[dict]:
    """HVD703: one problem per re-streamed buffer (already
    thresholded by :func:`restreamed`)."""
    problems = []
    for r in rows:
        problems.append({
            "buffer": r["name"], "reads": r["reads"],
            "bytes_padded": r["bytes_padded"],
            "message": (
                f"{r['shape']} intermediate '{r['name']}' "
                f"({r['bytes_padded'] / 2 ** 20:.1f} MiB padded) is "
                f"re-read from HBM by {r['reads']} non-overlapping "
                f"fusions ({', '.join(r['consumers'][:4])}"
                f"{', ...' if len(r['consumers']) > 4 else ''}) — "
                f"{r['reads']}x streaming of the same bytes; fuse the "
                f"readers or restructure to read once (the BN-wall "
                f"signature, PERF.md r2)"),
        })
    return problems


def check_replicated(leaves: Sequence[dict], min_bytes: int,
                     data_axes: Sequence[str]) -> List[dict]:
    """HVD704: optimizer-state leaves whose per-device bytes equal
    their logical bytes (fully replicated) on a mesh with a >1-sized
    data axis. ``leaves`` rows carry label/category/logical_bytes/
    per_device_bytes (built by the driver from the executable's input
    shardings — exact, not inferred)."""
    hits = [l for l in leaves
            if l.get("category") == "opt_state"
            and l["per_device_bytes"] >= l["logical_bytes"]
            and l["logical_bytes"] >= min_bytes]
    if not hits or not data_axes:
        return []
    total = sum(l["logical_bytes"] for l in hits)
    biggest = max(hits, key=lambda l: l["logical_bytes"])
    return [{
        "leaves": len(hits), "replicated_bytes": total,
        "message": (
            f"{len(hits)} optimizer-state leaf(s) totalling "
            f"{total / 2 ** 20:.0f} MiB are fully replicated across the "
            f"data axis {list(data_axes)} (largest: {biggest['label']} "
            f"{biggest['logical_bytes'] / 2 ** 20:.0f} MiB) — every "
            f"device pays the full copy; shard the optimizer state over "
            f"the data axis (ZeRO/FSDP) to cut it by the axis size"),
    }]


def check_roofline(projection: dict, measured_ms: float,
                   measured_source: str, tolerance: float) -> List[dict]:
    """HVD705: |projected/measured - 1| beyond tolerance."""
    proj = float(projection["total_ms"])
    if measured_ms <= 0:
        return []
    ratio = proj / measured_ms
    if abs(ratio - 1.0) <= tolerance:
        return []
    return [{
        "projected_ms": round(proj, 2), "measured_ms": measured_ms,
        "ratio": round(ratio, 3),
        "message": (
            f"projected step time {proj:.1f} ms is {ratio:.2f}x the "
            f"measured {measured_ms:.1f} ms ({measured_source}) — "
            f"beyond the {tolerance:.0%} tolerance: the cost-model "
            f"rates (SCALING.json cost_model_rates) or the committed "
            f"measurement have drifted; remeasure or recalibrate "
            f"before trusting HVD701-704 verdicts"),
    }]
