"""HVD5xx — IR-tier verification rules over the traced jaxpr and the
compiled (optimized) HLO of a real step function.

PR 4's AST rules catch distributed-correctness bugs in *source*; this
family catches the ones that only exist in what XLA actually compiles: a
gradient leaf whose allreduce was dropped (HVD501), an all-gather the
GSPMD partitioner inserted because a sharding annotation is wrong
(HVD502), controllers compiling different collective orders (HVD503 —
the deadlock class Horovod's tensor-negotiation protocol exists for,
proven at build time instead of hung at step 40,000), donated buffers
the executable did not alias (HVD504), and reductions silently executing
in bf16 over f32 leaves (HVD505).

This module is analysis-only and stdlib-only like its AST siblings: the
functions take already-traced jaxpr objects (duck-typed — ``eqn.
primitive.name`` / ``eqn.params`` / ``var.aval``) and HLO text; they
never import jax. Tracing/lowering/compiling lives in
:mod:`horovod_tpu.analysis.ir` (``verify_step``), which is the only part
of the analysis package that needs the runtime installed.
"""

from __future__ import annotations

import hashlib
import json
import re
from typing import Any, Dict, FrozenSet, Iterable, List, Optional, Sequence, Tuple

from horovod_tpu.analysis.engine import Rule


class IrRule(Rule):
    """Metadata carrier for an HVD5xx rule (the checks are driven by
    ``ir.verify_step``, not the per-file AST walk)."""

    def check_file(self, sf):
        return iter(())


class UnreducedGradient(IrRule):
    code = "HVD501"
    severity = "error"
    summary = ("IR: shard_map output declared replicated over a mesh axis "
               "but derived from that axis's sharded data with no "
               "psum/reduce on the path (unreduced gradient)")


class ImplicitResharding(IrRule):
    code = "HVD502"
    severity = "error"
    summary = ("IR: all-gather/collective-permute/all-to-all in the "
               "optimized HLO above the byte threshold and not accounted "
               "for by the expected-collectives manifest (implicit GSPMD "
               "resharding — check pjit sharding annotations)")


class CollectiveOrderDivergence(IrRule):
    code = "HVD503"
    severity = "error"
    summary = ("IR: compiled collective order (op kind, shape, dtype, "
               "replica_groups fingerprint) differs across controllers or "
               "across recompiles of the same signature — the multi-host "
               "deadlock class, caught at build time")


class DonationMiss(IrRule):
    code = "HVD504"
    severity = "warning"
    summary = ("IR: donated buffer the executable did not alias, or a "
               "state-shaped argument never donated at all — params/opt "
               "state held twice in HBM")


class ReductionDtypeDrift(IrRule):
    code = "HVD505"
    severity = "warning"
    summary = ("IR: reduction executing in bf16/f16 over values converted "
               "down from f32 with no compression asked for — silent "
               "gradient precision loss on the wire")


RULES = (UnreducedGradient(), ImplicitResharding(),
         CollectiveOrderDivergence(), DonationMiss(), ReductionDtypeDrift())

RULES_BY_CODE = {r.code: r for r in RULES}


# ---------------------------------------------------------------------------
# HVD501 — replication-taint analysis over shard_map bodies
# ---------------------------------------------------------------------------
#
# Inside a shard_map body every value carries a "taint": the set of mesh
# axes along which its per-shard value may DIFFER. Inputs sharded over an
# axis (in_specs) seed taint; axis_index introduces taint; reduction
# collectives over an axis clear it; everything else unions its operands.
# A body output whose out_specs do NOT shard it over axis A claims it is
# replicated over A — if its taint still contains A, some data path from
# A-sharded inputs reached it without a psum: on a gradient leaf that is
# exactly the dropped allreduce.

Taint = FrozenSet[str]
_EMPTY: Taint = frozenset()

# Reductions/gathers that make their result agree across the named axes.
_CLEARING_PRIMS = {"psum", "pmax", "pmin", "all_gather"}
# reduce-scatter leaves each shard a distinct PIECE of the full
# reduction: the data is reduced (the HVD501 property) even though the
# value is sharded, so it clears like psum per the rule's contract.
_CLEARING_PRIMS |= {"reduce_scatter", "psum_scatter"}


def _prim_axes(params: Dict[str, Any]) -> Tuple[str, ...]:
    axes = params.get("axes")
    if axes is None:
        axes = params.get("axis_name")
    if axes is None:
        return ()
    if isinstance(axes, (tuple, list)):
        return tuple(a for a in axes if isinstance(a, str))
    return (axes,) if isinstance(axes, str) else ()


def _is_jaxprish(obj: Any) -> bool:
    return hasattr(obj, "eqns") and hasattr(obj, "invars")


def _open(jaxpr: Any) -> Any:
    """ClosedJaxpr -> Jaxpr (duck-typed; plain Jaxpr passes through)."""
    return getattr(jaxpr, "jaxpr", jaxpr)


def _taint_eqn(eqn: Any, in_taints: List[Taint]) -> List[Taint]:
    name = eqn.primitive.name
    union: Taint = frozenset().union(*in_taints) if in_taints else _EMPTY
    n_out = len(eqn.outvars)

    if name in _CLEARING_PRIMS:
        if eqn.params.get("axis_index_groups") is not None:
            # subgroup reduce: cross-group variation survives — keep taint
            return [union] * n_out
        cleared = union - set(_prim_axes(eqn.params))
        return [cleared] * n_out
    if name == "axis_index":
        ax = eqn.params.get("axis_name")
        extra = set(ax) if isinstance(ax, (tuple, list)) else {ax}
        return [union | frozenset(a for a in extra if a)] * n_out
    if name == "optimization_barrier" and len(in_taints) == n_out:
        return list(in_taints)

    if name == "scan":
        return _taint_scan(eqn, in_taints)
    if name == "while":
        return _taint_while(eqn, in_taints)
    if name == "cond":
        return _taint_cond(eqn, in_taints)
    if name in ("pjit", "closed_call", "core_call", "remat", "checkpoint",
                "remat_call", "custom_jvp_call", "custom_vjp_call",
                "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr"):
        sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        if sub is not None and len(_open(sub).invars) == len(in_taints):
            outs = _taint_jaxpr(_open(sub), in_taints)
            if len(outs) >= n_out:
                return outs[:n_out]
        return [union] * n_out

    # Unknown primitive with embedded jaxprs (vmap'd custom ops, ...):
    # conservative union keeps soundness (may over-taint, never under).
    return [union] * n_out


def _taint_scan(eqn: Any, in_taints: List[Taint]) -> List[Taint]:
    body = _open(eqn.params["jaxpr"])
    n_consts = int(eqn.params.get("num_consts", 0))
    n_carry = int(eqn.params.get("num_carry", 0))
    consts = list(in_taints[:n_consts])
    carry = list(in_taints[n_consts:n_consts + n_carry])
    xs = list(in_taints[n_consts + n_carry:])
    for _ in range(16):             # fixpoint: taints only grow, few axes
        outs = _taint_jaxpr(body, consts + carry + xs)
        new_carry = [c | o for c, o in zip(carry, outs[:n_carry])]
        if new_carry == carry:
            break
        carry = new_carry
    outs = _taint_jaxpr(body, consts + carry + xs)
    return carry + outs[n_carry:]


def _taint_while(eqn: Any, in_taints: List[Taint]) -> List[Taint]:
    cn = int(eqn.params.get("cond_nconsts", 0))
    bn = int(eqn.params.get("body_nconsts", 0))
    cond = _open(eqn.params["cond_jaxpr"])
    body = _open(eqn.params["body_jaxpr"])
    cond_consts = list(in_taints[:cn])
    body_consts = list(in_taints[cn:cn + bn])
    carry = list(in_taints[cn + bn:])
    for _ in range(16):
        pred = _taint_jaxpr(cond, cond_consts + carry)
        pred_t = pred[0] if pred else _EMPTY
        outs = _taint_jaxpr(body, body_consts + carry)
        new_carry = [c | o | pred_t for c, o in zip(carry, outs)]
        if new_carry == carry:
            break
        carry = new_carry
    return carry


def _taint_cond(eqn: Any, in_taints: List[Taint]) -> List[Taint]:
    pred_t = in_taints[0] if in_taints else _EMPTY
    ops = in_taints[1:]
    branch_outs = []
    for br in eqn.params.get("branches", ()):
        b = _open(br)
        if len(b.invars) == len(ops):
            branch_outs.append(_taint_jaxpr(b, ops))
    n_out = len(eqn.outvars)
    if not branch_outs:
        u = frozenset().union(*in_taints) if in_taints else _EMPTY
        return [u] * n_out
    outs = []
    for i in range(n_out):
        t = pred_t
        for bo in branch_outs:
            if i < len(bo):
                t = t | bo[i]
        outs.append(t)
    return outs


def _taint_jaxpr(jaxpr: Any, in_taints: List[Taint]) -> List[Taint]:
    env: Dict[Any, Taint] = {}

    def read(v: Any) -> Taint:
        if hasattr(v, "val"):       # Literal
            return _EMPTY
        return env.get(v, _EMPTY)

    for v, t in zip(jaxpr.invars, in_taints):
        env[v] = t
    for eqn in jaxpr.eqns:
        outs = _taint_eqn(eqn, [read(v) for v in eqn.invars])
        for v, t in zip(eqn.outvars, outs):
            env[v] = t
    return [read(v) for v in jaxpr.outvars]


def _spec_axes(spec: Any) -> Taint:
    """PartitionSpec -> the set of axes the value is sharded over (an
    entry is None, one axis name, or a tuple of names)."""
    out = set()
    for axes in spec:
        for a in (axes if isinstance(axes, (tuple, list)) else (axes,)):
            if isinstance(a, str):
                out.add(a)
    return frozenset(out)


def _iter_all_eqns(jaxpr: Any) -> Iterable[Any]:
    """Every eqn of the jaxpr and all reachable sub-jaxprs."""
    stack = [_open(jaxpr)]
    seen = set()
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        for eqn in j.eqns:
            yield eqn
            for val in eqn.params.values():
                for sub in (val if isinstance(val, (tuple, list))
                            else (val,)):
                    if _is_jaxprish(_open(sub)):
                        stack.append(_open(sub))


def check_unreduced(jaxpr: Any) -> List[dict]:
    """HVD501 problems for every shard_map eqn reachable in ``jaxpr``.

    Returns dicts with ``out_index``, ``aval`` (short type string),
    ``axes`` (the replication-declared axes the value still varies
    over), and ``message``.
    """
    problems: List[dict] = []
    for eqn in _iter_all_eqns(jaxpr):
        if eqn.primitive.name != "shard_map":
            continue
        mesh = eqn.params.get("mesh")
        axis_names = tuple(getattr(mesh, "axis_names", ()) or ())
        # mesh axes the body does not handle manually stay the
        # partitioner's business
        auto = set(axis_names) - set(eqn.params["manual_axes"])
        body = _open(eqn.params.get("jaxpr"))
        if body is None or not axis_names:
            continue
        in_taints = [_spec_axes(s) for s in eqn.params["in_specs"]]
        out_taints = _taint_jaxpr(body, in_taints)
        for i, (spec, taint) in enumerate(zip(eqn.params["out_specs"],
                                              out_taints)):
            allowed = _spec_axes(spec) | auto
            bad = sorted(taint & (set(axis_names) - allowed))
            if not bad:
                continue
            aval = str(getattr(eqn.outvars[i], "aval", "?"))
            axes_s = "/".join(bad)
            problems.append({
                "out_index": i, "aval": aval, "axes": bad,
                "message": (
                    f"shard_map output #{i} ({aval}) is declared replicated "
                    f"over mesh axis {axes_s!r} but is derived from "
                    f"{axes_s!r}-sharded data with no psum/reduce-scatter "
                    f"over {axes_s!r} on the path — an unreduced gradient "
                    f"(or rank-dependent value) leaves the shard_map as if "
                    f"it were replica-identical"),
            })
    return problems


# ---------------------------------------------------------------------------
# HVD505 — reduction dtype drift (convert f32->bf16 feeding a psum)
# ---------------------------------------------------------------------------

_WIDE_FLOATS = {"float32", "float64"}
_NARROW_FLOATS = {"bfloat16", "float16",
                  "float8_e4m3fn", "float8_e5m2"}
# Pure data movement between the convert and the reduce: chase through
# these (the fusion pack — ravel/concat — sits between compression's
# convert and the fused psum).
_TRANSPARENT_PRIMS = {
    "reshape", "concatenate", "transpose", "squeeze", "broadcast_in_dim",
    "slice", "dynamic_slice", "dynamic_update_slice", "copy", "rev",
    "optimization_barrier", "convert_element_type_noop",
}


def _dtype_name(var: Any) -> str:
    aval = getattr(var, "aval", None)
    return str(getattr(aval, "dtype", ""))


def check_reduction_dtype(jaxpr: Any,
                          allowed_narrow: Iterable[str] = ()) -> List[dict]:
    """HVD505: psum/reduce-scatter whose operand reaches back through
    pure data movement to a convert_element_type narrowing f32/f64 to
    bf16/f16/fp8.

    ``allowed_narrow``: dtype names the caller DECLARED as intended wire
    compression (the manifest's ``wire_dtype`` —
    ops/fusion.expected_manifest). Reductions executing in exactly those
    dtypes stay quiet; a stray cast to any OTHER narrow dtype still
    trips, so a declared-bf16 run cannot silently ship fp8 (or vice
    versa)."""
    allowed = {str(a) for a in allowed_narrow}
    problems: List[dict] = []
    stack = [_open(jaxpr)]
    seen_j = set()
    while stack:
        j = stack.pop()
        if id(j) in seen_j:
            continue
        seen_j.add(id(j))
        defs: Dict[Any, Any] = {}
        for eqn in j.eqns:
            for v in eqn.outvars:
                defs[v] = eqn
            for val in eqn.params.values():
                for sub in (val if isinstance(val, (tuple, list))
                            else (val,)):
                    if _is_jaxprish(_open(sub)):
                        stack.append(_open(sub))
        for eqn in j.eqns:
            if eqn.primitive.name not in ("psum", "reduce_scatter",
                                          "psum_scatter"):
                continue
            for op in eqn.invars:
                if _dtype_name(op) not in _NARROW_FLOATS:
                    continue
                if _dtype_name(op) in allowed:
                    continue             # declared wire compression
                conv = _chase_to_convert(op, defs)
                if conv is None:
                    continue
                src_dtype = _dtype_name(conv.invars[0])
                problems.append({
                    "axes": list(_prim_axes(eqn.params)),
                    "message": (
                        f"{eqn.primitive.name} over axes "
                        f"{_prim_axes(eqn.params)!r} executes in "
                        f"{_dtype_name(op)} on values converted down from "
                        f"{src_dtype} immediately before the reduce — "
                        f"gradient bits are dropped on the wire; if this "
                        f"is intended wire compression, say so via "
                        f"verify_step(expect_compression=True) or a "
                        f"suppression"),
                })
    return problems


def _chase_to_convert(var: Any, defs: Dict[Any, Any],
                      limit: int = 64) -> Optional[Any]:
    """Follow ``var`` back through pure data movement; return the
    narrowing convert_element_type eqn feeding it, else None."""
    frontier = [var]
    for _ in range(limit):
        if not frontier:
            return None
        v = frontier.pop()
        eqn = defs.get(v)
        if eqn is None:
            continue
        name = eqn.primitive.name
        if name == "convert_element_type":
            if (_dtype_name(eqn.invars[0]) in _WIDE_FLOATS
                    and _dtype_name(eqn.outvars[0]) in _NARROW_FLOATS):
                return eqn
            continue
        if name in _TRANSPARENT_PRIMS:
            frontier.extend(x for x in eqn.invars if not hasattr(x, "val"))
    return None


def reduction_dtypes(jaxpr: Any) -> List[dict]:
    """Every psum/reduce-scatter in the traced jaxpr with its operand
    dtype and element count — the platform-independent wire-dtype
    evidence (the OPTIMIZED HLO is not: XLA's float-normalization pass
    upcasts narrow all-reduces on backends without native support, e.g.
    bf16->f32 on CPU, so the compressed-wire structural assert reads the
    traced IR for exact dtypes and the optimized HLO only for the
    no-wide-collective property)."""
    rows: List[dict] = []
    for eqn in _iter_all_eqns(jaxpr):
        # pmax/pmin included: the fp8 wire's per-bucket amax scale
        # exchange is a scalar pmax — part of the wire evidence.
        if eqn.primitive.name not in ("psum", "reduce_scatter",
                                      "psum_scatter", "pmax", "pmin"):
            continue
        for op in eqn.invars:
            aval = getattr(op, "aval", None)
            size = 1
            for d in (getattr(aval, "shape", ()) or ()):
                size *= int(d)
            rows.append({"prim": eqn.primitive.name,
                         "dtype": _dtype_name(op),
                         "size": size,
                         "axes": list(_prim_axes(eqn.params))})
    return rows


# ---------------------------------------------------------------------------
# optimized-HLO parsing (HVD502 / HVD503)
# ---------------------------------------------------------------------------

_HLO_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}
_HLO_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
# An instruction's result type: one shape or a tuple of shapes. TPU layouts
# nest one more level of parentheses inside it (``{1,0:T(8,128)S(1)}``).
HLO_RESULT_TYPE = r"(?:\((?:[^()]|\([^()]*\))*\)|\S+)"
_HLO_OP_RE = re.compile(r"=\s*(" + HLO_RESULT_TYPE + r")\s+([a-z\-]+)\(")

HLO_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                   "collective-permute", "all-to-all", "collective-broadcast")
# The kinds HVD502 treats as resharding suspects when unaccounted for.
RESHARD_KINDS = ("all-gather", "collective-permute", "all-to-all")


def _hlo_shape_sizes(typestr: str) -> List[int]:
    sizes = []
    for dtype, dims in _HLO_SHAPE_RE.findall(typestr):
        if dtype not in _HLO_DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        sizes.append(n * _HLO_DTYPE_BYTES[dtype])
    return sizes


def _hlo_shape_bytes(typestr: str) -> int:
    return sum(_hlo_shape_sizes(typestr))


def hlo_collectives(hlo_text: str) -> List[dict]:
    """Ordered collective ops of an (optimized) HLO module: one entry per
    op with kind, result shape/bytes, replica_groups, and the traced
    op_name metadata when present. Async pairs count their ``-start``
    (the ``-done`` moves no new data)."""
    entries: List[dict] = []
    for lineno, line in enumerate(hlo_text.splitlines(), 1):
        m = _HLO_OP_RE.search(line)
        if not m:
            continue
        typestr, raw = m.group(1), m.group(2)
        kind = raw[:-len("-start")] if raw.endswith("-start") else raw
        if kind not in HLO_COLLECTIVES or raw.endswith("-done"):
            continue
        if raw.endswith("-start"):
            # async form: the result is a tuple (operand alias, result
            # [, contexts]) — summing it would double-count; the payload
            # the ring actually moves is the (largest) result element.
            nbytes = max(_hlo_shape_sizes(typestr) or [0])
        else:
            nbytes = _hlo_shape_bytes(typestr)
        groups = ""
        gm = re.search(r"replica_groups=(\{[^}]*\}\}|\[[^\]]*\]<=\[[0-9,]*\])",
                       line)
        if gm:
            groups = gm.group(1)
        opname = ""
        om = re.search(r'op_name="([^"]*)"', line)
        if om:
            opname = om.group(1)
        entries.append({
            "kind": kind,
            "shape": typestr,
            "bytes": nbytes,
            "replica_groups": groups,
            "op_name": opname,
            "hlo_line": lineno,
        })
    return entries


def replica_group_size(replica_groups: str) -> int:
    """Members per group of a ``replica_groups`` value (as
    :func:`hlo_collectives` returns it), in either spelling: the list
    ``{{0,1,2,3}}`` or the iota form ``[groups,size]<=[n]``; 1 when the
    attribute is empty (a collective over one device)."""
    m = re.match(r"\[\d+,(\d+)\]<=", replica_groups)
    if m:
        return int(m.group(1))
    m = re.match(r"\{\{([\d,]*)\}", replica_groups)
    if m:
        return len([x for x in m.group(1).split(",") if x])
    return 1


_WIDE_HLO_DTYPES = ("f32", "f64")


def wide_gradient_allreduces(entries: Sequence[dict],
                             min_bytes: int) -> List[dict]:
    """All-reduce entries (from :func:`hlo_collectives`) at least
    ``min_bytes`` big whose payload carries a full-precision (>= 32-bit)
    float — the thing a compressed-wire step must have NONE of. The byte
    floor exempts the scalar traffic compression legitimately keeps in
    f32 (the loss pmean, fp8 per-bucket amax scale exchanges)."""
    out = []
    for e in entries:
        if e["kind"] != "all-reduce" or e["bytes"] < min_bytes:
            continue
        dtypes = {d for d, _ in _HLO_SHAPE_RE.findall(e["shape"])}
        if dtypes & set(_WIDE_HLO_DTYPES):
            out.append(dict(e))
    return out


def collective_fingerprint(entries: Sequence[dict]) -> str:
    """Stable digest of the ORDERED (kind, shape, replica_groups)
    sequence — the thing that must agree across every controller (and
    across recompiles of one signature) or the pod deadlocks."""
    canon = [(e["kind"], e["shape"], e["replica_groups"]) for e in entries]
    return hashlib.sha1(
        json.dumps(canon, separators=(",", ":")).encode()).hexdigest()[:16]


def first_divergence(a: Sequence[dict], b: Sequence[dict]) -> str:
    """Human description of the first position where two collective
    sequences differ."""
    for i, (x, y) in enumerate(zip(a, b)):
        kx = (x["kind"], x["shape"], x["replica_groups"])
        ky = (y["kind"], y["shape"], y["replica_groups"])
        if kx != ky:
            return (f"op #{i}: {x['kind']} {x['shape']} vs "
                    f"{y['kind']} {y['shape']}")
    if len(a) != len(b):
        return f"op #{min(len(a), len(b))}: sequence lengths {len(a)} vs {len(b)}"
    return "identical"


def check_implicit_resharding(entries: Sequence[dict],
                              manifest: Optional[dict],
                              min_bytes: int) -> List[dict]:
    """HVD502: resharding-suspect ops above ``min_bytes`` not covered by
    the expected-collectives ``manifest`` (see
    :func:`horovod_tpu.ops.fusion.expected_manifest`). Manifest entries
    are count-and-byte budgets per op kind; tiny resharding below the
    threshold stays quiet by design."""
    budgets: List[dict] = []
    for e in (manifest or {}).get("entries", ()):
        budgets.append({"op": e.get("op", ""),
                        "count": int(e.get("count", 0)),
                        "bytes": int(e.get("bytes", 0))})
    problems: List[dict] = []
    for e in entries:
        if e["kind"] not in RESHARD_KINDS or e["bytes"] < min_bytes:
            continue
        covered = False
        for b in budgets:
            if (b["op"] == e["kind"] and b["count"] > 0
                    and e["bytes"] <= b["bytes"]):
                b["count"] -= 1
                covered = True
                break
        if covered:
            continue
        src = f" (from {e['op_name']})" if e["op_name"] else ""
        mib = e["bytes"] / (1024.0 * 1024.0)
        problems.append({
            "entry": dict(e),
            "message": (
                f"optimized HLO contains an unaccounted {e['kind']} of "
                f"{e['shape']} ({mib:.1f} MiB){src} — the GSPMD "
                f"partitioner inserted data movement no declared "
                f"collective explains; check the pjit/shard_map sharding "
                f"annotations, or add it to the expected-collectives "
                f"manifest if intended"),
        })
    return problems


# ---------------------------------------------------------------------------
# HVD504 — donation parsing/checking
# ---------------------------------------------------------------------------

def parse_input_output_alias(hlo_text: str) -> List[int]:
    """Parameter numbers the compiled executable aliases to outputs
    (the honored donations), from the HloModule header's
    ``input_output_alias={ {out}: (param, {index}, kind), ... }``.
    Brace-balanced scan (no size cap): a large model's alias map — one
    entry per donated leaf — can run to hundreds of KiB, and truncating
    it would misreport honored donations as HVD504 misses."""
    start = hlo_text.find("input_output_alias={")
    if start < 0:
        return []
    i = start + len("input_output_alias=")
    depth = 0
    for j in range(i, len(hlo_text)):
        c = hlo_text[j]
        if c == "{":
            depth += 1
        elif c == "}":
            depth -= 1
            if depth == 0:
                body = hlo_text[i + 1:j]
                return [int(p)
                        for p in re.findall(r"\(\s*(\d+)\s*,", body)]
    return []


def check_donation(donated: Sequence[bool],
                   leaf_bytes: Sequence[int],
                   leaf_labels: Sequence[str],
                   arg_of_leaf: Sequence[int],
                   aliased_params: Sequence[int],
                   out_shapes: Sequence[Tuple[Tuple[int, ...], str]],
                   in_shapes: Sequence[Tuple[Tuple[int, ...], str]],
                   min_bytes: int,
                   alias_supported: bool) -> List[dict]:
    """HVD504 problems, two sub-checks:

    - *dropped donation*: a leaf marked donated whose parameter the
      executable did not alias (only judged when the backend honored at
      least one alias, or ``alias_supported`` says it can);
    - *forgotten donation*: an argument none of whose leaves are donated
      even though they match output leaves shape-for-shape (the carried
      train state) above ``min_bytes`` — params/opt state held twice.
    """
    problems: List[dict] = []
    n = len(donated)
    aliased = set(aliased_params)
    judge_drops = alias_supported or bool(aliased)
    if judge_drops:
        for i in range(n):
            if donated[i] and i not in aliased and leaf_bytes[i] >= min_bytes:
                mib = leaf_bytes[i] / (1024.0 * 1024.0)
                problems.append({
                    "leaf": leaf_labels[i],
                    "message": (
                        f"argument leaf {leaf_labels[i]} ({mib:.1f} MiB) is "
                        f"marked for donation but the compiled executable "
                        f"did not alias its buffer to any output — the "
                        f"donated memory is NOT reused (shape/dtype must "
                        f"match an output exactly for XLA to alias it)"),
                })

    # forgotten donation: per top-level argument, sum the undonated
    # state-like bytes (leaves whose (shape, dtype) matches an output).
    remaining = list(out_shapes)
    per_arg: Dict[int, int] = {}
    per_arg_donated: Dict[int, bool] = {}
    for i in range(n):
        per_arg_donated.setdefault(arg_of_leaf[i], False)
        if donated[i]:
            per_arg_donated[arg_of_leaf[i]] = True
            continue
        if in_shapes[i] in remaining:
            remaining.remove(in_shapes[i])
            per_arg[arg_of_leaf[i]] = per_arg.get(arg_of_leaf[i], 0) \
                + leaf_bytes[i]
    for argnum, nbytes in sorted(per_arg.items()):
        if nbytes < min_bytes or per_arg_donated.get(argnum):
            continue
        mib = nbytes / (1024.0 * 1024.0)
        problems.append({
            "argnum": argnum,
            "message": (
                f"argument {argnum} carries {mib:.1f} MiB of leaves whose "
                f"shapes/dtypes exactly match output leaves (a carried "
                f"train state) but is not in donate_argnums — params/opt "
                f"state are held twice in device memory; jit the step with "
                f"donate_argnums=({argnum},) (trainer.jit_step does this "
                f"under HOROVOD_TPU_DONATE_BUFFERS)"),
        })
    return problems
