"""Trip-count-aware HLO cost model.

XLA's ``compiled.cost_analysis()`` visits each ``while`` body ONCE — a
scan-over-layers model under-reports FLOPs by the layer count (verified
empirically: a 10-iteration scan of a matmul reports 1 matmul).  This module
re-derives roofline quantities from the optimized HLO text with loop trip
multiplication:

  * flops        — dot ops: 2 * |out| * |contracted|; reduces: |in|
  * hbm_bytes    — per top-level op: operand + output buffer sizes (fusions
                   count only their boundary buffers — internal traffic stays
                   in registers/VMEM, matching the fused-op HBM model)
  * collective_bytes — per collective op: wire bytes with standard factors
                   (all-reduce 2x ring, reduce-scatter/all-gather 1x,
                   all-to-all 1x, collective-permute 1x)

While-loop trip counts are read from the loop condition's compare-constant
(scan bounds are static in this codebase).  Conditionals take the max branch.
All quantities are per-device: the HLO module is the SPMD-partitioned
per-device program.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

__all__ = ["analyze_hlo", "analyze_jaxpr", "HloCost"]

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "token": 0,
    "s4": 1, "u4": 1,
}

_COMP_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\((.*)\)\s*->")
# type strings may contain /*index=N*/ comments (inside tuples), so match
# lazily up to the first "opcode(" word — metadata strings come later on the
# line and cannot match first.
_OP_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\("
)
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_CALLS = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_COND_BODY = re.compile(r"condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_CONTRACT = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_OPERANDS = re.compile(r"%([\w.\-]+)")
_CONSTANT_S32 = re.compile(r"constant\((\d+)\)")

# ops whose operand/output buffers count as HBM traffic
_MEM_OPS = {
    "fusion", "dot", "convolution", "custom-call", "copy", "transpose",
    "concatenate", "pad", "reduce", "sort", "gather", "scatter",
    "dynamic-slice", "dynamic-update-slice", "broadcast", "convert", "slice",
    "reduce-window", "select-and-scatter", "reverse", "iota", "rng",
    "rng-bit-generator", "select", "compare", "add", "multiply", "subtract",
    "divide", "exponential", "tanh", "log", "clamp", "maximum", "minimum",
    "reshape", "cbrt", "rsqrt", "sqrt", "negate", "abs", "and", "or", "xor",
    "shift-left", "shift-right-logical", "shift-right-arithmetic",
}
_COLLECTIVES = {
    "all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
    "all-to-all": 1.0, "collective-permute": 1.0,
    "all-reduce-start": 2.0, "all-gather-start": 1.0,
    "collective-permute-start": 1.0,
}
_SKIP_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "while", "conditional",
    "call", "domain", "opt-barrier", "all-reduce-done", "all-gather-done",
    "collective-permute-done", "async-start", "async-done", "async-update",
    "get-dimension-size", "outfeed", "infeed",
}


def _shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE.finditer(type_str):
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


def _shape_dims(type_str: str) -> List[int]:
    m = _SHAPE.search(type_str)
    if not m or not m.group(2):
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    coll_bytes: float = 0.0
    coll_by_op: Optional[Dict[str, float]] = None
    coll_f32_bytes: float = 0.0

    def __add__(self, o: "Cost") -> "Cost":
        merged = dict(self.coll_by_op or {})
        for k, v in (o.coll_by_op or {}).items():
            merged[k] = merged.get(k, 0.0) + v
        return Cost(
            self.flops + o.flops,
            self.hbm_bytes + o.hbm_bytes,
            self.coll_bytes + o.coll_bytes,
            merged,
            self.coll_f32_bytes + o.coll_f32_bytes,
        )

    def scaled(self, k: float) -> "Cost":
        return Cost(
            self.flops * k, self.hbm_bytes * k, self.coll_bytes * k,
            {n: v * k for n, v in (self.coll_by_op or {}).items()},
            self.coll_f32_bytes * k,
        )


@dataclasses.dataclass
class HloCost:
    flops: float
    hbm_bytes: float
    collective_bytes: float
    collective_by_op: Dict[str, float]
    num_whiles: int
    unknown_trip_whiles: int
    collective_f32_bytes: float = 0.0
    pallas_calls: int = 0  # jaxpr-level analysis only (analyze_jaxpr)

    @property
    def collective_bytes_tpu(self) -> float:
        """bf16-adjusted wire bytes: the CPU backend upcasts bf16 compute
        to f32 before SPMD partitioning, so activation collectives in this
        lowering are f32; TPU (native bf16) moves half.  True-f32 state
        (optimizer scalars, fp32 routers) is a negligible share of the f32
        volume here — every activation/param tensor in the model is bf16 by
        construction."""
        return self.collective_bytes - 0.5 * self.collective_f32_bytes


class _Parser:
    def __init__(self, text: str):
        self.computations: Dict[str, List[str]] = {}
        self.entry: Optional[str] = None
        self._split(text)
        self._memo: Dict[str, Cost] = {}
        self.num_whiles = 0
        self.unknown_trips = 0

    def _split(self, text: str):
        cur = None
        buf: List[str] = []
        for line in text.splitlines():
            m = _COMP_HEADER.match(line)
            if m and line.rstrip().endswith("{"):
                if cur is not None:
                    self.computations[cur] = buf
                cur = m.group(2)
                buf = []
                if m.group(1):
                    self.entry = cur
            elif line.strip() == "}":
                if cur is not None:
                    self.computations[cur] = buf
                    cur = None
                    buf = []
            elif cur is not None:
                buf.append(line)
        if cur is not None:
            self.computations[cur] = buf

    # -- trip count from the while condition computation ------------------
    def _trip_count(self, cond_name: str) -> Optional[int]:
        lines = self.computations.get(cond_name, [])
        consts = []
        for ln in lines:
            consts += [int(x) for x in _CONSTANT_S32.findall(ln)]
            # the bound may live one fusion deeper
            cm = _CALLS.search(ln)
            if cm:
                for ln2 in self.computations.get(cm.group(1), []):
                    consts += [int(x) for x in _CONSTANT_S32.findall(ln2)]
        consts = [c for c in consts if c > 0]
        return max(consts) if consts else None

    def _internal_slice_bytes(self, comp_name: str) -> Optional[int]:
        """If the called computation slices a big buffer (scan-accumulator
        pattern), return the total sliced bytes; else None.

        dynamic-slice: the op's OUTPUT is the slice.  dynamic-update-slice:
        the UPDATE operand (2nd arg) is the slice; the buffer is aliased.
        """
        if not hasattr(self, "_slice_memo"):
            self._slice_memo = {}
        if comp_name in self._slice_memo:
            return self._slice_memo[comp_name]
        shapes: Dict[str, str] = {}
        total = 0
        found = False
        for ln in self.computations.get(comp_name, []):
            m = _OP_LINE.match(ln)
            if not m:
                continue
            name, type_str, opcode = m.groups()
            shapes[name] = type_str
            if opcode == "dynamic-slice":
                found = True
                total += _shape_bytes(type_str)
            elif opcode == "dynamic-update-slice":
                found = True
                ops = _OPERANDS.findall(ln[m.end():].split(", calls=")[0])
                if len(ops) >= 2 and ops[1] in shapes:
                    total += _shape_bytes(shapes[ops[1]])
                else:
                    total += _shape_bytes(type_str) // 64  # fallback guess
        out = total if found else None
        self._slice_memo[comp_name] = out
        return out

    def cost_of(self, comp_name: str) -> Cost:
        if comp_name in self._memo:
            return self._memo[comp_name]
        self._memo[comp_name] = Cost()  # cycle guard
        lines = self.computations.get(comp_name, [])
        shapes: Dict[str, str] = {}
        total = Cost(coll_by_op={})
        for ln in lines:
            m = _OP_LINE.match(ln)
            if not m:
                continue
            name, type_str, opcode = m.groups()
            shapes[name] = type_str
            out_bytes = _shape_bytes(type_str)

            if opcode == "while":
                cb = _COND_BODY.search(ln)
                if not cb:
                    continue
                cond, body = cb.groups()
                trip = self._trip_count(cond)
                self.num_whiles += 1
                if trip is None:
                    trip = 1
                    self.unknown_trips += 1
                inner = self.cost_of(body) + self.cost_of(cond)
                total = total + inner.scaled(trip)
                continue
            if opcode == "conditional":
                br = _BRANCHES.search(ln)
                if br:
                    branch_costs = [
                        self.cost_of(b.strip().lstrip("%"))
                        for b in br.group(1).split(",")
                    ]
                    if branch_costs:
                        best = max(branch_costs, key=lambda c: c.flops)
                        total = total + best
                continue
            if opcode == "call":
                cm = _CALLS.search(ln)
                if cm:
                    total = total + self.cost_of(cm.group(1))
                continue

            # operand bytes (definitions seen so far in this computation)
            tail = ln[m.end():]
            operand_bytes = 0
            for om in _OPERANDS.finditer(tail.split(", calls=")[0]):
                op_shape = shapes.get(om.group(1))
                if op_shape:
                    operand_bytes += _shape_bytes(op_shape)

            if opcode in _COLLECTIVES:
                factor = _COLLECTIVES[opcode]
                wire = factor * max(out_bytes, operand_bytes)
                key = opcode.replace("-start", "")
                total.coll_bytes += wire
                total.coll_by_op[key] = total.coll_by_op.get(key, 0.0) + wire
                # track fp32 collective volume: the CPU backend's float
                # normalization upcasts bf16 dots/elementwise to f32 BEFORE
                # partitioning, so activation collectives ride f32 wires in
                # this lowering; on TPU (native bf16) they are half.  The
                # roofline reports both raw and bf16-adjusted numbers.
                if _SHAPE.search(type_str) and _SHAPE.search(
                    type_str
                ).group(1) == "f32":
                    total.coll_f32_bytes += wire
                total.hbm_bytes += out_bytes + operand_bytes
                continue

            if opcode == "fusion":
                cm = _CALLS.search(ln)
                slice_bytes = None
                if cm:
                    called = cm.group(1)
                    inner = self.cost_of(called)
                    # fusions contribute their internal flops but only their
                    # boundary bytes
                    total.flops += inner.flops
                    slice_bytes = self._internal_slice_bytes(called)
                if slice_bytes is not None:
                    # scan-accumulator pattern: the fusion reads/writes a
                    # [T, ...] buffer through internal dynamic-(update-)
                    # slices; real HBM traffic is the slices (the buffer is
                    # aliased in place).  Operands are capped at the slice
                    # volume; the small (non-accumulator) operands are below
                    # the cap anyway.
                    cap = max(slice_bytes, 1)
                    capped = 0
                    for om in _OPERANDS.finditer(tail.split(", calls=")[0]):
                        op_shape = shapes.get(om.group(1))
                        if op_shape:
                            capped += min(_shape_bytes(op_shape), cap)
                    total.hbm_bytes += 2 * slice_bytes + capped
                else:
                    total.hbm_bytes += out_bytes + operand_bytes
                continue

            if opcode == "dot":
                lhs_m = _OPERANDS.search(tail)
                lhs_shape = shapes.get(lhs_m.group(1), "") if lhs_m else ""
                lhs_dims = _shape_dims(lhs_shape)
                cm = _CONTRACT.search(ln)
                contracted = 1
                if cm and lhs_dims:
                    for idx in cm.group(1).split(","):
                        if idx:
                            i = int(idx)
                            if i < len(lhs_dims):
                                contracted *= lhs_dims[i]
                out_elems = 1
                for d in _shape_dims(type_str):
                    out_elems *= d
                total.flops += 2.0 * out_elems * contracted
                total.hbm_bytes += out_bytes + operand_bytes
                continue

            if opcode in ("reduce", "reduce-window"):
                total.flops += operand_bytes / 2  # ~1 flop per elem (bf16≈2B)
                total.hbm_bytes += out_bytes + operand_bytes
                continue

            if opcode == "dynamic-update-slice":
                # in-place: traffic = update read + update write (the full
                # buffer is aliased, not copied) — without this, scans that
                # accumulate into a [T, ...] buffer over-count by xT
                upd = max(operand_bytes - out_bytes, 0)
                total.hbm_bytes += 2 * upd
                continue
            if opcode == "dynamic-slice":
                total.hbm_bytes += 2 * out_bytes  # slice read + write
                continue
            if opcode == "scatter":
                # in-place scatter(-add): the big operand is aliased; real
                # traffic = updates read + scattered writes (+ indices)
                upd = max(operand_bytes - out_bytes, 0)
                total.hbm_bytes += 2 * min(upd, out_bytes) + (
                    upd - min(upd, out_bytes)
                )
                continue
            if opcode == "gather":
                # random-access reads of ~output volume (+ indices)
                total.hbm_bytes += 2 * out_bytes
                continue

            if opcode in _MEM_OPS:
                total.hbm_bytes += out_bytes + operand_bytes
                continue
            # _SKIP_OPS and anything unrecognized: no cost
        self._memo[comp_name] = total
        return total


def analyze_hlo(text: str) -> HloCost:
    p = _Parser(text)
    entry = p.entry or (next(iter(p.computations)) if p.computations else "")
    cost = p.cost_of(entry) if entry else Cost(coll_by_op={})
    return HloCost(
        flops=cost.flops,
        hbm_bytes=cost.hbm_bytes,
        collective_bytes=cost.coll_bytes,
        collective_by_op=cost.coll_by_op or {},
        num_whiles=p.num_whiles,
        unknown_trip_whiles=p.unknown_trips,
        collective_f32_bytes=cost.coll_f32_bytes,
    )


# ---------------------------------------------------------------------------
# Jaxpr-level analysis: the pre-lowering twin of analyze_hlo.
#
# Post-megakernel, the serving kernel path is all ``pallas_call`` — an
# opaque primitive whose kernel body never reaches the HLO text this
# module parses (on TPU it lowers to a custom-call; in interpret mode to
# an XLA while-nest whose structure has nothing to do with the kernel's
# declared tiling).  analyze_jaxpr walks the *jaxpr* instead and
# attributes each pallas_call from what the kernel declares:
#
#   flops = (kernel body cost) x prod(grid)       — every grid step runs
#           the body once;
#   hbm   = prod(grid) x sum(BlockSpec block bytes) — the pallas block
#           pipeline moves each operand/output block HBM<->VMEM once per
#           step; the body's own memory ops are VMEM traffic and are NOT
#           counted (same boundary-bytes model as analyze_hlo's fusions).
#
# scan multiplies its body by the static trip count (jax lowers
# fori_loop with concrete bounds to scan, so the kernels' slot loops and
# the 256-level LUT select are trip-counted exactly); while bodies with
# unknown trips count once and are flagged, mirroring the HLO parser.
# Elementwise traffic is attributed as whole-jaxpr I/O, not per-op: a
# jaxpr is pre-fusion, so summing every add/mul's operands would count
# register traffic as HBM.
# ---------------------------------------------------------------------------
_REDUCE_PRIMS = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "argmax", "argmin", "cumsum", "cummax", "cummin",
    "cumprod", "cumlogsumexp",
}


def _aval_bytes(aval) -> float:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0.0
    n = 1
    for d in shape:
        try:
            n *= int(d)
        except TypeError:  # dynamic dim: count as 1
            pass
    try:
        return float(n * dtype.itemsize)
    except AttributeError:
        return 0.0


def _aval_elems(aval) -> float:
    shape = getattr(aval, "shape", ())
    n = 1
    for d in shape:
        try:
            n *= int(d)
        except TypeError:
            pass
    return float(n)


class _JaxprState:
    def __init__(self):
        self.num_whiles = 0
        self.unknown_trips = 0
        self.pallas_calls = 0


def _unwrap_jaxpr(obj):
    """Accept Jaxpr, ClosedJaxpr, or anything carrying a .jaxpr."""
    inner = getattr(obj, "jaxpr", None)
    return obj if inner is None else _unwrap_jaxpr(inner)


def _pallas_block_bytes(grid_mapping, eqn) -> Tuple[float, float]:
    """(steps, per-step boundary bytes) of one pallas_call from its
    declared grid and BlockSpecs."""
    steps = 1.0
    for g in getattr(grid_mapping, "grid", ()) or ():
        try:
            steps *= max(int(g), 1)
        except TypeError:
            pass  # symbolic grid dim: count once
    per_step = 0.0
    for bm in getattr(grid_mapping, "block_mappings", ()) or ():
        block = bm.ref_aval  # the block as one grid step sees it
        elems = 1
        for d in block.shape:
            elems *= max(int(d), 1)
        per_step += float(elems * block.dtype.itemsize)
    if per_step == 0.0:
        # no usable block mappings (e.g. an older pallas): fall back to
        # the call's operand + output avals, moved once
        per_step = sum(_aval_bytes(v.aval) for v in eqn.invars) + sum(
            _aval_bytes(v.aval) for v in eqn.outvars
        )
        steps = 1.0
    return steps, per_step


def _jaxpr_cost(jaxpr, state: _JaxprState) -> Cost:
    total = Cost(coll_by_op={})
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name

        if prim == "pallas_call":
            state.pallas_calls += 1
            gm = eqn.params.get("grid_mapping")
            body = eqn.params.get("jaxpr")
            steps, per_step = (
                _pallas_block_bytes(gm, eqn) if gm is not None
                else (1.0, sum(_aval_bytes(v.aval) for v in eqn.invars))
            )
            if body is not None:
                inner = _jaxpr_cost(_unwrap_jaxpr(body), state)
                total.flops += inner.flops * steps
            total.hbm_bytes += steps * per_step
            continue

        if prim == "scan":
            body = eqn.params.get("jaxpr")
            length = int(eqn.params.get("length", 1) or 1)
            state.num_whiles += 1
            if body is not None:
                inner = _jaxpr_cost(_unwrap_jaxpr(body), state)
                total = total + inner.scaled(length)
            continue

        if prim == "while":
            state.num_whiles += 1
            state.unknown_trips += 1  # trip is data-dependent in a jaxpr
            for key in ("body_jaxpr", "cond_jaxpr"):
                body = eqn.params.get(key)
                if body is not None:
                    total = total + _jaxpr_cost(_unwrap_jaxpr(body), state)
            continue

        if prim == "cond":
            branches = eqn.params.get("branches") or ()
            costs = [
                _jaxpr_cost(_unwrap_jaxpr(b), state) for b in branches
            ]
            if costs:
                total = total + max(costs, key=lambda c: c.flops)
            continue

        if prim in ("jit", "pjit", "closed_call", "core_call", "remat_call",
                    "checkpoint", "remat", "custom_jvp_call",
                    "custom_vjp_call", "custom_vjp_call_jaxpr"):
            body = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            if body is not None:
                total = total + _jaxpr_cost(_unwrap_jaxpr(body), state)
            continue

        if prim == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval
            contracted = 1
            for i in lc:
                contracted *= int(lhs.shape[i])
            out_elems = _aval_elems(eqn.outvars[0].aval)
            total.flops += 2.0 * out_elems * contracted
            total.hbm_bytes += sum(
                _aval_bytes(v.aval) for v in eqn.invars
            ) + _aval_bytes(eqn.outvars[0].aval)
            continue

        if prim in _REDUCE_PRIMS:
            total.flops += _aval_elems(eqn.invars[0].aval)
            total.hbm_bytes += sum(
                _aval_bytes(v.aval) for v in eqn.invars
            ) + sum(_aval_bytes(v.aval) for v in eqn.outvars)
            continue

        if prim in ("gather", "dynamic_slice"):
            total.hbm_bytes += 2.0 * _aval_bytes(eqn.outvars[0].aval)
            continue
        if prim in ("scatter", "scatter-add", "scatter_add",
                    "dynamic_update_slice"):
            upd = _aval_bytes(eqn.invars[-1].aval)
            total.hbm_bytes += 2.0 * upd
            continue
        # elementwise & everything else: no per-op cost (see module note)
    return total


def analyze_jaxpr(jaxpr_or_fn, *example_args, **example_kwargs) -> HloCost:
    """Cost-analyze a jaxpr — including ones containing ``pallas_call``.

    Accepts a ``Jaxpr``/``ClosedJaxpr`` (e.g. from ``jax.make_jaxpr``), or
    a callable plus example arguments, which is traced here.  Returns the
    same :class:`HloCost` as :func:`analyze_hlo`, with ``pallas_calls``
    counting the kernels attributed from their declared grid/block shapes.
    Collective fields are always zero (jaxprs here are pre-partitioning).
    """
    if callable(jaxpr_or_fn) and not hasattr(jaxpr_or_fn, "eqns"):
        import jax

        jaxpr = jax.make_jaxpr(jaxpr_or_fn)(
            *example_args, **example_kwargs
        )
    else:
        jaxpr = jaxpr_or_fn
    jaxpr = _unwrap_jaxpr(jaxpr)
    state = _JaxprState()
    cost = _jaxpr_cost(jaxpr, state)
    # whole-jaxpr I/O: entry operands in, results out (counted once; the
    # per-op extras above only cover ops with non-streaming access)
    io_bytes = sum(_aval_bytes(v.aval) for v in jaxpr.invars) + sum(
        _aval_bytes(v.aval) for v in jaxpr.outvars
    )
    return HloCost(
        flops=cost.flops,
        hbm_bytes=cost.hbm_bytes + io_bytes,
        collective_bytes=0.0,
        collective_by_op={},
        num_whiles=state.num_whiles,
        unknown_trip_whiles=state.unknown_trips,
        collective_f32_bytes=0.0,
        pallas_calls=state.pallas_calls,
    )
