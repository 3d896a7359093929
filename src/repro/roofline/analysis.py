"""Three-term roofline model over post-SPMD compiled HLO text.

Why a custom analyzer: ``compiled.cost_analysis()`` does NOT multiply ops
inside ``while`` bodies by their trip count (verified empirically — a
4-step scan reports ~1 body's flops), and our models are scanned over
layers, so XLA's own numbers undercount by ~num_layers. The compiled HLO
text, however, carries ``backend_config={"known_trip_count":{"n":...}}``
on every scan-derived while op, so an exact correction is parseable.

The analyzer walks the partitioned (= per-device) HLO:

  - **FLOPs**: every ``dot`` op contributes 2 x prod(result dims) x
    prod(contracting dims) x trip-multiplier. Element-wise flops are
    ignored (sub-1% for transformer workloads).
  - **HBM traffic**: every *top-level* op in ENTRY / while bodies counts
    operand + result bytes once (a fusion reads its inputs once and
    writes its outputs once — the fusion-level caching abstraction that
    rooflines assume). Ops inside fusion computations are NOT counted.
  - **Collective bytes**: all-gather / all-reduce / reduce-scatter /
    all-to-all / collective-permute operand bytes x trip-multiplier,
    converted to per-device link traffic with ring-algorithm factors:
    AG: (n-1)x shard, AR: 2(n-1)/n, RS: (n-1)/n, A2A: (n-1)/n, CP: 1x.

Terms (seconds, per device — the HLO is already per-device):

    compute    = flops / peak_flops
    memory     = hbm_bytes / hbm_bw
    collective = link_bytes / link_bw

Hardware constants come from :data:`PEAKS`, keyed by the device's
``device_kind``; a kind missing from the table is an error, never a
default (:func:`hw_for`).
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Any

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


@dataclasses.dataclass(frozen=True)
class HW:
    """Per-chip hardware constants."""
    peak_flops: float                 # bf16 FLOP/s
    hbm_bw: float                     # bytes/s
    link_bw: float                    # bytes/s per ICI link
    hbm_bytes: float                  # capacity, for the fits-check


# Published per-chip peaks, keyed by ``jax.Device.device_kind``. TPU v5e
# ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" — 197 TFLOP/s
# bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of ICI over 4 links
# (= 50 GB/s per link).
PEAKS: dict[str, HW] = {
    "TPU v5 lite": HW(peak_flops=197e12, hbm_bw=819e9, link_bw=50e9,
                      hbm_bytes=16 * 2**30),
}


def hw_for(device_kind: str) -> HW:
    """The peaks of ``device_kind``; an unknown kind raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks for device kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); add its published numbers to PEAKS") \
            from None


def _shape_bytes_and_dims(type_str: str):
    """Total bytes and the dims of the FIRST array in a type string
    (tuples: bytes summed, dims of first element)."""
    total = 0
    first_dims = None
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims_s = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        dims = [int(d) for d in dims_s.split(",") if d] if dims_s else []
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dt]
        if first_dims is None:
            first_dims = dims
    return total, (first_dims if first_dims is not None else [])


_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*((?:\([^()]*\)|[a-z0-9]+\[[0-9,]*\]"
    r"(?:\{[^}]*\})?))\s*([\w\-]+)\(([^\n]*)$")


def _parse_computations(hlo: str):
    """Split HLO text into computations: name -> list of op dicts."""
    comps: dict[str, list[dict]] = {}
    cur = None
    for line in hlo.splitlines():
        s = line.strip()
        if s.startswith("ENTRY"):
            cur = "ENTRY"
            comps.setdefault(cur, [])
            continue
        m = re.match(r"^%([\w\.\-]+)\s*\(", s)
        if m and s.endswith("{") and ") -> " in s:
            cur = m.group(1)
            comps.setdefault(cur, [])
            continue
        if cur is None:
            continue
        om = _OP_RE.match(line)
        if om:
            name, type_str, opcode, rest = om.groups()
            comps.setdefault(cur, []).append({
                "name": name, "type": type_str, "op": opcode,
                "rest": rest, "line": s,
            })
    return comps


def _operand_names(rest: str) -> list[str]:
    """Operand names from the call-paren contents (up to the closing paren
    at depth 0)."""
    out = []
    depth = 0
    token = ""
    for ch in rest:
        if ch == "(":
            depth += 1
        elif ch == ")":
            if depth == 0:
                break
            depth -= 1
        token += ch
    for part in token.split(","):
        part = part.strip()
        m = re.search(r"%([\w\.\-]+)\s*$", part)
        if m:
            out.append(m.group(1))
    return out


def _attr_dims(rest: str, key: str) -> list[int]:
    m = re.search(key + r"=\{([0-9,]*)\}", rest)
    if not m:
        return []
    return [int(x) for x in m.group(1).split(",") if x]


def _group_size(rest: str) -> int:
    # replica_groups=[8,2]<=[16] → groups of 2; or {{0,1},{2,3}} form.
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", rest)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([0-9,]+)\}", rest)
    if m:
        return len(m.group(1).split(","))
    return 2


@dataclasses.dataclass
class HloAnalysis:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    link_bytes: float = 0.0
    collective_bytes_raw: float = 0.0
    by_collective: dict = dataclasses.field(default_factory=dict)
    dot_flops_top: list = dataclasses.field(default_factory=list)
    hbm_top: list = dataclasses.field(default_factory=list)
    coll_top: list = dataclasses.field(default_factory=list)
    notes: list = dataclasses.field(default_factory=list)

    def to_json(self) -> dict[str, Any]:
        d = dataclasses.asdict(self)
        d["dot_flops_top"] = d["dot_flops_top"][:10]
        d["hbm_top"] = d["hbm_top"][:10]
        d["coll_top"] = d["coll_top"][:10]
        return d

    def report(self, k: int = 12) -> str:
        """Human-readable per-op breakdown — the hillclimb 'profile'."""
        lines = [f"flops/chip {self.flops:.3e}  hbm {self.hbm_bytes:.3e}B"
                 f"  link {self.link_bytes:.3e}B"]
        lines.append("-- top HBM traffic ops (bytes x trips) --")
        for b, l in self.hbm_top[:k]:
            lines.append(f"  {b:10.3e}  {l}")
        lines.append("-- top collectives (link bytes x trips) --")
        for b, l in self.coll_top[:k]:
            lines.append(f"  {b:10.3e}  {l}")
        lines.append("-- top dots (flops) --")
        for f, l in self.dot_flops_top[:k]:
            lines.append(f"  {f:10.3e}  {l}")
        return "\n".join(lines)


def analyze_hlo_text(hlo: str) -> HloAnalysis:
    comps = _parse_computations(hlo)

    # --- symbol tables: op name -> (bytes, dims) per computation ---------
    sym: dict[str, dict[str, tuple[float, list[int]]]] = {}
    for cname, ops in comps.items():
        table = {}
        for op in ops:
            table[op["name"]] = _shape_bytes_and_dims(op["type"])
        sym[cname] = table

    # --- effective read size of fusion parameters -------------------------
    # A fusion that only dynamic-slices a parameter reads the SLICE from
    # HBM, not the whole buffer (scan bodies slice their stacked inputs).
    # fusion computation -> [effective bytes per parameter index].
    fusion_param_bytes: dict[str, list[float]] = {}
    for cname, ops in comps.items():
        params: dict[str, int] = {}
        full: list[float] = []
        for op in ops:
            if op["op"] == "parameter":
                idx = len(full)
                params[op["name"]] = idx
                full.append(_shape_bytes_and_dims(op["type"])[0])
        if not params:
            continue
        sliced: dict[int, float] = {}
        direct: set[int] = set()
        for op in ops:
            if op["op"] == "parameter":
                continue
            operands = _operand_names(op["rest"])
            if op["op"] in ("dynamic-slice", "slice") and operands \
                    and operands[0] in params:
                res, _ = _shape_bytes_and_dims(op["type"])
                i = params[operands[0]]
                sliced[i] = sliced.get(i, 0.0) + res
                operands = operands[1:]  # index operands: scalars
            for o in operands:
                if o in params:
                    direct.add(params[o])
        eff = []
        for i, fb in enumerate(full):
            if i in direct or i not in sliced:
                eff.append(fb)
            else:
                eff.append(min(fb, sliced[i]))
        fusion_param_bytes[cname] = eff

    # --- trip-count multipliers ------------------------------------------
    # while ops: body=%comp, known_trip_count n. Multiplier of a body =
    # multiplier of the computation containing the while x n.
    body_of: dict[str, tuple[str, int]] = {}  # body comp -> (parent, n)
    for cname, ops in comps.items():
        for op in ops:
            if op["op"] == "while":
                bm = re.search(r"body=%?([\w\.\-]+)", op["rest"])
                tm = re.search(r'known_trip_count[^0-9]*(\d+)', op["rest"])
                n = int(tm.group(1)) if tm else 1
                if bm:
                    body_of[bm.group(1)] = (cname, n)

    mult: dict[str, float] = {}

    def get_mult(cname: str) -> float:
        if cname in mult:
            return mult[cname]
        if cname == "ENTRY":
            mult[cname] = 1.0
        elif cname in body_of:
            parent, n = body_of[cname]
            mult[cname] = n * get_mult(parent)
        else:
            # fusion / reduce / conditional-branch computations: counted at
            # their call sites, not walked -> multiplier irrelevant (0).
            mult[cname] = 0.0
        return mult[cname]

    # computations we walk top-level: ENTRY + while bodies (+ conditional
    # branches would go here; none in these models).
    walk = ["ENTRY"] + list(body_of.keys())

    out = HloAnalysis()
    for cname in walk:
        if cname not in comps:
            continue
        m = get_mult(cname) or 1.0
        table = sym.get(cname, {})
        for op in comps[cname]:
            opc = op["op"]
            if opc in ("parameter", "constant", "while", "tuple",
                       "get-tuple-element", "bitcast", "after-all",
                       # dtype converts fuse into producers/consumers on
                       # the TPU pipeline; XLA:CPU leaves them top-level —
                       # charging them would bill phantom traffic.
                       "convert"):
                continue
            res_bytes, res_dims = _shape_bytes_and_dims(op["type"])
            operands = _operand_names(op["rest"])
            opd_bytes = sum(table.get(o, (0.0, []))[0] for o in operands)

            if opc == "dot":
                # flops = 2 x prod(result) x prod(contracting dims of lhs)
                lhs = operands[0] if operands else None
                lhs_dims = table.get(lhs, (0.0, []))[1] if lhs else []
                cdims = _attr_dims(op["rest"], "lhs_contracting_dims")
                k = 1
                for c in cdims:
                    if c < len(lhs_dims):
                        k *= lhs_dims[c]
                nres = 1
                for d in res_dims:
                    nres *= d
                f = 2.0 * nres * k * m
                out.flops += f
                out.dot_flops_top.append((f, op["line"][:120]))

            # ---- HBM traffic special cases -------------------------------
            # Slicing ops inside while bodies take the FULL carried tensor
            # as an operand; actual traffic is the slice, not the buffer.
            hbm = None
            if opc == "dynamic-slice" or opc == "gather":
                hbm = 2.0 * res_bytes
            elif opc == "dynamic-update-slice":
                upd = (table.get(operands[1], (0.0, []))[0]
                       if len(operands) > 1 else res_bytes)
                hbm = 2.0 * upd
            elif opc == "fusion":
                comp_m = re.search(r"calls=%?([\w\.\-]+)", op["rest"])
                fname = comp_m.group(1) if comp_m else None
                # Trivial fusions (convert/bitcast/reshape only) also fuse
                # away on TPU.
                if fname in comps and all(
                        f["op"] in ("parameter", "convert", "bitcast",
                                    "reshape", "broadcast")
                        for f in comps[fname]):
                    continue
                # Per-parameter effective reads: parameters consumed only
                # through (dynamic-)slice inside the fusion are charged at
                # slice size — scan bodies slice their stacked inputs.
                eff = fusion_param_bytes.get(fname)
                sizes = [table.get(o, (0.0, []))[0] for o in operands]
                if eff is not None and len(eff) == len(sizes):
                    charges = [min(s, e) for s, e in zip(sizes, eff)]
                else:
                    charges = sizes
                reads = sum(charges)
                root_dus = False
                if fname in comps:
                    for fop in comps[fname]:
                        if fop["op"] == "dynamic-update-slice" and \
                                fop["line"].startswith("ROOT"):
                            root_dus = True
                if root_dus and sizes:
                    # In-place update fusion: the aliased buffer (largest
                    # operand) is neither fully read nor fully written —
                    # charge the other reads + an equal write.
                    ibuf = max(range(len(sizes)), key=lambda i: sizes[i])
                    other = reads - charges[ibuf]
                    hbm = 2.0 * other
                else:
                    hbm = reads + res_bytes

            if any(opc.startswith(c) for c in _COLLECTIVES):
                n = _group_size(op["rest"])
                base = opd_bytes
                if opc.startswith("all-gather"):
                    traffic = base * (n - 1)
                elif opc.startswith("all-reduce"):
                    traffic = base * 2.0 * (n - 1) / n
                elif opc.startswith("reduce-scatter"):
                    traffic = base * (n - 1) / n
                elif opc.startswith("all-to-all"):
                    traffic = base * (n - 1) / n
                else:  # collective-permute
                    traffic = base
                out.collective_bytes_raw += base * m
                out.link_bytes += traffic * m
                key = opc.split(".")[0]
                out.by_collective[key] = out.by_collective.get(key, 0.0) \
                    + traffic * m
                out.coll_top.append((traffic * m,
                                     f"x{m:g} {op['line'][:140]}"))

            # HBM traffic: operands + result, once per top-level op.
            if hbm is None:
                hbm = opd_bytes + res_bytes
            out.hbm_bytes += hbm * m
            out.hbm_top.append((hbm * m, f"x{m:g} {op['line'][:140]}"))

    for attr in ("dot_flops_top", "hbm_top", "coll_top"):
        vals = getattr(out, attr)
        vals.sort(key=lambda t: -t[0])
        setattr(out, attr, vals[:30])
    return out


def roofline_terms(analysis: HloAnalysis, hw: HW) -> dict[str, float]:
    compute = analysis.flops / hw.peak_flops
    memory = analysis.hbm_bytes / hw.hbm_bw
    collective = analysis.link_bytes / hw.link_bw
    dominant = max(("compute", compute), ("memory", memory),
                   ("collective", collective), key=lambda kv: kv[1])
    bound = max(compute, memory, collective)
    return {
        "compute_s": compute,
        "memory_s": memory,
        "collective_s": collective,
        "dominant": dominant[0],
        "bound_s": bound,
        # fraction of roofline the *useful* compute achieves if the step ran
        # exactly at the bound: compute / bound.
        "roofline_fraction": (compute / bound) if bound > 0 else 0.0,
    }


def model_flops(mcfg, *, tokens: int, kind: str) -> float:
    """MODEL_FLOPS = 6·N·D (train) / 2·N·D (inference), N = active params."""
    n = mcfg.count_active_params()
    per_tok = 6 * n if kind == "train" else 2 * n
    return float(per_tok) * tokens


def dump_json(path: str, payload: dict) -> None:
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
