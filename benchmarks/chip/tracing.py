"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A :class:`TraceView` holds three lists of intervals on one clock, in
nanoseconds: the device's operations, the device's executable (module)
runs, and the host spans the benchmark opened with
``jax.profiler.TraceAnnotation``. It is built from the ``.xplane.pb`` the
JAX profiler writes (:meth:`TraceView.from_xspace`) or directly from lists
(the tests do). Everything is clipped to the host span named ``window``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW = "window"


@dataclasses.dataclass(frozen=True)
class Event:
    start: int          # ns
    end: int            # ns
    name: str           # op, module or span name
    module: str = ""    # executable the op ran in (device ops)
    kernel: str = ""    # Pallas kernel name (device ops), "" otherwise
    device: int = 0
    operands: tuple = ()  # a kernel call's (itemsize, shape) per operand
    results: tuple = ()   # ... and per result

    @property
    def dur(self) -> int:
        return self.end - self.start


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted [start, end) intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


_CALL_RE = re.compile(
    r"^%([\w.-]+) = (.*?) custom-call\(.*?custom_call_target="
    r'"tpu_custom_call", operand_layout_constraints=\{(.*?\})\}, '
    r'.*?"kernel":"([\w.]+)"', re.S)
_SHAPE_RE = re.compile(r"\b(bf16|f16|f32|f64|s8|u8|s16|s32|u32|s64|pred)"
                       r"\[([\d,]*)\]")
_ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s8": 1, "u8": 1,
             "s16": 2, "s32": 4, "u32": 4, "s64": 8, "pred": 1}


def _shapes(text: str) -> tuple:
    return tuple((_ITEMSIZE[t], tuple(int(x) for x in dims.split(",") if x))
                 for t, dims in _SHAPE_RE.findall(text))


def parse_op(text: str) -> Event:
    """An op of the trace's "XLA Ops" line, named by its HLO instruction
    text (``%name = <results> <opcode>(...)...``), as an Event without
    times: its instruction name and, for a Pallas kernel call, the kernel's
    name (from the ``kernel_metadata`` each ``pallas_call`` of the program
    carries) with its operands' and results' (itemsize, shape)."""
    m = _CALL_RE.match(text)
    if m:
        name, results, operands, kernel = m.groups()
        return Event(0, 0, name, kernel=kernel, operands=_shapes(operands),
                     results=_shapes(results))
    name = re.match(r"^%?([\w.-]+)", text)
    return Event(0, 0, name.group(1) if name else text)


class TraceView:
    def __init__(self, ops: list[Event], modules: list[Event],
                 spans: list[Event]):
        wins = [s for s in spans if s.name == WINDOW]
        if not wins:
            raise ValueError("the trace holds no host span named "
                             f"{WINDOW!r}")
        self.t0, self.t1 = wins[0].start, wins[0].end
        inside = lambda e: e.end > self.t0 and e.start < self.t1
        self.ops = [e for e in ops if inside(e)]
        self.modules = [e for e in modules if inside(e)]
        self.spans = [s for s in spans if inside(s) and s.name != WINDOW]

    # -- construction from the profiler's file -------------------------------

    @classmethod
    def from_xspace(cls, path: str) -> "TraceView":
        """Read the newest ``*.xplane.pb`` under ``path``: the "XLA Ops"
        and "XLA Modules" lines of each ``/device:TPU:<n>`` plane, and the
        benchmark's host spans. An op's executable is the module run its
        start falls in."""
        import bisect
        import jax
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        pd = jax.profiler.ProfileData.from_file(files[-1])
        ops, modules, spans = [], [], []
        for plane in pd.planes:
            m = re.match(r"/device:TPU:(\d+)$", plane.name)
            if m:
                dev = int(m.group(1))
                lines = {line.name: line for line in plane.lines}
                runs = sorted(
                    (int(ev.start_ns), int(ev.end_ns),
                     re.sub(r"\(\d+\)$", "", ev.name))
                    for ev in (lines["XLA Modules"].events
                               if "XLA Modules" in lines else ()))
                modules += [Event(a, b, n, device=dev) for a, b, n in runs]
                starts = [r[0] for r in runs]
                for ev in (lines["XLA Ops"].events
                           if "XLA Ops" in lines else ()):
                    a, b = int(ev.start_ns), int(ev.end_ns)
                    k = bisect.bisect_right(starts, a) - 1
                    module = runs[k][2] if k >= 0 and a < runs[k][1] else ""
                    ops.append(dataclasses.replace(
                        parse_op(ev.name), start=a, end=b, module=module,
                        device=dev))
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name in HOST_SPANS:
                            spans.append(Event(int(ev.start_ns),
                                               int(ev.end_ns), ev.name))
        return cls(ops, modules, spans)

    # -- reductions -----------------------------------------------------------

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy_intervals(self, device: int | None = None):
        return union(clip([(e.start, e.end) for e in self.ops
                           if device is None or e.device == device],
                          self.t0, self.t1))

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        devs = sorted({e.device for e in self.ops}) or [0]
        total = sum(e - s for d in devs
                    for s, e in self.busy_intervals(d))
        return total * 1e-9 / len(devs)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def idle_gaps(self, device: int = 0) -> list[tuple[str, float]]:
        """Every idle gap of ``device`` in the window, longest first, named
        by the innermost host span open at its middle ("none" if no span
        is)."""
        busy = self.busy_intervals(device)
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        gaps = []
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                mid = (s + e) // 2
                open_ = [sp for sp in self.spans if sp.start <= mid < sp.end]
                name = (min(open_, key=lambda sp: sp.dur).name
                        if open_ else "none")
                gaps.append((name, (e - s) * 1e-9))
        return sorted(gaps, key=lambda g: -g[1])

    def op_label(self, e: Event) -> str:
        return e.kernel or f"{e.module}:{_base(e.name)}"

    def top_ops(self, n: int = 10) -> list[tuple[str, float]]:
        """The device operations that took most time, by label. Loops and
        calls are left out: their spans hold the ops of their bodies."""
        tot: dict[str, int] = {}
        for e in self.ops:
            if _base(e.name) in CONTAINERS:
                continue
            s, t = max(e.start, self.t0), min(e.end, self.t1)
            tot[self.op_label(e)] = tot.get(self.op_label(e), 0) + (t - s)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [(k, v * 1e-9) for k, v in top]

    def kernel_ops(self, kernel: str) -> list[Event]:
        return [e for e in self.ops if e.kernel == kernel]

    def module_runs(self, fragment: str, device: int = 0) -> list[Event]:
        return [e for e in self.modules
                if fragment in e.name and e.device == device]

    def breakdown(self) -> dict:
        return {"device_ops": [[k, v] for k, v in self.top_ops(10)],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps()[:10]]}


def kernel_roofline(ctx: dict, kernel: str, cost_name: str):
    """Share (%) of the roofline reached by every traced call of ``kernel``:
    the least time of each call (the larger of its operations over the bf16
    peak and its bytes over the HBM bandwidth, from the call's own operand
    and result shapes) summed, over the time the calls took. None when the
    trace holds no call of it."""
    ops = ctx["trace"].kernel_ops(kernel)
    if not ops:
        return None
    cost = ctx["kernel_cost"](cost_name).cost
    peaks = ctx["peaks"]
    least = took = 0.0
    for e in ops:
        c = cost(list(e.operands), list(e.results))
        least += max(c["flops"] / peaks["bf16_flops"],
                     c["bytes"] / peaks["hbm_bytes_per_s"])
        took += e.dur * 1e-9
    return 100.0 * least / took


def _base(name: str) -> str:
    """An op name without its instance number: ``fusion.123`` ->
    ``fusion``."""
    return re.sub(r"[.]\d+$", "", name)


# Ops whose span on the device holds the ops of their bodies.
CONTAINERS = ("while", "conditional", "call")
# Host spans the benchmark opens around its calls into the program.
HOST_SPANS = (WINDOW, "submit", "engine.step", "next_batch", "train_step",
              "wait")
