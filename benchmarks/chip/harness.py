"""What every cell shares: finding its files by name, the device gate, the
compile cache, the host spans, the compile counter and the result line."""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import sys
import threading
import time
from typing import Any

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class NoDevice(RuntimeError):
    """The cell's chips are not there."""


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def data_file(kind: str, name: str) -> dict:
    """``<kind>/<name>.json`` under this directory (configs, traffic,
    limits)."""
    return json.loads((HERE / kind / f"{name}.json").read_text())


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` under this directory, by path: drivers by traffic
    kind, per-layer metric readers and kernel cost functions by name."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchchip_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, workload: str) -> tuple[list, list]:
    """(end-to-end, per-layer) metric entries this cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return e2e, per_layer


@dataclasses.dataclass
class Cell:
    """One run of one cell: its entries and files, the seed and window."""
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    peaks: dict
    t_start: float            # perf_counter at process start (set-up)


@dataclasses.dataclass
class Check:
    """One number compared with its limit: correct while value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to ``run.py``."""
    attempted: int
    failed: int
    e2e: dict[str, float]
    checks: list[Check]
    memory_peak_bytes: int
    context: dict[str, Any]           # what the per-layer readers read
    trace: Any = None                 # tracing.TraceView of --trace 1
    correct_extra: bool = True        # False: a run fault besides checks
    notes: list[str] = dataclasses.field(default_factory=list)

    @property
    def correct(self) -> bool:
        return self.correct_extra and all(c.ok for c in self.checks)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_gate(chips: int) -> dict:
    """The device description of the cell's chips; raises
    :class:`NoDevice` unless the first device is a TPU and there are
    ``chips`` of them."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"benchmark: JAX's first device is on platform "
                       f"{devices[0].platform!r}, not 'tpu'; no result")
    if len(devices) < chips:
        raise NoDevice(f"benchmark: the cell needs {chips} chips, JAX "
                       f"finds {len(devices)}; no result")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": chips}


def memory_peak_bytes(chips: int) -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


class CompileCounter:
    """Counts XLA backend compiles (persistent-cache hits are not
    compiles)."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0

        def listen(event, duration, **kw):
            if event == self.EVENT:
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


class HostWatch:
    """What stopped the host during the window, read beside the metrics.

    A thread sleeps ``TICK_S`` at a time and notes how late it wakes: the
    main thread waiting on the device releases the interpreter, so a late
    wake-up means the whole process was held (descheduled, or the
    interpreter held by one long call). Python's collections are timed
    apart, so that a pause of the collector is told from one of the
    machine."""
    TICK_S = 0.02

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.late: list[tuple[float, float]] = []    # (at, late) over 0.1 s
        self.longest = (0.0, 0.0)
        self.collections: list[tuple[int, float]] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._tick, daemon=True)
        self._thread.start()
        gc.callbacks.append(self._collect)
        return self

    def _tick(self):
        while not self._stop.is_set():
            t = time.perf_counter()
            time.sleep(self.TICK_S)
            late = time.perf_counter() - t - self.TICK_S
            at = t - self.t0
            if late > self.longest[1]:
                self.longest = (at, late)
            if late > 0.1:
                self.late.append((at, late))

    def _collect(self, phase, info):
        if phase == "start":
            self._gc_t = time.perf_counter()
        else:
            self.collections.append((info["generation"],
                                     time.perf_counter() - self._gc_t))

    def __exit__(self, *exc):
        gc.callbacks.remove(self._collect)
        self._stop.set()
        self._thread.join()
        return False

    def note(self) -> str:
        at, late = self.longest
        gc_max = max((p for _, p in self.collections), default=0.0)
        return (f"host: a {self.TICK_S * 1e3:.0f} ms timer woke at most "
                f"{late * 1e3:.3f} ms late ({at:.3f}s in); "
                f"{len(self.late)} wake-ups over 100 ms late "
                f"{[(round(a, 3), round(l, 3)) for a, l in self.late[:8]]}; "
                f"{len(self.collections)} collections "
                f"({sum(g == 2 for g, _ in self.collections)} full), longest "
                f"{gc_max * 1e3:.3f} ms")


def span(name: str):
    """A host span on the profiler's clock (a no-op unless tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def profiled(enabled: bool, out: dict):
    """Trace the body with the JAX profiler when ``enabled``; on exit
    ``out["trace"]`` holds its :class:`tracing.TraceView`."""
    if not enabled:
        yield
        return
    import shutil
    import tempfile
    import jax
    from tracing import TraceView
    path = tempfile.mkdtemp(prefix="bench_chip_trace_")
    jax.profiler.start_trace(path)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        try:
            out["trace"] = TraceView.from_xspace(path)
        finally:
            shutil.rmtree(path, ignore_errors=True)


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: list[Check],
                breakdown: dict | None = None) -> str:
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in checks}
    return json.dumps(line)
