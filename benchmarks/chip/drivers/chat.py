"""Open-loop multi-tenant chat traffic through the program's serving engine.

Set-up makes the weights and every tenant's adapters from the seed,
registers the tenants with ``AdapterStateCache`` and precomputes their
serving states, builds ``EngineServer`` over the paged engine, and serves a
fixed warm-up set that compiles every executable the window uses. The
window then offers the schedule of :func:`gen.chat_schedule`: each request
is submitted between engine ticks once it is due, and timed from when it
was due. After the window, every request due in it is driven to its end.

Once the program's state is freed, a sample of the finished requests drawn
from the seed, with the longest output and the longest prompt (prefilled in
the most chunks) among them, is run through the plain
reference, and the widest gap by which a served token's logit lies below
the reference's best is compared with its limit.
"""
from __future__ import annotations

import collections
import gc
import time

import numpy as np

import gen
import harness as H
import model
import program
import reference as R

FINISHED_OK = ("eos", "length")


def tenant_name(t: int) -> str:
    return f"tenant-{t}"


class Server:
    """The program's serving stack for one seed: weights, tenants, adapter
    cache and engine."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        from repro.core import AdapterStateCache
        from repro.launch.serve import EngineServer
        import jax
        d = model.dims(cfg)
        mcfg = program.model_config(cfg)
        scfg = program.step_config(cfg)
        params, tenants = model.make_weights(d, seed, traffic["tenants"])
        self.cache = AdapterStateCache.for_serving(mcfg, scfg)
        for t, ad in enumerate(tenants):
            self.cache.register(tenant_name(t), ad)
        for t in range(traffic["tenants"]):
            jax.block_until_ready(self.cache.get_state(
                params, self.cache.current_handle(tenant_name(t))))
        self.server = EngineServer(
            mcfg, scfg, params, cache=self.cache, slots=traffic["slots"],
            max_len=traffic["max_len"], paged=True,
            block_size=traffic["block_size"],
            prefill_chunk=traffic["prefill_chunk"],
            dynamic_grouping=traffic["dynamic_grouping"])
        self.engine = self.server.engine
        self.params = params

    def warm_up(self, traffic: dict, vocab: int, seed: int) -> None:
        """Serve the fixed warm-up set: every prompt length in
        ``warmup_prompts`` under every tenant, a few tokens each, so each
        executable the window uses is compiled and each tenant has been
        inserted into the engine's adapter stack once."""
        rng = gen.rng_for(seed, 3)
        for t in range(traffic["tenants"]):
            for n in traffic["warmup_prompts"]:
                self.engine.submit(rng.integers(0, vocab, n, dtype=np.int32),
                                   adapter=tenant_name(t),
                                   max_new_tokens=traffic["warmup_tokens"])
        while self.engine.has_work():
            self.engine.step()
        self.engine.pop_results()

    def free(self) -> None:
        self.server = self.engine = self.cache = self.params = None
        gc.collect()


def serve_window(srv: Server, schedule: list, ticks: list | None = None):
    """Offer ``schedule`` open loop; returns (token times per request index,
    results per request index, generator lateness per request, window
    start, window end). With ``ticks``, each engine tick appends the
    blocks each slot holds before and after it, for the paged gather's
    roofline."""
    eng = srv.engine
    times = collections.defaultdict(list)
    index_of: dict[int, int] = {}
    late = []
    on_token = lambda rid, tok: times[index_of[rid]].append(
        time.perf_counter())
    results = {}
    n = len(schedule)
    i = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        while i < n and schedule[i].due_s <= now:
            req = schedule[i]
            with H.span("submit"):
                rid = eng.submit(req.prompt, adapter=tenant_name(req.tenant),
                                 max_new_tokens=req.max_new_tokens)
            index_of[rid] = i
            late.append(time.perf_counter() - t0 - req.due_s)
            i += 1
        if eng.has_work():
            if ticks is not None:
                pre = eng.pool_stats()["per_slot_blocks"]
            with H.span("engine.step"):
                for r in eng.step(on_token):
                    results[index_of[r.request_id]] = r
            if ticks is not None:
                ticks.append((pre, eng.pool_stats()["per_slot_blocks"]))
        elif i < n:
            time.sleep(max(0.0, min(1e-3, schedule[i].due_s - now)))
        else:
            break
    t1 = time.perf_counter()
    return times, results, late, t0, t1


def latencies(schedule, times, results, t0):
    """(time to first token of each request in ms, every gap between
    consecutive tokens in ms); a request that failed or never produced a
    token has an infinite time to first token."""
    ttft, itl = [], []
    for req in schedule:
        ts = times.get(req.index, [])
        ok = (req.index in results
              and results[req.index].finish_reason in FINISHED_OK and ts)
        ttft.append((ts[0] - (t0 + req.due_s)) * 1e3 if ok else np.inf)
        itl.extend(np.diff(ts) * 1e3)
    return np.asarray(ttft), np.asarray(itl)


def pick_sample(schedule, results, traffic: dict, seed: int) -> list[int]:
    """Request indices to check: the one with the most served tokens, the
    one with the longest prompt (prefilled in the most chunks), then others
    drawn from the seed, ``check_requests`` in all."""
    done = sorted(i for i, r in results.items()
                  if r.finish_reason in FINISHED_OK)
    if not done:
        return []
    first = [max(done, key=lambda i: len(results[i].tokens)),
             max(done, key=lambda i: len(schedule[i].prompt))]
    first = first[:1] if first[0] == first[1] else first
    rest = [done[k] for k in gen.rng_for(seed, 4).permutation(len(done))
            if done[k] not in first]
    return (first + rest)[:traffic["check_requests"]]


def reference_gaps(cfg: dict, traffic: dict, seed: int, checks: list,
                   quant=None):
    """Per checked request, (tenant, prompt, served tokens): the reference's
    gaps of the served tokens (and of ``quant``'s first choices)."""
    d = model.dims(cfg)
    params, tenants = model.make_weights(d, seed, traffic["tenants"])
    served, low = [], []
    for tenant, prompt, toks in checks:
        g, gl = R.served_gaps(d, params, tenants[tenant], prompt, toks,
                              pad_to=traffic["max_len"], quant=quant)
        served.append(g)
        if gl is not None:
            low.append(gl)
    del params, tenants
    gc.collect()
    return (np.concatenate(served),
            np.concatenate(low) if low else None)


def run(cell: H.Cell) -> H.Outcome:
    d = model.dims(cell.config)
    tr = cell.traffic
    compiles = H.CompileCounter()
    srv = Server(cell.config, tr, cell.seed)
    srv.warm_up(tr, d.vocab, cell.seed)
    schedule = gen.chat_schedule(tr, seed=cell.seed, seconds=cell.seconds,
                                 vocab=d.vocab)
    counts_before = srv.engine.compile_counts()
    before = compiles.n
    traced: dict = {}
    ticks = [] if cell.trace else None
    with H.profiled(cell.trace, traced), H.HostWatch() as host:
        setup_s = time.perf_counter() - cell.t_start
        with H.span("window"):
            times, results, late, t0, t1 = serve_window(
                srv, schedule, ticks)
    in_window = compiles.n - before
    counts_after = srv.engine.compile_counts()
    stats = srv.engine.stats()
    peak = H.memory_peak_bytes(cell.workload["chips"])
    ttft, itl = latencies(schedule, times, results, t0)
    failed = int(np.sum(~np.isfinite(ttft)))
    sample = pick_sample(schedule, results, tr, cell.seed)
    checks_in = [(schedule[i].tenant, schedule[i].prompt,
                  np.asarray(results[i].tokens)) for i in sample]
    srv.free()
    del srv
    gc.collect()

    gaps = (reference_gaps(cell.config, tr, cell.seed, checks_in)[0]
            if checks_in else np.asarray([np.inf]))
    checks = [H.Check("widest_gap", float(np.max(gaps)),
                      cell.limits["widest_gap"])]
    late = np.asarray(late)
    prompts = [len(schedule[i].prompt) for i in sample]
    notes = [
        f"chat: {len(schedule)} requests due in {cell.seconds}s at "
        f"{tr['rate']}/s, {failed} failed; served for {t1 - t0:.3f}s; "
        f"set-up {setup_s:.3f}s",
        f"chat: generator lateness p50 {np.median(late) * 1e3:.3f} ms, max "
        f"{late.max() * 1e3:.3f} ms",
        f"chat: engine {stats.decode_steps} decode ticks, occupancy "
        f"{stats.mean_occupancy:.3f}, {stats.generated_tokens} tokens",
        f"chat: compiles inside the window: {in_window}; executables "
        f"before {counts_before}, after {counts_after}", host.note(),
        f"chat: checked {len(sample)} requests (prompts {prompts}), "
        f"{gaps.size} served tokens against the reference",
        f"chat: time to first token p50 {gen.percentile(ttft, 50):.3f} ms, "
        f"p90 {gen.percentile(ttft, 90):.3f} ms (not bounded); token gaps "
        f"p50 {gen.percentile(itl, 50):.3f} ms"]
    return H.Outcome(
        attempted=len(schedule), failed=failed,
        e2e={"itl_p99_ms": gen.percentile(itl, 99), "setup_s": setup_s},
        checks=checks, memory_peak_bytes=peak,
        context={"dims": d, "traffic": tr, "ticks": ticks},
        trace=traced.get("trace"), correct_extra=failed == 0, notes=notes)
