"""Fine-tuning traffic: the program's DoRA train step, as
``repro.launch.train`` runs it, fed batch after batch of the synthetic
stream for the whole window.

Set-up builds one object, the compiled step with its weights, adapters and
AdamW state, and drives it from the seed through its first three steps on
the window's own feed; those steps' losses, the first gradient (read back
from AdamW's first moment) and the adapters' change after three steps are
what the plain reference is compared with once the window has closed. The
window then keeps driving the same object, with up to ``in_flight`` steps
dispatched before the oldest one's loss is read back, so a pause of the
host shorter than the queued steps does not leave the chip idle.
"""
from __future__ import annotations

import collections
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

import gen
import harness as H
import model
import program
import reference as R

CHECK_STEPS = 3
# Leaves whose reference gradient is under this share of the median
# leaf's are nought to rounding: AdamW moves them by round-off alone.
NOUGHT = 1e-3


@jax.jit
def leaf_norms(tree):
    return jnp.stack([jnp.linalg.norm(x.astype(jnp.float32).ravel())
                      for _, x in model.adapter_leaves(tree)])


@jax.jit
def delta_norms(new, old):
    return jnp.stack([
        jnp.linalg.norm((a.astype(jnp.float32)
                         - b.astype(jnp.float32)).ravel())
        for (_, a), (_, b) in zip(model.adapter_leaves(new),
                                  model.adapter_leaves(old))])


@jax.jit
def _copy(tree):
    return jax.tree.map(lambda x: x + jnp.zeros((), x.dtype), tree)


def stream_of(d: model.Dims, traffic: dict, seed: int) -> gen.TrainStream:
    return gen.TrainStream(vocab=d.vocab, seq=traffic["seq"],
                           batch=traffic["batch"], seed=seed)


def device_batch(stream: gen.TrainStream, i: int) -> dict:
    return {k: jnp.asarray(v) for k, v in stream.batch_np(i).items()}


class Trainer:
    """The program's compiled train step with its state, from the seed."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        from repro.launch.steps import make_train_step
        from repro.optim import adamw_init
        self.d = model.dims(cfg)
        self.stream = stream_of(self.d, traffic, seed)
        mcfg = program.model_config(cfg)
        scfg = program.step_config(cfg, traffic)
        self.beta1 = scfg.optim.betas[0]
        self.params, (self.adapters,) = model.make_weights(self.d, seed, 1)
        self.opt = jax.jit(adamw_init)(self.adapters)
        self.batch = device_batch(self.stream, 0)
        step = jax.jit(make_train_step(mcfg, scfg, None,
                                       batch=traffic["batch"],
                                       seq=traffic["seq"]),
                       donate_argnums=(1, 2))
        self.compiled = step.lower(self.params, self.adapters, self.opt,
                                   self.batch).compile()
        self.steps = 0

    def step(self):
        """One step on the current batch; returns its metrics (not waited
        for)."""
        self.adapters, self.opt, metrics = self.compiled(
            self.params, self.adapters, self.opt, self.batch)
        self.steps += 1
        return metrics

    def next_batch(self):
        self.batch = device_batch(self.stream, self.steps)

    def first_steps(self) -> dict:
        """Steps 1-3 through the window's own call and feed: each loss, the
        first gradient's per-leaf norms as AdamW took it (its first moment
        over 1 - beta1), and the per-leaf norms of the adapters' change
        after three steps."""
        start = _copy(self.adapters)
        losses = []
        for i in range(CHECK_STEPS):
            metrics = self.step()
            self.next_batch()
            losses.append(float(metrics["loss"]))
            if i == 0:
                grad = np.asarray(leaf_norms(self.opt["mu"])) / (
                    1.0 - self.beta1)
        delta = np.asarray(delta_norms(self.adapters, start))
        return {"loss": losses, "grad": grad, "delta": delta}

    def free(self):
        for k in ("params", "adapters", "opt", "batch", "compiled"):
            setattr(self, k, None)
        gc.collect()


def reference_readings(cfg: dict, traffic: dict, seed: int,
                       quant=None, fault=None) -> dict:
    """The same three readings from the plain reference (``quant=None``),
    from its control in lower precision (``quant="fp8"``), or with a fault
    planted in it (``fault="half_tokens"``: the loss over every second
    loss token)."""
    d = model.dims(cfg)
    stream = stream_of(d, traffic, seed)
    params, (start,) = model.make_weights(d, seed, 1)
    a32, moments = R.start_training(start)
    opt_items = tuple(sorted(traffic["optimizer"].items()))
    losses = []
    for i in range(CHECK_STEPS):
        b = stream.batch_np(i)
        a32, moments, loss, grads = R.train_step(
            d, a32, moments, params, jnp.asarray(b["tokens"]),
            jnp.asarray(b["labels"]), traffic["loss_tokens"], opt_items,
            quant, 2 if fault == "half_tokens" else 1)
        losses.append(float(loss))
        if i == 0:
            grad = np.asarray(leaf_norms(grads))
        del grads
    delta = np.asarray(delta_norms(a32, start))
    del params, start, a32, moments
    gc.collect()
    return {"loss": losses, "grad": grad, "delta": delta}


def compare(got: dict, ref: dict) -> dict[str, float]:
    """Gaps between two sets of readings. Losses: the largest relative gap
    of the three. Gradient and change: the worst leaf's gap between the two
    norms, over the larger of the reference leaf's norm and the median
    leaf's; leaves whose reference gradient is nought to rounding are left
    out."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(got["loss"], ref["loss"]))
    g_ref = ref["grad"]
    keep = g_ref >= NOUGHT * np.median(g_ref)

    def worst(a, b):
        a, b = a[keep], b[keep]
        scale = np.maximum(b, np.median(b))
        return float(np.max(np.abs(a - b) / scale))

    return {"loss_gap": float(loss),
            "grad_gap": worst(got["grad"], g_ref),
            "update_gap": worst(got["delta"], ref["delta"])}


def run(cell: H.Cell) -> H.Outcome:
    d = model.dims(cell.config)
    tr = cell.traffic
    compiles = H.CompileCounter()
    trainer = Trainer(cell.config, tr, cell.seed)
    got = trainer.first_steps()
    flops = model.train_flops_per_step(d, batch=tr["batch"], seq=tr["seq"],
                                       loss_tokens=tr["loss_tokens"])
    before = compiles.n
    traced: dict = {}
    losses = []
    with H.profiled(cell.trace, traced), H.HostWatch() as host, \
            H.span("window"):
        t0 = time.perf_counter()
        setup_s = t0 - cell.t_start
        pending = collections.deque()
        while True:
            with H.span("train_step"):
                pending.append(trainer.step())
            if len(pending) >= tr["in_flight"]:
                with H.span("wait"):
                    losses.append(float(pending.popleft()["loss"]))
            if time.perf_counter() - t0 >= cell.seconds:
                break
            with H.span("next_batch"):
                trainer.next_batch()
        with H.span("wait"):
            losses.extend(float(m["loss"]) for m in pending)
        t1 = time.perf_counter()
    n_steps = len(losses)
    tokens_per_s = n_steps * tr["batch"] * tr["seq"] / (t1 - t0)
    peak = H.memory_peak_bytes(cell.workload["chips"])
    in_window = compiles.n - before
    trainer.free()
    del trainer, pending
    gc.collect()

    ref = reference_readings(cell.config, tr, cell.seed)
    gaps = compare(got, ref)
    checks = [H.Check(k, gaps[k], v) for k, v in cell.limits.items()]
    notes = [f"train: {n_steps} steps in {t1 - t0:.3f}s of window, "
             f"{tokens_per_s:.1f} tokens/s, set-up {setup_s:.3f}s, "
             f"compiles inside the window: {in_window}", host.note(),
             f"train: program losses {got['loss']}, reference "
             f"{ref['loss']} (loss_gap {gaps['loss_gap']!r}, not compared)"]
    finite = bool(np.all(np.isfinite(losses)))
    return H.Outcome(
        attempted=n_steps, failed=0 if finite else n_steps,
        e2e={"train_tokens_per_s": tokens_per_s, "setup_s": setup_s},
        checks=checks, memory_peak_bytes=peak,
        context={"dims": d, "train_tokens_per_s": tokens_per_s,
                 "flops_per_step": flops, "steps": n_steps},
        trace=traced.get("trace"), correct_extra=finite,
        notes=notes)
