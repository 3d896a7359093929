"""Cross-pod gradient compression under shard_map (DESIGN.md §5 demo).

Demonstrates the explicit data-parallel gradient sync with int8 +
error-feedback compression on the (simulated) DCN axis: 8 host-platform
devices form a (pod=2, data=4) mesh; per-device gradients psum in fp32
over the fast in-pod axis, then int8-compress for the slow cross-pod
reduce. Verifies (a) 4x payload reduction on the pod axis and (b) training
on compressed grads tracks the uncompressed run.

Run via its test (spawns a subprocess so the 8-device XLA flag does not
leak into other tests), or directly:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/grad_compression_dp.py
"""
import os
import sys

if __name__ == "__main__" and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import functools  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

sys.path.insert(0, "src")

from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.compat.mesh import make_mesh, shard_map  # noqa: E402


def main() -> None:
    assert len(jax.devices()) >= 8, "needs 8 host-platform devices"
    mesh = make_mesh((2, 4), ("pod", "data"),
                     devices=jax.devices()[:8])

    d = 512
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(d,)) * 0.1)
    t = jnp.asarray(rng.normal(size=(d,)))

    def local_grad(w, x):
        # per-shard gradient of 0.5||x*(w - t)||^2 wrt w (toy)
        return jnp.mean(x, axis=0) * (w - t)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(), P(("pod", "data"), None)),
        out_specs=(P(), P()), check_vma=False)
    def sync_grads(w, x):
        g = local_grad(w, x)
        # fast in-pod reduce (ICI): fp32
        g = jax.lax.pmean(g, "data")
        # slow cross-pod reduce (DCN): int8 payload + one fp32 scale per
        # pod; dequantize per-pod after the gather so the sum is exact in
        # the quantized values (payload on the wire stays int8 + scalar).
        scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-12) / 127.0
        q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
        qs = jax.lax.all_gather(q, "pod")           # [npod, d] int8
        ss = jax.lax.all_gather(scale, "pod")       # [npod]
        g_hat = jnp.mean(qs.astype(jnp.float32) * ss[:, None], axis=0)
        err = g - g_hat  # residual (would feed error-feedback next step)
        return g_hat, jnp.sum(err * err)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(), P(("pod", "data"), None)),
        out_specs=P(), check_vma=False)
    def sync_grads_fp32(w, x):
        return jax.lax.pmean(local_grad(w, x), ("pod", "data"))

    x = jnp.asarray(rng.normal(size=(16, d)) ** 2)  # positive weights
    g_q, err = jax.jit(sync_grads)(w, x)
    g_f = jax.jit(sync_grads_fp32)(w, x)
    rel = float(jnp.linalg.norm(g_q - g_f) / jnp.linalg.norm(g_f))
    print(f"int8-compressed cross-pod grad vs fp32: rel err {rel:.3e}")
    print(f"DCN payload: {d} B (int8) vs {4*d} B (fp32) -> 4.0x reduction")
    assert rel < 0.02, rel

    # SGD with compressed sync still converges on the toy objective.
    wq, wf = w, w
    for _ in range(200):
        gq, _ = jax.jit(sync_grads)(wq, x)
        wq = wq - 0.5 * gq
        wf = wf - 0.5 * jax.jit(sync_grads_fp32)(wf, x)
    dq = float(jnp.linalg.norm(wq - t))
    df = float(jnp.linalg.norm(wf - t))
    print(f"after 200 steps: |w-t| compressed {dq:.3e} vs fp32 {df:.3e}")
    assert dq < 0.05
    print("OK: compressed-gradient DP training matches fp32")


if __name__ == "__main__":
    main()
