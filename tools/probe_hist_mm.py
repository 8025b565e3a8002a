"""Microbench: histogram-matmul formulations on TPU (dev tool).

Hypothesis: the vmapped per-tree [m, n] @ [n, dBc] batched-GEMM lowers
poorly at batch=chunk; flattening the tree axis into the GEMM M dimension
([T*m, n] @ [n, dBc]) should run near MXU speed.
"""
import os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import init_backend

platform = init_backend()["platform"]
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

n, dBc, m, T = 891, 1536, 128, 635
rng = np.random.default_rng(0)
Og = jnp.asarray(rng.normal(size=(n, dBc)).astype(np.float32))
slot = jnp.asarray(rng.integers(0, m, size=(T, n)))
w = jnp.asarray(rng.random((T, n)).astype(np.float32))


@jax.jit
def batched(slot, w):
    S = jax.nn.one_hot(slot, m, dtype=jnp.float32)         # [T, n, m]
    Sw = S * w[:, :, None]
    f = jax.vmap(lambda s: lax.dot_general(s, Og, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32))
    return f(Sw.transpose(0, 1, 2))                        # [T, m, dBc]


@jax.jit
def flat(slot, w):
    S = jax.nn.one_hot(slot, m, dtype=jnp.float32)         # [T, n, m]
    Sw = (S * w[:, :, None]).transpose(0, 2, 1).reshape(T * m, n)
    return (Sw @ Og).reshape(T, m, dBc)


@jax.jit
def flat_bf16(slot, w):
    S = jax.nn.one_hot(slot, m, dtype=jnp.bfloat16)
    Sw = (S * w.astype(jnp.bfloat16)[:, :, None]).transpose(0, 2, 1).reshape(T * m, n)
    return lax.dot_general(Sw, Og.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32).reshape(T, m, dBc)


@jax.jit
def onehot_only(slot, w):
    S = jax.nn.one_hot(slot, m, dtype=jnp.float32)
    return (S * w[:, :, None]).sum()


results = {}


def timed(name, fn, reps=10):
    fn(slot, w).block_until_ready()
    outs = []
    t0 = time.perf_counter()
    for r in range(reps):
        outs.append(fn(slot + 0 * r, w + 1e-7 * r))
    jax.block_until_ready(outs[-1])
    dt = (time.perf_counter() - t0) / reps
    gf = 2 * T * m * n * dBc / 1e9
    print(f"{name:16s} {dt*1e3:8.2f} ms   ({gf/dt/1e3:6.2f} TFLOP/s)")
    results[name] = {"ms": round(dt * 1e3, 4),
                     "tflops": round(gf / dt / 1e3, 4)}


timed("batched-gemm", batched)
timed("flat-gemm", flat)
timed("flat-bf16", flat_bf16)
timed("onehot-only", onehot_only)

from transmogrifai_tpu import obs  # noqa: E402

obs.write_record("probe_hist_mm", extra={"report": {
    "metric": "hist_matmul_tflops", "platform": platform,
    "value": results["flat-gemm"]["tflops"],
    "shape": {"n": n, "dBc": dBc, "m": m, "T": T}, "cases": results}})
