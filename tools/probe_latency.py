"""Measure the device round-trip latency floor on this backend (dev tool)."""
import os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import init_backend

platform = init_backend()["platform"]
import jax
import jax.numpy as jnp
import numpy as np

print("platform:", platform)
x = jnp.ones((891, 24), jnp.float32)


@jax.jit
def trivial(a):
    return a + 1.0


@jax.jit
def loop200(a):
    def body(i, s):
        return s + a.T @ a
    return jax.lax.fori_loop(0, 200, body, jnp.zeros((24, 24), jnp.float32))


@jax.jit
def loop2000(a):
    def body(i, s):
        return s + a.T @ a
    return jax.lax.fori_loop(0, 2000, body, jnp.zeros((24, 24), jnp.float32))


results = {}


def timed(name, fn, arg, reps=20):
    fn(arg).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        fn(arg).block_until_ready()
    dt = (time.perf_counter() - t0) / reps
    print(f"{name:24s} {dt*1e3:9.2f} ms")
    results[name] = round(dt * 1e3, 4)
    return dt


timed("trivial add", trivial, x)
timed("fori 200 matmul", loop200, x)
timed("fori 2000 matmul", loop2000, x)

# async pipelining: 10 trivial launches, one sync at the end
trivial(x).block_until_ready()
t0 = time.perf_counter()
outs = [trivial(x + i) for i in range(10)]
outs[-1].block_until_ready()
results["10 async trivial"] = round((time.perf_counter() - t0) * 1e3, 4)
print(f"{'10 async trivial':24s} {results['10 async trivial']:9.2f} ms total")

# host pull of a small array
y = trivial(x)
y.block_until_ready()
t0 = time.perf_counter()
for _ in range(20):
    np.asarray(y)
results["small pull (86KB)"] = round((time.perf_counter() - t0) / 20 * 1e3, 4)
print(f"{'small pull (86KB)':24s} {results['small pull (86KB)']:9.2f} ms")

# device_put of the same
arr = np.ones((891, 24), np.float32)
t0 = time.perf_counter()
for _ in range(20):
    jax.device_put(arr).block_until_ready()
results["device_put (86KB)"] = round((time.perf_counter() - t0) / 20 * 1e3, 4)
print(f"{'device_put (86KB)':24s} {results['device_put (86KB)']:9.2f} ms")

from transmogrifai_tpu import obs  # noqa: E402

obs.write_record("probe_latency", extra={"report": {
    "metric": "device_roundtrip_latency_ms", "platform": platform,
    "value": results["trivial add"], "cases_ms": results}})
