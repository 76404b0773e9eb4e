"""Round-3 device microbench: program overhead, gather/scatter rates."""
import os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax, jax.numpy as jnp, numpy as np
from bfs_tpu.config import enable_compile_cache

enable_compile_cache()
dev = jax.devices()[0]
print("device", dev)

def timeit(fn, n=20):
    fn()  # warm
    _ = int(jax.device_get(fn())[0]) if hasattr(fn(), '__getitem__') else None
    ts=[]
    for _ in range(n):
        t0=time.perf_counter(); r=fn(); v=np.asarray(jax.device_get(r)).ravel()[0]; ts.append(time.perf_counter()-t0)
    return float(np.median(ts))

# 1. fixed program overhead: trivial program
@jax.jit
def trivial(x): return x + 1
x0 = jnp.zeros((8,), jnp.int32)
t = timeit(lambda: trivial(x0))
print(f"trivial program round-trip: {t*1000:.1f} ms")

# small while_loop program (6 iterations of tiny work) - mimics bfs structure
@jax.jit
def loop6(x):
    def body(c):
        i, x = c
        return i+1, x*2+1
    return jax.lax.while_loop(lambda c: c[0]<6, body, (0, x))[1]
t = timeit(lambda: loop6(x0))
print(f"6-iter while_loop round-trip: {t*1000:.1f} ms")

# 2. gather rates at various sizes
V = 1<<24
table = jnp.arange(V, dtype=jnp.int32)
for sz in [1<<15, 1<<17, 1<<19, 1<<21, 1<<23]:
    idx = jnp.asarray(np.random.default_rng(0).integers(0, V, sz).astype(np.int32))
    @jax.jit
    def g(idx):
        # loop K times to amortize: chain to prevent CSE
        def body(i, acc):
            return acc + table[(idx + acc[0]) & (V-1)].sum()//jnp.int32(1<<30)
        K=8
        acc = jnp.zeros((1,), jnp.int32)
        for _ in range(K): acc = acc + table[(idx + acc[0]) & (V-1)][:8]
        return acc
    t = timeit(lambda: g(idx), n=8)
    rate = 8*sz/ t / 1e9
    print(f"gather {sz>>10}K elems x8: {t*1000:.1f} ms -> {rate:.3f} G/s")

# 3. scatter-min rate
for sz in [1<<17, 1<<21]:
    idx = jnp.asarray(np.random.default_rng(1).integers(0, V, sz).astype(np.int32))
    vals = jnp.asarray(np.random.default_rng(2).integers(0, 1<<30, sz).astype(np.int32))
    @jax.jit
    def s(idx, vals):
        out = jnp.full((V,), np.int32(2**31-1))
        for k in range(4):
            out = out.at[(idx+k) & (V-1)].min(vals)
        return out[:8]
    t = timeit(lambda: s(idx, vals), n=8)
    print(f"scatter-min {sz>>10}K x4: {t*1000:.1f} ms -> {4*sz/t/1e9:.3f} G/s")

# 4. dense V-sized pass (nonzero-style cumsum) cost
big = jnp.zeros((1<<24,), jnp.uint8)
@jax.jit
def scan_cost(b):
    c = jnp.cumsum(b.astype(jnp.int32))
    return c[-8:]
t = timeit(lambda: scan_cost(big), n=8)
print(f"cumsum over 2^24: {t*1000:.1f} ms")
