"""Isolated net-kernel timing at a given scale with variants."""
import os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax, jax.numpy as jnp, numpy as np
from bfs_tpu.bench import load_or_build, load_or_build_relay
from bfs_tpu.ops import relay_pallas as RP

scale = int(os.environ.get("P_SCALE", "20"))
ef = int(os.environ.get("P_EF", "16"))
dg, source = load_or_build(scale, ef, 42, 8192, "native")
rg, _ = load_or_build_relay(dg, f"native_s{scale}_ef{ef}_seed42_block8192")
K = int(os.environ.get("P_K", "16"))
OPTS = {"xla_tpu_scoped_vmem_limit_kib": "65536"}

net_static = RP.pass_static(rg.net_table, rg.net_size)
arrays = [jnp.asarray(a) for a in RP.prepare_pass_masks(rg.net_masks, rg.net_table, rg.net_size)]
print("passes:", [(m[0], len(m[3])) for m in net_static], "mask MB", rg.net_masks.nbytes/1e6)

def bench(fn, args, label):
    f = jax.jit(fn)
    c = f.lower(*args).compile(compiler_options=OPTS)
    r = c(*args); _ = np.asarray(jax.device_get(r)).ravel()[0]
    ts=[]
    for _ in range(3):
        t0=time.perf_counter(); r=c(*args); _ = np.asarray(jax.device_get(r)).ravel()[0]
        ts.append(time.perf_counter()-t0)
    t=(min(ts)-0.107)/K
    bw = rg.net_masks.nbytes/t/1e9
    print(f"{label:24s}: {t*1000:7.2f} ms/iter  ({bw:.0f} GB/s mask stream)")

x0 = jnp.zeros(rg.net_size // 32, jnp.uint32)

def k_full(x, *m):
    def body(i, x):
        return RP.apply_benes_fused(x, m, net_static, rg.net_size) ^ (x & 1)
    return jax.lax.fori_loop(0, K, body, x)
bench(k_full, (x0, *arrays), "all passes")

# each pass alone
for pi, (ps, arr) in enumerate(zip(net_static, arrays)):
    def k_pass(x, m, ps=ps):
        def body(i, x):
            return RP._run_pass(x, m, ps[0], ps[1], ps[2], ps[3], rg.net_size, False) ^ (x & 1)
        return jax.lax.fori_loop(0, K, body, x)
    bench(k_pass, (x0, arr), f"pass {pi} ({ps[0]}, {len(ps[3])} stages)")
