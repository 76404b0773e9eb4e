"""Per-phase timing of the v4 relay superstep on the real TPU.

Thin CLI over the shared phase ledger (bfs_tpu/profiling.py — the same
phase-isolated K-loop jits the bench ships as details.superstep_phases):
vperm / broadcast / net-apply / masked row-min / state-update (both
layouts, with the analytic dist/parent byte halving) + the full dense
superstep cross-check.  P_SCALE / P_EF select the cached bench graph.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax


from bfs_tpu.bench import load_or_build, load_or_build_relay
from bfs_tpu.models.bfs import RelayEngine
from bfs_tpu.profiling import superstep_phase_ledger

scale = int(os.environ.get("P_SCALE", "20"))
ef = int(os.environ.get("P_EF", "16"))
loops = int(os.environ.get("P_LOOPS", "16"))
dg, source = load_or_build(scale, ef, 42, 8192, "native")
key = f"native_s{scale}_ef{ef}_seed42_block8192"
rg, _ = load_or_build_relay(dg, key)
eng = RelayEngine(rg)

ledger = superstep_phase_ledger(eng, loops=loops, repeats=3)
for name, ph in ledger["phases"].items():
    print(f"{name:16s}: {ph['seconds'] * 1e3:8.2f} ms/superstep")
su = ledger["phases"]["state_update"]
print(
    f"state update packed {su['packed']['seconds'] * 1e3:.2f} ms "
    f"({su['packed']['bytes']['total'] >> 20} MB) vs unpacked "
    f"{su['unpacked']['seconds'] * 1e3:.2f} ms "
    f"({su['unpacked']['bytes']['total'] >> 20} MB) — dist/parent bytes "
    f"ratio {su['dist_parent_bytes_ratio']:.1f}x"
)
print(
    f"sum of phases {ledger['sum_of_phases_seconds'] * 1e3:.2f} ms vs "
    f"full superstep {ledger['full_superstep_seconds'] * 1e3:.2f} ms"
)
print(json.dumps(ledger))
