"""Per-phase on-chip superstep ledger: attribute the non-mask residual.

Round 5 left the headline at ~47% of the repo's own mask-stream roofline
with a ~6.8 ms/superstep residual that no capture could attribute — the
superstep profile times WHOLE supersteps only (VERDICT r5 weak #5, task
#4).  This module decomposes one dense relay superstep into its five
phases and times each as an ISOLATED K-loop jit over the engine's real
device operands, so the residual is measured, not guessed:

    vperm         frontier words through the small Beneš network
    broadcast     vperm output words -> L2 slot words (class replication)
    net_apply     L2 -> L1 through the big Beneš network (the mask stream)
    rowmin        masked per-class row-min tournament over L1 slots
    state_update  candidate merge into the dist/parent carry + frontier
                  repack — timed in BOTH layouts (packed fused-word vs
                  unpacked int32 pair) with analytic byte accounting, the
                  before/after evidence for the packed-state tentpole

plus the full dense superstep for cross-checking (``sum_of_phases`` vs
``full_superstep``).  Every K-loop body feeds its output back into its
input (xor) so XLA cannot hoist the work out of the loop, and the K / 2K
timing difference cancels dispatch + sync overhead — the same
methodology as the applier probe (models/bfs.py).

The ledger is CPU-runnable (tests and ``python -m bfs_tpu.profiling``
run it on a small R-MAT without any TPU) and ships in the bench headline
as ``details.superstep_phases``.

Analytic bytes are the MINIMUM HBM traffic of each phase (operands read
once + outputs written once); a measured phase time far above
``bytes / available_bandwidth`` marks compute- or layout-bound work.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "superstep_phase_ledger",
    "state_update_bytes",
    "probe_phase_kernels",
]


def state_update_bytes(vr: int, packed: bool) -> dict:
    """Analytic per-superstep HBM bytes of the state-update phase.

    The dist/parent carry term — the tentpole's target — is 8 bytes/vertex
    (one uint32 read + one written) packed vs 16 (two int32s each way)
    unpacked: exactly halved.  The candidate read and frontier-word write
    are layout-independent."""
    word = 4 * vr if packed else 8 * vr
    return {
        "dist_parent_read": word,
        "dist_parent_written": word,
        "candidate_read": 4 * vr,
        "frontier_words_written": vr // 8,
        "total": 2 * word + 4 * vr + vr // 8,
    }


def _compile(fn, args, compiler_options):
    opts = compiler_options if jax.default_backend() == "tpu" else None
    return jax.jit(fn).lower(jnp.int32(1), *args).compile(
        compiler_options=opts
    )


def _sync(result):
    leaf = jax.tree_util.tree_leaves(result)[0]
    return int(np.asarray(jax.device_get(leaf.ravel()[:1]))[0])


def _measure(fn, args, loops: int, repeats: int, compiler_options) -> float:
    """Seconds per iteration of ``fn(k, *args)``'s K-loop: compile, warm
    BOTH loop counts, then (min-of-repeats at 2K) - (min-of-repeats at K)
    over K.  The min per count rejects one-off contamination (first-call
    cache effects, a tenant burst) that a paired-sample difference would
    fold straight into the result."""
    compiled = _compile(fn, args, compiler_options)

    def timed(k):
        t0 = time.perf_counter()
        _sync(compiled(jnp.int32(k), *args))
        return time.perf_counter() - t0

    timed(loops)
    timed(2 * loops)  # warm both counts
    r = max(repeats, 2)
    t1 = min(timed(loops) for _ in range(r))
    t2 = min(timed(2 * loops) for _ in range(r))
    return max(t2 - t1, 1e-9) / loops


def superstep_phase_ledger(eng, *, loops: int = 4, repeats: int = 2) -> dict:
    """Measure the per-phase superstep ledger on a RelayEngine's own
    device operands.  Returns a JSON-ready dict (the bench ships it as
    ``details.superstep_phases``)."""
    from .ops import relay as R

    rg = eng.relay_graph
    static = eng._static
    (vr, vperm_size, vperm_table, out_classes, out_space, net_table,
     net_size, in_classes) = static
    vperm_m, net_m, valid = eng._tensors
    opts = eng._COMPILER_OPTIONS
    vp_pallas = isinstance(vperm_m, tuple)
    net_pallas = isinstance(net_m, tuple)
    if vp_pallas or net_pallas:
        from .ops import relay_pallas as RP

        vp_static = RP.pass_static(vperm_table, vperm_size) if vp_pallas else None
        net_static = RP.pass_static(net_table, net_size) if net_pallas else None

    def mb(fn, args):
        return _measure(fn, args, loops, repeats, opts)

    phases: dict = {}

    # ---- vperm ------------------------------------------------------------
    def k_vperm(k, x, *m):
        def body(i, x):
            if vp_pallas:
                y = RP.apply_benes_fused(x, m, vp_static, vperm_size)
            else:
                y = R.apply_benes_std(x, m[0], vperm_table, vperm_size)
            return y ^ (x & jnp.uint32(1))

        return jax.lax.fori_loop(0, k, body, x)

    x_vp = jnp.zeros(vperm_size // 32, jnp.uint32).at[0].set(1)
    vp_args = (x_vp, *vperm_m) if vp_pallas else (x_vp, vperm_m)
    vperm_mask_bytes = int(rg.vperm_masks.nbytes)
    phases["vperm"] = {
        "seconds": mb(k_vperm, vp_args),
        "mask_bytes": vperm_mask_bytes,
        "word_bytes_rw": vperm_size // 8,
    }

    # ---- broadcast --------------------------------------------------------
    def k_bcast(k, y):
        # Feed only the overlapping prefix back: tiny layouts can have
        # net_size < vperm_size, where a full-width slice would overrun.
        w = min(y.shape[0], net_size // 32)

        def body(i, c):
            l2 = R.broadcast_l2(y ^ c, out_classes, net_size, out_space)
            bit = jax.lax.slice_in_dim(l2, 0, w) & jnp.uint32(1)
            pad = jnp.zeros(y.shape[0] - w, jnp.uint32)
            return c ^ jnp.concatenate([bit, pad])

        return jax.lax.fori_loop(0, k, body, jnp.zeros_like(y))

    phases["broadcast"] = {
        "seconds": mb(k_bcast, (x_vp,)),
        "word_bytes_rw": (vperm_size + net_size) // 8,
    }

    # ---- net apply (the mask stream) --------------------------------------
    def k_net(k, x, *m):
        def body(i, x):
            if net_pallas:
                y = RP.apply_benes_fused(x, m, net_static, net_size)
            else:
                y = R.apply_benes_std(x, m[0], net_table, net_size)
            return y ^ (x & jnp.uint32(1))

        return jax.lax.fori_loop(0, k, body, x)

    x_net = jnp.zeros(net_size // 32, jnp.uint32)
    net_args = (x_net, *net_m) if net_pallas else (x_net, net_m)
    net_mask_bytes = int(rg.net_masks.nbytes)
    phases["net_apply"] = {
        "seconds": mb(k_net, net_args),
        "mask_bytes": net_mask_bytes,
        "word_bytes_rw": net_size // 8,
    }

    # ---- masked row-min ----------------------------------------------------
    # Packed layouts measure BOTH implementations (ISSUE 7 tentpole b):
    # the XLA word tournament and the fused Pallas kernel — compiled on
    # TPU backends, interpret-mode elsewhere (a real if slow measurement,
    # so the verdict is always a comparison).  ``seconds`` reports the
    # arm the ENGINE actually selected (phase_selection), keeping the
    # before/after ledger comparable with what timed repeats ran.
    packed = bool(getattr(eng, "packed", False))
    sel = getattr(eng, "phase_selection", None) or {
        "rowmin": "xla", "state_update": "xla", "basis": {},
    }
    from .ops.relay_pallas import pallas_interpret

    interp = pallas_interpret()

    def k_rowmin_arm(use_pallas_arm):
        def k_rowmin(k, l1, vw):
            def body(i, c):
                lx = l1 ^ jax.lax.slice_in_dim(c, 0, l1.shape[0])
                if packed:
                    if use_pallas_arm:
                        from .ops import relay_pallas as RP

                        cand = RP.rowmin_ranks_pallas(
                            lx, vw, in_classes, vr, interpret=interp
                        )
                    else:
                        cand = R.rowmin_ranks(lx, vw, in_classes, vr)
                    bit = cand & jnp.uint32(1)
                else:
                    cand = R.rowmin_candidates(lx, vw, in_classes, vr)
                    bit = cand.astype(jnp.uint32) & jnp.uint32(1)
                w = max(l1.shape[0], vr)
                pad = jnp.zeros(w - vr, jnp.uint32)
                return c ^ jnp.concatenate([bit, pad])

            size = max(net_size // 32, vr)
            return jax.lax.fori_loop(0, k, body, jnp.zeros(size, jnp.uint32))

        return k_rowmin

    def _effective(arms: dict, wanted: str, basis: str):
        """(selected, basis, seconds) — if the engine's wanted arm has no
        measurement here (the pallas arm errored), the ledger must SAY
        the fallback happened, never attribute the other arm's seconds
        to the wanted one."""
        if wanted in arms:
            return wanted, basis, arms[wanted]
        return (
            "xla",
            f"fallback: {wanted} arm unmeasured "
            f"({arms.get('pallas_error', 'missing')})",
            arms["xla"],
        )

    rowmin_arms = {"xla": mb(k_rowmin_arm(False), (x_net, valid))}
    if packed:
        try:
            rowmin_arms["pallas"] = mb(k_rowmin_arm(True), (x_net, valid))
        except Exception as exc:
            rowmin_arms["pallas_error"] = repr(exc)
    rm_sel, rm_basis, rm_seconds = _effective(
        rowmin_arms,
        sel["rowmin"] if packed else "xla",
        sel.get("basis", {}).get("rowmin", "unpacked carry (no fused arm)"),
    )
    phases["rowmin"] = {
        "seconds": rm_seconds,
        "selected": rm_sel,
        "selection_basis": rm_basis,
        "arms": rowmin_arms,
        "interpret_arm": interp,
        "flavor": "ranks (packed)" if packed else "slots (unpacked)",
        "word_bytes_read": 2 * (net_size // 8),
        "candidate_bytes_written": 4 * vr,
    }

    # ---- state update: BOTH layouts (the tentpole's before/after) ----------
    def k_apply_packed(k, pk, fw, cand):
        st0 = R.PackedRelayState(pk, fw, jnp.int32(0), jnp.bool_(True))

        def body(i, st):
            s2 = R.apply_relay_candidates_packed(
                st, cand ^ (st.packed & jnp.uint32(1))
            )
            return R.PackedRelayState(
                s2.packed, s2.fwords, jnp.int32(0), s2.changed
            )

        return jax.lax.fori_loop(0, k, body, st0).packed

    def k_apply_unpacked(k, dist, parent, fw, cand):
        st0 = R.RelayState(dist, parent, fw, jnp.int32(0), jnp.bool_(True))

        def body(i, st):
            s2 = R.apply_relay_candidates(st, cand ^ (st.dist & 1))
            return R.RelayState(
                s2.dist, s2.parent, s2.fwords, jnp.int32(0), s2.changed
            )

        return jax.lax.fori_loop(0, k, body, st0).dist

    from .ops.packed import PACKED_SENTINEL

    fw0 = jnp.zeros(vr // 32, jnp.uint32)
    pk0 = jnp.full(vr, PACKED_SENTINEL, jnp.uint32)
    cand_r = jnp.full(vr, PACKED_SENTINEL, jnp.uint32).at[:64].set(
        jnp.arange(64, dtype=jnp.uint32)
    )
    d0 = jnp.full(vr, np.int32(2**31 - 1), jnp.int32)
    p0 = jnp.full(vr, -1, jnp.int32)
    cand_s = jnp.full(vr, np.int32(2**31 - 1), jnp.int32).at[:64].set(
        jnp.arange(64, dtype=jnp.int32)
    )
    def k_apply_packed_pallas(k, pk, fw, cand):
        from .ops import relay_pallas as RP

        st0 = R.PackedRelayState(pk, fw, jnp.int32(0), jnp.bool_(True))

        def body(i, st):
            s2 = RP.apply_relay_candidates_packed_pallas(
                st, cand ^ (st.packed & jnp.uint32(1)), interpret=interp
            )
            return R.PackedRelayState(
                s2.packed, s2.fwords, jnp.int32(0), s2.changed
            )

        return jax.lax.fori_loop(0, k, body, st0).packed

    t_packed = mb(k_apply_packed, (pk0, fw0, cand_r))
    t_unpacked = mb(k_apply_unpacked, (d0, p0, fw0, cand_s))
    update_arms = {"xla": t_packed}
    if packed:
        try:
            update_arms["pallas"] = mb(
                k_apply_packed_pallas, (pk0, fw0, cand_r)
            )
        except Exception as exc:
            update_arms["pallas_error"] = repr(exc)
    up_sel, up_basis, up_seconds = _effective(
        update_arms,
        sel["state_update"] if packed else "xla",
        sel.get("basis", {}).get(
            "state_update", "unpacked carry (no fused arm)"
        ),
    )
    phases["state_update"] = {
        "seconds": up_seconds if packed else t_unpacked,
        "selected": up_sel,
        "selection_basis": up_basis,
        "arms": update_arms,
        "interpret_arm": interp,
        "packed": {
            "seconds": t_packed, "bytes": state_update_bytes(vr, True),
        },
        "unpacked": {
            "seconds": t_unpacked, "bytes": state_update_bytes(vr, False),
        },
        "dist_parent_bytes_ratio": (
            state_update_bytes(vr, False)["dist_parent_written"]
            / state_update_bytes(vr, True)["dist_parent_written"]
        ),
    }

    # ---- expansion arms (ISSUE 15) -----------------------------------------
    # Present whenever the engine carries a tile layout: the gather
    # (Beneš) dense superstep vs the mxu tiled masked matmul, measured on
    # a pinned fully-dense frontier (the regime the direction optimizer
    # hands to the pull/expansion body).  ``seconds`` reports the arm the
    # engine actually runs, keeping the ledger comparable with the timed
    # repeats (the _effective contract above).
    if getattr(eng, "adj_tiles", None) is not None:
        try:
            exp = _expansion_arms(eng, mb)
        except Exception as exc:
            exp = {"probe_error": repr(exc), "arms": {}}
        eng_arm = getattr(eng, "expansion", "gather")
        if eng_arm in exp.get("arms", {}):
            exp["seconds"] = exp["arms"][eng_arm]
        exp["selected"] = eng_arm
        exp["selection_basis"] = getattr(eng, "expansion_basis", None)
        exp["interpret_arm"] = interp
        phases["expansion"] = exp

    # ---- full dense superstep (cross-check) --------------------------------
    from .models.bfs import _superstep_fn

    superstep = _superstep_fn(
        static, eng._use_pallas(), packed,
        eng._phase_sel() if hasattr(eng, "_phase_sel") else None,
    )
    flat_masks = []
    for m in (vperm_m, net_m):
        flat_masks.extend(m if isinstance(m, tuple) else (m,))
    n_vp = len(vperm_m) if isinstance(vperm_m, tuple) else 1

    def k_full(k, pk_or_d, maybe_p, fw, *ms):
        vm = ms[:n_vp] if isinstance(vperm_m, tuple) else ms[0]
        nm = ms[n_vp:-1] if isinstance(net_m, tuple) else ms[1]
        vw = ms[-1]
        if packed:
            st0 = R.PackedRelayState(
                pk_or_d, fw, jnp.int32(0), jnp.bool_(True)
            )

            def body(i, st):
                s2 = superstep(st, vm, nm, vw)
                return R.PackedRelayState(
                    s2.packed, s2.fwords, st.level, st.changed
                )

        else:
            st0 = R.RelayState(
                pk_or_d, maybe_p, fw, jnp.int32(0), jnp.bool_(True)
            )

            def body(i, st):
                s2 = superstep(st, vm, nm, vw)
                return R.RelayState(
                    s2.dist, s2.parent, s2.fwords, st.level, st.changed
                )

        return jax.lax.fori_loop(0, k, body, st0)

    fw_src = jnp.zeros(vr // 32, jnp.uint32).at[0].set(1)
    full_args = (pk0 if packed else d0, p0, fw_src, *flat_masks, valid)
    phases["full_superstep"] = {"seconds": mb(k_full, full_args)}

    # ---- full superstep + device telemetry (the OBS overhead arm) ----------
    # Same K-loop with the obs/telemetry level accumulator folded into the
    # carry (one popcount-sum + one 4-byte scatter-add per superstep): the
    # measured cost of carrying the level curve, shipped in every capture
    # next to the curve itself so "telemetry changes timed medians by <2%"
    # is a number, not a promise.
    from .obs import telemetry as T

    def k_full_tel(k, pk_or_d, maybe_p, fw, *ms):
        vm = ms[:n_vp] if isinstance(vperm_m, tuple) else ms[0]
        nm = ms[n_vp:-1] if isinstance(net_m, tuple) else ms[1]
        vw = ms[-1]
        acc0 = T.init_level_acc()
        if packed:
            st0 = R.PackedRelayState(
                pk_or_d, fw, jnp.int32(0), jnp.bool_(True)
            )

            def body(i, c):
                st, acc = c
                s2 = superstep(st, vm, nm, vw)
                acc = T.record_frontier_words(acc, s2.fwords, s2.level)
                return (
                    R.PackedRelayState(
                        s2.packed, s2.fwords, st.level, st.changed
                    ),
                    acc,
                )

        else:
            st0 = R.RelayState(
                pk_or_d, maybe_p, fw, jnp.int32(0), jnp.bool_(True)
            )

            def body(i, c):
                st, acc = c
                s2 = superstep(st, vm, nm, vw)
                acc = T.record_frontier_words(acc, s2.fwords, s2.level)
                return (
                    R.RelayState(
                        s2.dist, s2.parent, s2.fwords, st.level, st.changed
                    ),
                    acc,
                )

        return jax.lax.fori_loop(0, k, body, (st0, acc0))

    t_tel = mb(k_full_tel, full_args)
    phases["full_superstep_telemetry"] = {"seconds": t_tel}

    accounted = sum(
        phases[p]["seconds"]
        for p in ("vperm", "broadcast", "net_apply", "rowmin", "state_update")
    )
    return {
        "packed_state": packed,
        "applier": getattr(eng, "applier", "xla"),
        "loops": loops,
        "repeats": repeats,
        "device": str(jax.devices()[0]),
        "phases": phases,
        "sum_of_phases_seconds": accounted,
        "full_superstep_seconds": phases["full_superstep"]["seconds"],
        "telemetry_overhead_ratio": (
            phases["full_superstep_telemetry"]["seconds"]
            / max(phases["full_superstep"]["seconds"], 1e-12)
        ),
        "mask_bytes_total": vperm_mask_bytes + net_mask_bytes,
        "note": (
            "phase-isolated K-loop jits on the engine's real operands; "
            "K/2K timing difference cancels dispatch+sync; state_update "
            "reports BOTH layouts — dist/parent bytes halved packed"
        ),
    }


def _expansion_arms(eng, mb) -> dict:
    """K-loop both EXPANSION arms — the gather (Beneš relay) dense
    superstep vs the mxu tiled masked matmul — on the engine's real
    operands with a PINNED fully-dense frontier (the regime the arm
    targets; an evolving state would empty the frontier after one
    superstep and time the mxu early-out instead of the expand).  The
    packed-word feedback keeps XLA from hoisting either body."""
    import jax

    from .models.bfs import _superstep_fn
    from .ops import relay as R
    from .ops import relay_mxu as RM
    from .ops.packed import PACKED_SENTINEL

    packed = bool(getattr(eng, "packed", False))
    static = eng._static
    vr = static[0]
    superstep = _superstep_fn(
        static, eng._use_pallas(), packed,
        eng._phase_sel() if hasattr(eng, "_phase_sel") else None,
    )
    vperm_m, net_m, valid = eng._tensors
    geo = RM.mxu_static(eng.adj_tiles)
    use_kernel = RM.resolve_mxu_kernel() == "pallas"
    tile_ops = eng._mxu_ops()
    mxu_step = RM.mxu_superstep_packed if packed else RM.mxu_superstep

    nw = vr // 32
    fw_dense = jnp.full(nw, 0xFFFFFFFF, jnp.uint32)
    pk0 = jnp.full(vr, PACKED_SENTINEL, jnp.uint32)
    d0 = jnp.full(vr, np.int32(2**31 - 1), jnp.int32)
    p0 = jnp.full(vr, -1, jnp.int32)

    def feedback(st):
        word = st.packed if packed else st.dist.astype(jnp.uint32)
        return fw_dense ^ (jax.lax.slice_in_dim(word, 0, nw) & 1)

    def mk(st_words, fw):
        if packed:
            return R.PackedRelayState(
                st_words, fw, jnp.int32(0), jnp.bool_(True)
            )
        return R.RelayState(
            st_words, p0, fw, jnp.int32(0), jnp.bool_(True)
        )

    def k_arm(run_body):
        # Operands arrive as ARGS (pytrees), never closed over — a
        # closed-over mask/tile array bakes into the program as a
        # constant (GBs at bench scale; the RelayEngine._tensors rule).
        def fn(k, st_words, fw, *ops):
            st0 = mk(st_words, fw)

            def body(i, st):
                s2 = run_body(
                    mk(st.packed if packed else st.dist, feedback(st)),
                    *ops,
                )
                if packed:
                    return R.PackedRelayState(
                        s2.packed, st.fwords, jnp.int32(0), st.changed
                    )
                return R.RelayState(
                    s2.dist, p0, st.fwords, jnp.int32(0), st.changed
                )

            out = jax.lax.fori_loop(0, k, body, st0)
            return out.packed if packed else out.dist

        return fn

    def gather_body(st, vm, nm, vw):
        return superstep(st, vm, nm, vw)

    def mxu_body(st, ops):
        return mxu_step(st, ops, geo, use_kernel)

    init = pk0 if packed else d0
    arms = {
        "gather": mb(
            k_arm(gather_body), (init, fw_dense, vperm_m, net_m, valid)
        )
    }
    try:
        arms["mxu"] = mb(k_arm(mxu_body), (init, fw_dense, tile_ops))
    except Exception as exc:
        arms["mxu_error"] = repr(exc)
    from .ops.relay_pallas import pallas_interpret

    interp = pallas_interpret()
    rec = {
        "arms": arms,
        "gather_seconds": arms["gather"],
        "tiles": int(eng.adj_tiles.nt),
        "mxu_kernel": "pallas" if use_kernel else "xla",
        "frontier": "pinned dense (all bits set)",
    }
    if "mxu" in arms:
        rec["mxu_seconds"] = arms["mxu"]
        rec["selected"] = "mxu" if arms["mxu"] <= arms["gather"] else "gather"
        rec["selection_basis"] = (
            "measured (interpret arm)" if interp else "measured"
        )
    else:
        rec["selected"] = "gather"
        rec["selection_basis"] = "measured (mxu arm failed)"
    return rec


def probe_phase_kernels(eng, *, loops: int = 4, repeats: int = 2) -> dict:
    """Measure the pallas-vs-XLA arms of the packed row-min and packed
    state-update on a RelayEngine's real shapes and pick per phase — the
    engine-init selector (RelayEngine._resolve_phase_selection) on TPU
    backends, where the fused kernels compile for real.  K-loop / 2K-loop
    difference timing, same methodology as the applier probe and the
    ledger; ``selection_basis`` is always ``"measured"`` — a failed
    pallas arm records its error and selects xla, still a comparison
    with the failure on record, never a silent default.

    Runs anywhere (interpret-mode kernels off-TPU — the ledger uses the
    same arms to ship the verdict in every capture), but only the TPU
    engine init consults it for production selection: interpret arms
    measure real work at interpreter speed and must not steer the timed
    repeats."""
    from .ops import relay as R
    from .ops import relay_pallas as RP
    from .ops.packed import PACKED_SENTINEL

    rg = eng.relay_graph
    (vr, _vs, _vt, _oc, _os, _nt, net_size, in_classes) = eng._static
    valid = eng._tensors[2]
    opts = eng._COMPILER_OPTIONS
    interp = RP.pallas_interpret()
    x_net = jnp.zeros(net_size // 32, jnp.uint32)
    fw0 = jnp.zeros(vr // 32, jnp.uint32)
    pk0 = jnp.full(vr, PACKED_SENTINEL, jnp.uint32)
    cand_r = jnp.full(vr, PACKED_SENTINEL, jnp.uint32).at[:64].set(
        jnp.arange(64, dtype=jnp.uint32)
    )

    def mb(fn, args):
        return _measure(fn, args, loops, repeats, opts)

    def k_rowmin(use_pallas_arm):
        def fn(k, l1, vw):
            def body(i, c):
                lx = l1 ^ jax.lax.slice_in_dim(c, 0, l1.shape[0])
                if use_pallas_arm:
                    cand = RP.rowmin_ranks_pallas(
                        lx, vw, in_classes, vr, interpret=interp
                    )
                else:
                    cand = R.rowmin_ranks(lx, vw, in_classes, vr)
                bit = cand & jnp.uint32(1)
                w = max(l1.shape[0], vr)
                return c ^ jnp.concatenate(
                    [bit, jnp.zeros(w - vr, jnp.uint32)]
                )

            size = max(net_size // 32, vr)
            return jax.lax.fori_loop(
                0, k, body, jnp.zeros(size, jnp.uint32)
            )

        return fn

    def k_update(use_pallas_arm):
        def fn(k, pk, fw, cand):
            st0 = R.PackedRelayState(pk, fw, jnp.int32(0), jnp.bool_(True))

            def body(i, st):
                c = cand ^ (st.packed & jnp.uint32(1))
                if use_pallas_arm:
                    s2 = RP.apply_relay_candidates_packed_pallas(
                        st, c, interpret=interp
                    )
                else:
                    s2 = R.apply_relay_candidates_packed(st, c)
                return R.PackedRelayState(
                    s2.packed, s2.fwords, jnp.int32(0), s2.changed
                )

            return jax.lax.fori_loop(0, k, body, st0).packed

        return fn

    out = {"interpret": interp, "device": str(jax.devices()[0])}
    for phase, maker, args in (
        ("rowmin", k_rowmin, (x_net, valid)),
        ("state_update", k_update, (pk0, fw0, cand_r)),
    ):
        t_xla = mb(maker(False), args)
        rec = {"xla_seconds": t_xla}
        try:
            t_pal = mb(maker(True), args)
            rec["pallas_seconds"] = t_pal
            rec["selected"] = "pallas" if t_pal <= t_xla else "xla"
            rec["selection_basis"] = (
                "measured (interpret arm)" if interp else "measured"
            )
        except Exception as exc:
            rec["pallas_error"] = repr(exc)
            rec["selected"] = "xla"
            rec["selection_basis"] = "measured (pallas arm failed)"
        out[phase] = rec
    # The EXPANSION arm (ISSUE 15): measured whenever the engine carries
    # a tile layout (auto-probe built it, or the arm was forced) — the
    # gather-vs-mxu verdict rides the same memoized probe document.
    if getattr(eng, "adj_tiles", None) is not None:
        try:
            out["expansion"] = _expansion_arms(eng, mb)
        except Exception as exc:
            # No "selected" entry: the engine falls back to gather with
            # the failure on record, never a silent default.
            out["expansion"] = {"probe_error": repr(exc)}
    return out


def main() -> None:
    """CPU-runnable microbench: build a small R-MAT, run the ledger, print
    JSON (the standalone evidence path)."""
    import argparse
    import json

    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--scale", type=int, default=12)
    parser.add_argument("--edge-factor", type=int, default=8)
    parser.add_argument("--loops", type=int, default=4)
    parser.add_argument("--repeats", type=int, default=2)
    args = parser.parse_args()

    from .graph.generators import rmat_graph
    from .models.bfs import RelayEngine

    g = rmat_graph(args.scale, args.edge_factor, seed=7)
    eng = RelayEngine(g, sparse_hybrid=False)
    ledger = superstep_phase_ledger(
        eng, loops=args.loops, repeats=args.repeats
    )
    print(json.dumps(ledger, indent=2))


if __name__ == "__main__":
    main()
