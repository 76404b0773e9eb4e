"""Sharding & collective checker: the IR005/IR006 half of the IR pass.

Works over walked jaxpr equations (:func:`bfs_tpu.analysis.ir.walk_eqns`)
rather than source text: ``shard_map`` axis use, missing/extra exchange
collectives and payload-format regressions are invisible to the AST
linter because they only exist in what actually lowers.

Checked invariants, per analyzed program (:class:`~bfs_tpu.analysis.ir.Program`):

* **IR005a** — every collective names only axes declared by the program
  spec (``mesh_axes``).  A collective over an undeclared axis (a second
  mesh axis, an outer vmap name) is an *extra* collective: per-superstep
  ICI traffic nobody budgeted.  (A truly unbound axis cannot reach the
  walk at all — jax rejects it at trace time, which surfaces as IR000.)
* **IR005b** — every axis in ``required_axes`` is touched by at least one
  collective.  The sharded relay/push/pull programs are only correct
  because a per-superstep merge rides the ``graph`` axis; a refactor that
  drops the all-reduce produces per-shard-plausible wrong results with no
  runtime error.
* **IR005c** — the ``shard_map`` result shardings (``out_specs`` in the
  lowered eqn) match the spec's ``expected_out_names``.  XLA will happily
  return per-shard state where the caller expects replicated state; every
  downstream consumer then silently reads shard 0.
* **IR006** — collectives moving V-scale payloads (>= ``exchange_floor``
  bytes) must use the declared exchange dtypes (packed uint32 words by
  default).  The compressed frontier exchange ROADMAP item 1 needs is
  guarded here the day it lands: a float32 or 64-bit-widened exchange
  doubles (or worse) the per-superstep ICI bytes.

Control-plane scalars (the ``changed`` all-reduce, axis_index) fall under
the floor and are never flagged.
"""

from __future__ import annotations

#: Primitives that move payload across mesh axes.  ``psum_invariant``
#: is jax's spelling of psum inside shard_map.
PAYLOAD_COLLECTIVES = frozenset({
    "psum", "psum_invariant", "pmin", "pmax", "ppermute", "pshuffle",
    "all_gather", "all_to_all", "reduce_scatter",
})

#: Axis-binding eqns that move no payload: mesh-coordinate reads and the
#: replication-rewrite casts shard_map inserts automatically.  They never
#: satisfy a required exchange axis and are never flagged — pvary in
#: particular appears in ANY shard_map body, collective or not.
CONTROL_COLLECTIVES = frozenset({"axis_index", "pvary", "pcast"})

_WIDE_DTYPES = frozenset({"int64", "uint64", "float64"})


def eqn_axis_names(eqn) -> tuple[str, ...]:
    """The mesh-axis names a collective eqn binds (positional vmap axes —
    ints — are not mesh axes and are dropped)."""
    raw = ()
    for key in ("axes", "axis_name", "axis"):
        if key in eqn.params:
            raw = eqn.params[key]
            break
    if raw is None:
        return ()
    if not isinstance(raw, (tuple, list)):
        raw = (raw,)
    return tuple(a for a in raw if isinstance(a, str))


def out_names_sets(eqn) -> tuple[frozenset, ...]:
    """``shard_map`` eqn ``out_specs`` (PartitionSpecs) as one frozenset
    of axis names per flat output — the comparable form of the declared
    ``out_specs``."""
    out = []
    for spec in eqn.params.get("out_specs", ()):
        names = set()
        for entry in spec:
            if entry is None:
                continue
            names.update(entry if isinstance(entry, tuple) else (entry,))
        out.append(frozenset(names))
    return tuple(out)


def check_collectives(prog, walked, make_finding):
    """IR005/IR006 over ``walked`` eqns.

    ``walked`` is the ``(eqn, ctx)`` sequence from
    :func:`bfs_tpu.analysis.ir.walk_eqns`; ``make_finding(rule, detail,
    message)`` builds the program-anchored finding (ir.py owns paths and
    fingerprint shape).  Returns a list of findings.
    """
    findings = []
    declared = prog.mesh_axes
    used_axes: set[str] = set()

    for eqn, _ctx in walked:
        name = eqn.primitive.name
        if name == "shard_map":
            if prog.expected_out_names is not None:
                actual = out_names_sets(eqn)
                expected = tuple(frozenset(s) for s in prog.expected_out_names)
                if actual != expected:
                    findings.append(make_finding(
                        "IR005", "out_specs",
                        f"shard_map result sharding {_fmt_specs(actual)} "
                        f"disagrees with the declared out_specs "
                        f"{_fmt_specs(expected)} — a consumer expecting "
                        "replicated state would silently read one shard",
                    ))
            continue
        if name not in PAYLOAD_COLLECTIVES:
            continue  # CONTROL_COLLECTIVES never count as an exchange
        axes = eqn_axis_names(eqn)
        if not axes:
            continue
        used_axes.update(axes)
        # A TRULY unbound axis never reaches this walk: jax raises at
        # trace time and analyze_program reports IR000.  What can reach
        # here is an axis bound by something other than the spec's
        # declaration (an outer vmap name, a second mesh axis) — the
        # "extra exchange nobody budgeted" case.
        for ax in axes:
            if declared is not None and ax not in declared:
                findings.append(make_finding(
                    "IR005", f"extra:{ax}",
                    f"collective '{name}' rides undeclared mesh axis "
                    f"'{ax}' (declared: {sorted(declared)}) — an extra "
                    "exchange nobody budgeted",
                ))
        findings.extend(_check_payload(prog, eqn, name, make_finding))

    for ax in sorted(set(prog.required_axes) - used_axes):
        findings.append(make_finding(
            "IR005", f"missing:{ax}",
            f"no collective touches required exchange axis '{ax}' — "
            "the per-superstep merge this program's correctness "
            "depends on is gone from the lowered IR",
        ))
    return findings


def _check_payload(prog, eqn, name, make_finding):
    findings = []
    allowed = frozenset(prog.exchange_dtypes)
    for var in eqn.invars:
        aval = getattr(var, "aval", None)
        if aval is None or not hasattr(aval, "dtype"):
            continue
        nbytes = int(getattr(aval, "size", 0)) * aval.dtype.itemsize
        if nbytes < prog.exchange_floor:
            continue  # control-plane scalar (the `changed` reduce etc.)
        dt = str(aval.dtype)
        if dt in _WIDE_DTYPES or dt not in allowed:
            findings.append(make_finding(
                "IR006", f"payload:{name}:{dt}",
                f"collective '{name}' moves a {nbytes}-byte {dt} payload; "
                f"the declared exchange format is {sorted(allowed)} — "
                "a widened exchange multiplies per-superstep ICI bytes",
            ))
    return findings


def _fmt_specs(specs) -> str:
    return "(" + ", ".join(
        "{" + ",".join(sorted(s)) + "}" if s else "replicated" for s in specs
    ) + ")"
