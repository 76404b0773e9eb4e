"""The typed env-knob registry: every ``BFS_TPU_*`` name the framework
reads, in one table, with a parser, a default, a doc line and — the part
the linter proves — an ``affects`` set naming which content-addressed
cache keys and journal config keys the knob must participate in.

Motivation (ISSUE 19): the framework's behavior is steered by ~50 env
knobs read across ~25 modules, but the flavor-env tuples keying the
IR/HLO/Pallas lint caches, the probe-verdict key, the bench run-journal
config and the serve resident-engine key were each a hand-maintained
list.  PR 15 shipped (and hot-fixed) exactly the resulting bug class: a
warm cache hit replayed under a knob value it was never keyed on.  This
module makes the key membership a DECLARED property of each knob; the
consumers derive their tuples from it (:func:`flavor_env`), and the
fifth analyzer rung (:mod:`bfs_tpu.analysis.knobs`, ``bfs-tpu-lint
--knobs``) proves registry <-> read sites and registry <-> key builders
stay in sync both ways.

Accessors:

* :func:`get` — the typed read: unset/empty falls back to the registered
  default, anything else goes through the knob's parser, and a bad value
  raises :class:`KnobError` NAMING the knob — a typo'd knob must never
  silently change what a capture measured (the resolve_direction
  contract, applied uniformly).
* :func:`raw` — the unparsed read (``os.environ.get``, None when unset)
  for the path knobs where unset-vs-explicitly-empty differ and for key
  builders that hash raw strings.

``affects`` domains (each a derived tuple somewhere — KNB002 verifies):

* ``ir`` / ``hlo`` / ``pal`` — the analysis result caches
  (``analysis/ir.py`` ``_FLAVOR_ENV``, ``analysis/hlo.py``
  ``_HLO_FLAVOR_ENV``, ``analysis/pallas.py`` ``_PAL_FLAVOR_ENV``).
* ``probe`` — the probe-verdict key (``cache/layout.py`` ``_PROBE_ENV``).
* ``journal`` — the bench :class:`RunJournal` config
  (``resilience/journal.py`` ``ENV_CONFIG_KEYS`` via ``journal_key``).
* ``serve`` — the serve registry's resident-engine key
  (``serve/registry.py`` ``ENGINE_FLAVOR_ENV``).

This module is PURE STDLIB and imports nothing from ``bfs_tpu`` — it is
imported by ops/, graph/, utils/ and the analysis package, so it must
never pull jax (or anything heavy) into an importer.
"""

from __future__ import annotations

import math
import os
import re
from dataclasses import dataclass, field

_INT32_MAX = 2**31 - 1


class KnobError(ValueError):
    """A ``BFS_TPU_*`` env value its registered parser rejects.  The
    message always names the knob (KNB005 pins this)."""

    def __init__(self, name: str, raw: str, why: str):
        self.knob = name
        super().__init__(f"{name}={raw!r}: {why}")


# --------------------------------------------------------------- parsers --
# Each parser maps a non-empty raw string to the knob's typed value and
# raises ValueError (wrapped into KnobError by parse_value) on anything
# outside the knob's documented domain.  Loose legacy spellings ("any
# non-0 means on") are deliberately tightened to the documented set.

def _enum(*choices):
    def parse(raw: str):
        if raw not in choices:
            raise ValueError(f"use one of {' | '.join(choices)}")
        return raw
    return parse


def _flag(true_values=("1",), false_values=("0",)):
    """Strict boolean: returns True/False, rejects everything else."""
    def parse(raw: str):
        if raw in true_values:
            return True
        if raw in false_values:
            return False
        allowed = " | ".join((*false_values, *true_values))
        raise ValueError(f"use one of {allowed}")
    return parse


def _int(minimum=None):
    def parse(raw: str):
        v = int(raw)
        if minimum is not None and v < minimum:
            raise ValueError(f"must be >= {minimum} (got {v})")
        return v
    return parse


def _float(minimum=None, exclusive=True):
    def parse(raw: str):
        v = float(raw)
        if minimum is not None and (v <= minimum if exclusive else v < minimum):
            op = ">" if exclusive else ">="
            raise ValueError(f"must be {op} {minimum} (got {v})")
        return v
    return parse


def _parse_tristate(raw: str):
    """'' = auto (resolved by capability/fit), '0' = forced off,
    '1' = forced on."""
    if raw not in ("", "0", "1"):
        raise ValueError("use '' (auto) | 0 | 1")
    return raw


def _parse_delta(raw: str):
    """Delta-stepping bucket width: a positive number (an int, or a float
    for float weights), or inf/infinite/single, like any non-positive
    number, for one bucket (plain frontier Bellman-Ford): ``math.inf``."""
    if raw.lower() in ("inf", "infinite", "single"):
        return math.inf
    v = float(raw)
    if not v > 0:
        return math.inf
    return int(v) if v.is_integer() else v


def _parse_mesh(raw: str):
    """'rxc' (or a bare integer c, meaning 1xc) -> the raw spec,
    validated; '' = the 1D degenerate 1 x num_devices."""
    if raw == "":
        return ""
    s = raw.strip().lower()
    if "x" in s:
        rs, _, cs = s.partition("x")
        r, c = int(rs), int(cs)
    else:
        r, c = 1, int(s)
    if r < 1 or c < 1:
        raise ValueError("both mesh axes must be >= 1")
    return raw


def _parse_ckpt(raw: str):
    """off | every[:k] | auto — the resolve_ckpt grammar; the full
    CkptConfig construction stays in resilience/superstep_ckpt.py."""
    mode, _, arg = raw.strip().partition(":")
    if mode not in ("off", "every", "auto"):
        raise ValueError("use off | every:<k> | auto")
    if mode == "every":
        if arg and int(arg) < 1:
            raise ValueError("every:<k> needs k >= 1")
    elif arg:
        raise ValueError("only 'every' takes an argument")
    return raw.strip()


def _parse_labels(raw: str):
    """off | <K> — landmark distance-label count; 'off' (or 0) parses to
    0 = no label tier, any positive int is the landmark budget K."""
    s = raw.strip().lower()
    if s in ("off", "0"):
        return 0
    v = int(s)
    if v < 1:
        raise ValueError("use off | <K> with K >= 1")
    return v


def _parse_fault(raw: str):
    """kill:<phase>[:nth] | raise:<phase>[:nth] | phase:<phase>[:nth] |
    delay:<phase>[:seconds]; '' = no fault.  Full parsing (nth/seconds
    disambiguation) stays in resilience/faults.py."""
    if raw.strip() == "":
        return ""
    action, _, rest = raw.strip().partition(":")
    if action == "phase":
        action = "kill"
    if action not in ("kill", "raise", "delay") or not rest:
        raise ValueError(
            "use kill:<phase>[:nth] | raise:<phase>[:nth] | "
            "phase:<phase>[:nth] | delay:<phase>[:seconds]"
        )
    return raw.strip()


def _parse_log_level(raw: str):
    """A stdlib logging level name or a numeric level."""
    up = raw.strip().upper()
    if up in ("DEBUG", "INFO", "WARNING", "WARN", "ERROR",
              "CRITICAL", "FATAL", "NOTSET"):
        return up
    if up.isdigit():
        return int(up)
    raise ValueError("use a logging level name (DEBUG/INFO/...) or number")


def _parse_transfer_guard(raw: str):
    """'' /0/off/false/allow = off (None); 1/on/true/disallow =
    'disallow'; any explicit jax guard level name passes through
    (``disallow_explicit`` for paranoia runs)."""
    s = raw.strip().lower()
    if s in ("", "0", "off", "false", "allow"):
        return None
    if s in ("1", "on", "true", "disallow"):
        return "disallow"
    if re.fullmatch(r"[a-z_]+", s):
        return s
    raise ValueError("use 0/off | 1/disallow | log | a jax guard level name")


def _parse_lock_order(raw: str):
    """'' /0/off/false = off (None); raise = raise at the violating
    acquisition; 1/on/true/record = record only."""
    s = raw.strip().lower()
    if s in ("", "0", "off", "false"):
        return None
    if s == "raise":
        return "raise"
    if s in ("1", "on", "true", "record"):
        return "record"
    raise ValueError("use 0/off | 1/record | raise")


def _parse_str(raw: str):
    return raw


# -------------------------------------------------------------- registry --

@dataclass(frozen=True)
class Knob:
    """One registered env knob.

    ``default`` is the RAW string substituted when the env var is unset
    or empty, then parsed like any explicit value — so the default is
    provably inside the parser's domain (KNB005).  ``canary`` is a raw
    value the parser must REJECT (None only for freeform ``str``/``path``
    knobs, which accept everything).  ``scope`` is ``'call'`` (read at
    call/resolve time — may change between runs in one process) or
    ``'import'`` (baked into module constants at import; KNB003 allows a
    module-level read only for these).  ``journal_key`` names the knob's
    field in the bench RunJournal config (required iff ``'journal'`` in
    ``affects``)."""

    name: str
    kind: str  # enum | flag | tristate | int | float | spec | str | path
    default: str
    parse: callable
    doc: str
    affects: frozenset = frozenset()
    scope: str = "call"
    canary: str | None = None
    journal_key: str | None = None


def _k(name, kind, default, parse, doc, *, affects=(), scope="call",
       canary=None, journal_key=None) -> Knob:
    return Knob(
        name=name, kind=kind, default=default, parse=parse, doc=doc,
        affects=frozenset(affects), scope=scope, canary=canary,
        journal_key=journal_key,
    )


#: The flavor domains: every knob that changes which traced-program
#: flavors get built must key all three lint caches — the jaxpr pass, the
#: compiled-HLO pass and the Pallas kernel pass all analyze the flavor
#: the env selects.
_FLAVOR = ("ir", "hlo", "pal")

KNOBS: dict[str, Knob] = {k.name: k for k in (
    # -- traversal arm selection ------------------------------------------
    _k("BFS_TPU_DIRECTION", "enum", "auto", _enum("push", "pull", "auto"),
       "traversal body: force push or pull, or switch per superstep on "
       "the alpha/beta thresholds",
       affects=(*_FLAVOR, "journal", "serve"), canary="sideways",
       journal_key="direction"),
    _k("BFS_TPU_DIRECTION_ALPHA", "float", "14.0", _float(0.0),
       "direction switch: enter pull when frontier out-edge mass * alpha "
       "exceeds unexplored mass",
       affects=(*_FLAVOR, "journal", "serve"), canary="fast",
       journal_key="direction_alpha"),
    _k("BFS_TPU_DIRECTION_BETA", "float", "24.0", _float(0.0),
       "direction switch: stay in pull while frontier occupancy * beta "
       "exceeds n",
       affects=(*_FLAVOR, "journal", "serve"), canary="-1",
       journal_key="direction_beta"),
    _k("BFS_TPU_PACKED", "tristate", "", _parse_tristate,
       "packed level:6|parent:26 state words: '' = auto by fit, 0/1 "
       "force",
       affects=(*_FLAVOR, "journal", "serve"), canary="2",
       journal_key="packed"),
    _k("BFS_TPU_PALLAS", "tristate", "", _parse_tristate,
       "hand-written Pallas kernels: '' = auto by backend, 0/1 force",
       affects=(*_FLAVOR, "serve"), canary="2"),
    _k("BFS_TPU_ROWMIN", "enum", "auto", _enum("auto", "pallas", "xla"),
       "packed row-min kernel arm; auto = measured per phase at engine "
       "init on TPU",
       affects=(*_FLAVOR, "journal", "serve"), canary="cuda",
       journal_key="rowmin_kernel"),
    _k("BFS_TPU_STATE_UPDATE", "enum", "auto", _enum("auto", "pallas", "xla"),
       "packed state-update kernel arm; same selection contract as "
       "ROWMIN",
       affects=(*_FLAVOR, "journal", "serve"), canary="cuda",
       journal_key="state_update_kernel"),
    _k("BFS_TPU_EXPANSION", "enum", "auto", _enum("auto", "gather", "mxu"),
       "dense-frontier expansion arm: Benes relay gather or "
       "BFS-as-masked-matmul on the MXU",
       affects=(*_FLAVOR, "journal", "serve"), canary="dense",
       journal_key="expansion"),
    _k("BFS_TPU_MXU_KERNEL", "enum", "auto", _enum("auto", "pallas", "xla"),
       "mxu expansion arm implementation: fused Pallas kernel or its "
       "bit-identical XLA twin",
       affects=(*_FLAVOR, "probe", "journal", "serve"), canary="mosaic",
       journal_key="mxu_kernel"),
    _k("BFS_TPU_MXU_TILE_GB", "float", "4", _float(0.0),
       "adjacency-tile storage budget; an over-budget graph rejects "
       "forced mxu and auto falls back to gather",
       affects=_FLAVOR, canary="huge"),
    _k("BFS_TPU_TILES", "enum", "resident", _enum("resident", "stream", "auto"),
       "where the mxu arm's adjacency tiles live: device-resident, "
       "host-streamed superblocks, or auto by fit",
       affects=(*_FLAVOR, "journal", "serve"), canary="hbm",
       journal_key="tiles"),
    _k("BFS_TPU_TILES_BUILD", "enum", "device", _enum("device", "host"),
       "adjacency-tile builder arm; host is the pinned oracle, "
       "bit-identical",
       affects=_FLAVOR, canary="gpu"),
    _k("BFS_TPU_STREAM_CACHE_GB", "float", "1", _float(0.0),
       "streamed-tiles HBM superblock cache budget (LRU, single "
       "oversized allowance)",
       affects=(*_FLAVOR, "journal", "serve"), canary="big",
       journal_key="stream_cache_gb"),
    _k("BFS_TPU_STREAM_VERIFY", "flag", "0", _flag(),
       "re-fingerprint streamed superblocks on every cache hit; corrupt "
       "entries are dropped and re-fetched",
       affects=_FLAVOR, canary="yes"),
    _k("BFS_TPU_SSSP_DELTA", "spec", "64", _parse_delta,
       "delta-stepping bucket width (a float or int, or inf/single for "
       "plain frontier Bellman-Ford); non-positive = one bucket; unset, "
       "64 under integer weights and inf under float weights",
       affects=(*_FLAVOR, "journal", "serve"), canary="wide",
       journal_key="sssp_delta"),
    _k("BFS_TPU_CKPT", "spec", "off", _parse_ckpt,
       "superstep checkpointing: off | every:<k> | auto (Young/Daly "
       "interval) — selects fused vs segmented programs",
       affects=_FLAVOR, canary="sometimes"),
    # -- serve label oracle / fleet router --------------------------------
    _k("BFS_TPU_LABELS", "spec", "off", _parse_labels,
       "landmark distance-label oracle tier: off | <K> landmark roots "
       "precomputed at serve register() time; point queries answer from "
       "labels when the tightness certificate holds",
       affects=("journal", "serve"), canary="many",
       journal_key="labels"),
    _k("BFS_TPU_LABELS_GB", "float", "2", _float(0.0),
       "device budget for the resident label index (uint16[K,V]); an "
       "over-budget index serves exact-only",
       canary="big"),
    _k("BFS_TPU_LABELS_VERIFY", "int", "0", _int(0),
       "sample-verify every Nth tight label answer against the exact "
       "traversal; a mismatch quarantines the index (0 = off)",
       canary="-1"),
    _k("BFS_TPU_ROUTER_FAILURES", "int", "2", _int(1),
       "fleet router per-replica breaker: consecutive submit failures "
       "before the replica is routed around",
       canary="0"),
    _k("BFS_TPU_ROUTER_COOLDOWN_S", "float", "2.0", _float(0.0),
       "fleet router breaker cooldown before an opened replica is "
       "retried",
       canary="slow"),
    # -- sharded exchange / mesh ------------------------------------------
    _k("BFS_TPU_EXCHANGE", "enum", "auto", _enum("auto", "bitmap", "delta", "flat"),
       "sharded frontier exchange arm: sieved bitmaps, word-list deltas "
       "on sparse levels, or the flat oracle",
       affects=(*_FLAVOR, "journal"), canary="zip",
       journal_key="exchange"),
    _k("BFS_TPU_EXCHANGE_DIV", "int", "8", _int(1),
       "exchange word-list budget divisor B = ceil(kw/div); larger cuts "
       "deeper but engages on sparser levels only",
       affects=(*_FLAVOR, "journal"), canary="0",
       journal_key="exchange_div"),
    _k("BFS_TPU_MESH", "spec", "", _parse_mesh,
       "2D tile-grid mesh shape 'rxc' (bare c = 1xc); unset = the 1D "
       "degenerate 1 x num_devices",
       affects=_FLAVOR, canary="3by2"),
    # -- kernel geometry (baked into module constants at import) ----------
    _k("BFS_TPU_TM", "flag", "1", _flag(),
       "tile-major (transposed) relay kernel layout; 0 = row-major "
       "legacy layout",
       affects=("pal",), scope="import", canary="2"),
    _k("BFS_TPU_LANE_COMPACT", "flag", "0", _flag(),
       "lane-compacted relay kernel variant (disables tile-major when "
       "set)",
       affects=_FLAVOR, canary="2"),
    _k("BFS_TPU_TILE_ROWS", "int", "256", _int(1),
       "relay kernel rows per grid tile",
       affects=("pal",), scope="import", canary="8k"),
    _k("BFS_TPU_OUTER_TT", "int", "64", _int(1),
       "relay kernel outer tile repeat factor",
       affects=("pal",), scope="import", canary="fast"),
    _k("BFS_TPU_DMA_DEPTH", "int", "2", _int(1),
       "relay kernel manual-DMA pipeline depth (clamped to >= 2 at the "
       "read site)",
       affects=("pal",), scope="import", canary="deep"),
    _k("BFS_TPU_GUARDS", "flag", "1", _flag(),
       "bounds-guard predicates inside the relay kernels; 0 only for "
       "kernel micro-benchmarks",
       affects=("pal",), scope="import", canary="2"),
    _k("BFS_TPU_PAL_VMEM_MB", "float", "16", _float(0.0),
       "per-core VMEM budget the Pallas lint proves residency against "
       "and the probe keys on",
       affects=("pal", "probe"), canary="lots"),
    _k("BFS_TPU_PULL_CHUNK_MB", "float", "128", _float(0.0),
       "pull-arm gather chunk size (module constant)",
       affects=_FLAVOR, scope="import", canary="chunky"),
    # -- probe / selection control ----------------------------------------
    _k("BFS_TPU_PROBE_BUDGET", "float", "600", _float(0.0),
       "phase-probe wall-clock budget in seconds before coarse mode",
       canary="lots"),
    _k("BFS_TPU_PROBE_COARSE", "flag", "0", _flag(),
       "force the coarse (cheap) phase probe",
       canary="yes"),
    _k("BFS_TPU_PHASE_PROBE", "enum", "", _enum("", "force"),
       "force the per-phase kernel probe even off-TPU",
       canary="maybe"),
    # -- layout-build arms (byte-identical outputs; deliberately NOT in
    # any cache key — the bundle content hash covers them) -----------------
    _k("BFS_TPU_LAYOUT_BUILD", "enum", "device", _enum("device", "host"),
       "layout-bundle builder arm; host is the pinned oracle, "
       "bit-identical",
       canary="tpu"),
    _k("BFS_TPU_LAYOUT_SEGMENTS", "enum", "auto", _enum("auto", "xla", "host"),
       "relay segment-build arm inside the layout builder",
       canary="gpu"),
    _k("BFS_TPU_LAYOUT_ROUTE", "enum", "auto", _enum("auto", "native", "jax"),
       "Benes route computation arm: native extension or pure-JAX",
       canary="numpy"),
    _k("BFS_TPU_HUGEPAGES", "flag", "1", _flag(),
       "try transparent-hugepage advice for the pinned host tile store",
       canary="yes"),
    # -- cache / journal plumbing (paths and switches; never part of a
    # content key — they select WHERE artifacts live, not what they are) --
    _k("BFS_TPU_CACHE_DIR", "path", "", _parse_str,
       "root directory for all persistent artifact caches (default "
       "<repo>/.bench_cache)"),
    _k("BFS_TPU_JOURNAL_DIR", "path", "", _parse_str,
       "run-journal directory (default <cache root>/journal)"),
    _k("BFS_TPU_IR_CACHE", "path", "", _parse_str,
       "IR-lint result cache dir (default <repo>/.bench_cache/ir)"),
    _k("BFS_TPU_HLO_CACHE", "path", "", _parse_str,
       "HLO-lint result cache dir (default <repo>/.bench_cache/hlo)"),
    _k("BFS_TPU_PAL_CACHE", "path", "", _parse_str,
       "Pallas-lint result cache dir (default <repo>/.bench_cache/pal)"),
    _k("BFS_TPU_KNB_CACHE", "path", "", _parse_str,
       "knob-lint result cache dir (default <repo>/.bench_cache/knb)"),
    _k("BFS_TPU_TILES_CACHE", "flag", "0", _flag(),
       "persist built adjacency-tile bundles in the layout store "
       "sidecar",
       canary="yes"),
    _k("BFS_TPU_JOURNAL", "flag", "1", _flag(),
       "bench run journal (crash-resume medians); 0 disables",
       canary="off"),
    # -- observability / debugging ----------------------------------------
    _k("BFS_TPU_LOG", "spec", "INFO", _parse_log_level,
       "stdlib logging level for the project loggers",
       canary="CHATTY"),
    _k("BFS_TPU_SPANS", "flag", "1", _flag(),
       "phase spans: Chrome-trace buffer and jax.profiler annotations; "
       "0 disables both",
       canary="yes"),
    _k("BFS_TPU_BUILD_LOG", "flag", "0", _flag(),
       "per-build layout/relay build-step logging (bench turns it on)",
       canary="verbose"),
    _k("BFS_TPU_TRANSFER_GUARD", "spec", "", _parse_transfer_guard,
       "jax transfer guard over the hot regions: 0/off | 1/disallow | "
       "log | any explicit jax level",
       canary="never ever"),
    _k("BFS_TPU_LOCK_ORDER", "spec", "", _parse_lock_order,
       "lock-order recorder on the serve locks: 0/off | 1/record | "
       "raise",
       canary="maybe"),
    # -- fault injection / resilience -------------------------------------
    _k("BFS_TPU_FAULT", "spec", "", _parse_fault,
       "fault injection: kill|raise|phase:<phase>[:nth] | "
       "delay:<phase>[:seconds]",
       canary="explode"),
    _k("BFS_TPU_CKPT_MTBF_S", "float", "600.0", _float(0.0),
       "mean-time-between-failures prior for the auto checkpoint "
       "interval",
       canary="-3"),
    # -- analysis-pass budgets --------------------------------------------
    _k("BFS_TPU_IR_HBM_GB", "float", "16", _float(0.0),
       "per-device HBM budget the IR/HLO lint proves footprints against",
       affects=_FLAVOR, canary="lots"),
)}


# -------------------------------------------------------------- accessors --

def parse_value(name: str, raw: str):
    """Parse ``raw`` as knob ``name``; raises :class:`KnobError` (naming
    the knob) on an unregistered name or a value outside the domain."""
    k = KNOBS.get(name)
    if k is None:
        raise KnobError(name, raw, "not a registered knob (bfs_tpu/knobs.py)")
    try:
        return k.parse(raw)
    except KnobError:
        raise
    except (ValueError, TypeError) as exc:
        raise KnobError(name, raw, str(exc) or "invalid value") from exc


def get(name: str):
    """The typed read: unset/empty -> the registered default, else the
    parsed env value; a bad value raises :class:`KnobError`."""
    k = KNOBS.get(name)
    if k is None:
        raise KnobError(name, "", "not a registered knob (bfs_tpu/knobs.py)")
    value = os.environ.get(name)
    if value is None or value == "":
        value = k.default
    return parse_value(name, value)


def raw(name: str) -> str | None:
    """The unparsed read (``None`` when unset) — for path knobs where
    unset and explicitly-empty mean different things, and for key
    builders that hash raw strings.  The name must still be registered."""
    if name not in KNOBS:
        raise KnobError(name, "", "not a registered knob (bfs_tpu/knobs.py)")
    return os.environ.get(name)


def flavor_env(domain: str) -> tuple:
    """Sorted tuple of knob names declaring ``domain`` in ``affects`` —
    the derived replacement for every hand-maintained flavor list."""
    return tuple(sorted(
        k.name for k in KNOBS.values() if domain in k.affects
    ))


def journal_map() -> dict:
    """``{journal config key: knob name}`` for the journal-affecting
    knobs (sorted by config key)."""
    pairs = sorted(
        (k.journal_key, k.name)
        for k in KNOBS.values() if "journal" in k.affects
    )
    return dict(pairs)
