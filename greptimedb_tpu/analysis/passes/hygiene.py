"""Telemetry/knob hygiene pass: metric names + env-knob inventory.

Two invariant families, both previously enforced ad hoc:

**Metrics** (the tests/test_telemetry.py registry static check, now
delegating here).  Every ``greptime_*`` metric name registered in code
must be literal-analyzable, convention-clean and collision-free:

- **GL-T001** — one metric name registered at two sites with a
  different kind or label set (the runtime Registry records these in
  ``collisions``; this catches them before any import runs).
- **GL-T002** — a literal metric or label name violating the
  Prometheus ``[a-z_][a-z0-9_]*`` convention or missing the
  ``greptime_`` prefix.
- **GL-T003** — a histogram whose exploded self-export tables
  (``_bucket``/``_sum``/``_count``) collide with another registered
  metric (the self-monitor imports the registry into tables named this
  way — a collision silently merges two metrics' history).

``check_registry(registry)`` is the RUNTIME twin shared with the tier-1
telemetry test: same name convention, applied to whatever actually got
registered (dynamic names included).

**Knobs.**  Every ``GREPTIME_*`` environment variable read anywhere in
the package must be documented in KNOB_DOCS below, from which CONFIG.md
is generated (render_config_md) — defaults and reader modules extracted
from the code, so the table can never drift silently:

- **GL-K001** — a knob read in code but missing from KNOB_DOCS (and
  hence from CONFIG.md).
- **GL-K002** — a KNOB_DOCS entry no code reads (stale documentation).

Reference analog: the workspace-wide lints + config-docs discipline
(config/config.md generated from the config structs).
"""

from __future__ import annotations

import ast
import re

from greptimedb_tpu.analysis.core import (
    AnalysisContext, Finding, Pass, attr_chain, qualname_map, register,
)

NAME_RE = re.compile(r"^[a-z_][a-z0-9_]*$")
METRIC_PREFIX = "greptime_"
KNOB_RE = re.compile(r"^GREPTIME_[A-Z0-9_]+$")

REGISTER_METHODS = {"counter": "counter", "gauge": "gauge",
                    "histogram": "histogram"}

# ---------------------------------------------------------------------------
# Knob documentation: name -> one-line effect.  Subsystem/readers/defaults
# are extracted from the code; this table holds only what cannot be
# derived.  CONFIG.md is generated from the union — the tier-1 gate fails
# when either side drifts (GL-K001 / GL-K002 / stale CONFIG.md).
# ---------------------------------------------------------------------------

KNOB_DOCS: dict[str, str] = {
    "GREPTIME_AOT_WARMUP": (
        "`off` disables AOT warmup; `auto` (default) replays the usage "
        "journal's top-K shape classes at open + drains the rest on "
        "scheduler-idle ticks whenever the compile cache is armed."),
    "GREPTIME_AOT_WARMUP_TOP_K": (
        "How many journaled shape classes replay synchronously at "
        "region-open (the rest warm on idle ticks)."),
    "GREPTIME_CHAOS": (
        "Seeded fault-injection spec (`seed=N;point=prob:action[:...]`) "
        "consulted at every remote/disk boundary; unset = disabled "
        "(zero overhead)."),
    "GREPTIME_COMPILE_CACHE": (
        "Persistent compile cache: `auto` arms the AOT artifact store + "
        "usage journal for persistent data homes; `on` forces it (also "
        "wiring jax's own compilation-cache hook); `off` disables."),
    "GREPTIME_COMPILE_CACHE_DIR": (
        "Override location of the AOT artifact store + usage journal "
        "(default `<data_home>/compile_cache`)."),
    "GREPTIME_COMPILE_CACHE_QUOTA_BYTES": (
        "Disk quota for serialized AOT artifacts (`compile_cache` "
        "workload, kind=disk; oldest artifacts evict first)."),
    "GREPTIME_LOCK_WITNESS": (
        "`on` installs the runtime lock-order witness (records real "
        "acquisition chains, fails on ABBA inversions) for the "
        "concurrency/chaos test tiers; unset = witness never imported."),
    "GREPTIME_FLOW_CKPT_INTERVAL_S": (
        "Flow checkpoint cadence: GTF1 state+watermark snapshots persist "
        "at most this often (post-fold and on scheduler-idle ticks; "
        "0 disables periodic checkpointing, shutdown still saves)."),
    "GREPTIME_FLOW_DEVICE": (
        "`off` disables the device flow runtime everywhere: streaming "
        "flows keep the host dict-of-partials engine byte-for-byte "
        "(flow/device.py + checkpoint.py never imported)."),
    "GREPTIME_FLOW_QUOTA_BYTES": (
        "Memory-manager quota for the `flow` workload (resident "
        "[G, W] partial-state matrices; reject-to-host-fallback "
        "admission)."),
    "GREPTIME_FULLTEXT": (
        "`off` disables the fingerprint text index everywhere: "
        "LIKE/MATCHES/regex/LogQL predicates walk their dictionaries "
        "host-side byte-for-byte as before (A/B twin)."),
    "GREPTIME_FULLTEXT_CACHE_BYTES": (
        "Capacity of the resident fulltext cache (fingerprint matrices, "
        "verified-vocabulary memos, combined line-filter vectors)."),
    "GREPTIME_FULLTEXT_MIN_GRAM": (
        "Shortest indexed n-gram (2 or 3): 2 doubles index build work "
        "but lets two-character literals prune."),
    "GREPTIME_FULLTEXT_QUOTA_BYTES": (
        "Memory-manager quota for the `fulltext` workload "
        "(reject-to-host-fallback admission)."),
    "GREPTIME_FULLTEXT_WORDS": (
        "uint32 words per fingerprint row (32 bloom bits each): more "
        "words = fewer prefilter false positives, more HBM."),
    "GREPTIME_GRID_BUDGET_BYTES": (
        "HBM budget for resident dense grids; regions past it stay on "
        "the row path."),
    "GREPTIME_GRID_MIN_DENSITY": (
        "Minimum (rows / series x buckets) fill ratio for a region to "
        "qualify for the dense grid."),
    "GREPTIME_INGEST_WORKERS": (
        "Width of the parallel per-region ingest append pool."),
    "GREPTIME_JOIN_MAX_ROWS": (
        "Hard cap on join output rows; larger products raise instead of "
        "exhausting memory."),
    "GREPTIME_JOIN_WARN_ROWS": (
        "Join output size above which a slow-join warning is logged."),
    "GREPTIME_LAYOUT_CACHE_BYTES": (
        "Capacity of the bucket-major derived layout cache."),
    "GREPTIME_LAYOUT_CACHE_QUOTA_BYTES": (
        "Memory-manager quota for the `layout_cache` workload "
        "(reject-to-fallback admission)."),
    "GREPTIME_MESH": (
        "`off` disables device-mesh sharding even when multiple devices "
        "are visible."),
    "GREPTIME_MESH_AXIS": (
        "Axis name for the 1-D device mesh the resident tables shard "
        "over."),
    "GREPTIME_MESH_MIN_ROWS": (
        "Minimum region rows before mesh-sharded dispatch is worth the "
        "collective overhead."),
    "GREPTIME_PREFETCH_THREADS": (
        "S3 scan-readahead fetcher thread count (the read path joins "
        "in-flight prefetches)."),
    "GREPTIME_PROMQL_CACHE_BYTES": (
        "Capacity of the resident PromQL evaluation cache."),
    "GREPTIME_PROMQL_CACHE_QUOTA_BYTES": (
        "Memory-manager quota for the `promql_cache` workload."),
    "GREPTIME_RPC_DEADLINE_S": (
        "Per-call deadline for Flight RPCs (rides each attempt as the "
        "gRPC timeout)."),
    "GREPTIME_RPC_RETRIES": (
        "Retry budget for transient Flight RPC failures (backoff + "
        "jitter envelope)."),
    "GREPTIME_S3_FENCING": (
        "`off` disables leader-epoch fencing of manifest/watermark "
        "writes on shared object storage (conditional puts under the "
        "Metasrv-minted epoch; standalone regions never arm a fence "
        "either way)."),
    "GREPTIME_SCAN_QUOTA_BYTES": (
        "Memory-manager quota for the `scan` staging workload "
        "(reject-to-sequential fallback)."),
    "GREPTIME_SCAN_THREADS": (
        "Cold-scan parallel SST decode pool width (default "
        "min(8, files, cores))."),
    "GREPTIME_SCHEDULER_LINGER_MS": (
        "Group-commit linger ceiling for coalescible query arrivals "
        "(adaptive: scaled by same-class pressure, 0 when idle)."),
    "GREPTIME_SCHEDULER_MAX_BATCH": (
        "Maximum queries coalesced into one stacked device dispatch."),
    "GREPTIME_SCHEDULER_QUEUE": (
        "Bound on total queued queries before submissions are rejected "
        "with ResourcesExhausted."),
    "GREPTIME_SCHEDULER_TIMEOUT_S": (
        "Default per-query deadline; queries shed if still queued past "
        "it."),
    "GREPTIME_SCHEDULER_WORKERS": (
        "Scheduler worker pool size (default 1: the db lock serializes "
        "execution anyway)."),
    "GREPTIME_SCRUB": (
        "Online integrity scrubber: `auto` (default) arms the verified "
        "background sweep for persistent data homes on scheduler idle "
        "capacity; `on` starts sweeping immediately (standby nodes "
        "scrub too); `off` disables (module never constructed)."),
    "GREPTIME_SCRUB_BATCH": (
        "Artifacts verified per scrubber idle tick (the preemption "
        "granularity: interactive queries wait at most one batch)."),
    "GREPTIME_SCRUB_INTERVAL_S": (
        "Pause between completed scrub sweeps (a sweep itself is paced "
        "by idle ticks and can take much longer)."),
    "GREPTIME_SELF_MONITOR": (
        "`on` starts the self-monitoring loop (own spans/metrics "
        "exported into own tables); module never imported when unset."),
    "GREPTIME_SELF_MONITOR_INTERVAL_S": (
        "Flush interval of the self-monitoring export loop."),
    "GREPTIME_SLO_ALPHA": (
        "Relative-error bound of the DDSketch-style latency sketches "
        "(smaller = more buckets = tighter quantiles)."),
    "GREPTIME_SLO_SLOT_S": (
        "Burn-rate ring-buffer slot width in seconds; the 5m/30m/1h/6h "
        "windows are fixed slot COUNTS, so shrinking this compresses "
        "every window proportionally."),
    "GREPTIME_SLO_THRESHOLD_MS": (
        "Default per-request latency objective for the interactive "
        "class; normal/background scale it by 4x/20x."),
    "GREPTIME_SLO_OBJECTIVE": (
        "Default availability objective (fraction of requests that "
        "must meet the threshold; 1-objective is the error budget)."),
    "GREPTIME_SLO_OVERRIDES": (
        "Per-tenant objective overrides, "
        "`tenant=threshold_ms:objective,...`."),
    "GREPTIME_SLO_FAST_BURN": (
        "Burn-rate multiplier that fires the fast (1h/5m) alert pair — "
        "and throttles every idle consumer while firing."),
    "GREPTIME_SLO_SLOW_BURN": (
        "Burn-rate multiplier that fires the slow (6h/30m) alert "
        "pair."),
    "GREPTIME_SLO_MIN_SAMPLES": (
        "Minimum short-window sample count before an alert pair may "
        "fire (thin traffic cannot page)."),
    "GREPTIME_SLO_ADMIT_MS": (
        "Background-admission allowance at FULL error budget; the "
        "journal-estimated cost of background work must fit the "
        "budget-scaled fraction of this."),
    "GREPTIME_SLO_DEADLINE_FACTOR": (
        "Adaptive per-class deadline = observed p99 x this factor "
        "(replaces the static GREPTIME_SCHEDULER_TIMEOUT_S once "
        "enough samples exist)."),
    "GREPTIME_SLO_DEADLINE_FLOOR_S": (
        "Lower bound of the adaptive deadline (a fast p99 must not "
        "strangle occasional legitimate slow queries)."),
    "GREPTIME_SLO_ROTATE_S": (
        "Sketch two-generation rotation period: adaptive deadlines and "
        "linger read the live+previous generations, so old latency "
        "regimes age out."),
    "GREPTIME_IDLE_QUANTUM_MS": (
        "Idle-economy accounting quantum: a consumer tick costs "
        "max(1, elapsed/quantum) credits, so long ticks auto-yield "
        "future grants."),
    "GREPTIME_IDLE_STARVE_TICKS": (
        "Starvation bound: a consumer passed over this many eligible "
        "ticks wins the next grant outright (counted in "
        "greptime_idle_starved_total — nonzero means misconfigured "
        "weights)."),
    "GREPTIME_IDLE_WEIGHTS": (
        "Idle-economy weight overrides, `name=weight,...` (substring "
        "match on the consumer name)."),
    "GREPTIME_SORTED_SEGMENTS": (
        "Segment-reduction strategy: `auto` and `off` take the scatter "
        "form on every backend; `force` takes the sorted form where "
        "the layout allows it (A/B, tests)."),
    "GREPTIME_TENANT_INFLIGHT": (
        "Default per-tenant concurrent-query cap (0 = unlimited)."),
    "GREPTIME_TENANT_MEM_BYTES": (
        "Default per-tenant memory budget, registered as a "
        "`tenant:<name>` workload."),
    "GREPTIME_TENANT_QPS": (
        "Default per-tenant token-bucket query rate (0 = unlimited)."),
    "GREPTIME_TENANT_QUERY_EST_BYTES": (
        "Per-query memory estimate charged against the tenant budget at "
        "admission."),
    "GREPTIME_VECTOR_MAX_DISTINCT": (
        "Distinct-value ceiling for vectorized set-ops; above it the "
        "evaluator falls back to hashing."),
    "GREPTIME_WAL_GROUP_COMMIT": (
        "`off` disables leader/follower WAL group commit (every append "
        "pays its own write+fsync)."),
    "GREPTIME_WAL_LINGER_MS": (
        "WAL group-commit linger: how long a contended leader holds the "
        "batch open for joiners (0 = flush immediately)."),
    "GREPTIME_WAL_REPLICAS": (
        "Shared-log broker replication factor (default 1 = legacy "
        "single copy; 3 = majority-quorum appends with read-repair — "
        "replay survives the loss or corruption of any minority of "
        "copies)."),
}


# ---------------------------------------------------------------------------
# Static collection
# ---------------------------------------------------------------------------


def _docstring_lines(tree: ast.Module) -> set[int]:
    out: set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            body = getattr(node, "body", [])
            if body and isinstance(body[0], ast.Expr) and isinstance(
                    body[0].value, ast.Constant) and isinstance(
                    body[0].value.value, str):
                c = body[0].value
                out.update(range(c.lineno,
                                 getattr(c, "end_lineno", c.lineno) + 1))
    return out


def collect_metric_registrations(ctx: AnalysisContext):
    """[(name, kind, labels|None, file, line, scope)] for every literal
    REGISTRY.counter/gauge/histogram call in the package."""
    regs = []
    for mod in ctx.modules:
        qnames = qualname_map(mod.tree)
        funcs = sorted(
            ((n.lineno, getattr(n, "end_lineno", n.lineno), q)
             for n, q in qnames.items()
             if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))),
        )

        def scope_of(line: int) -> str:
            best = "<module>"
            best_span = None
            for lo, hi, q in funcs:
                if lo <= line <= hi and (best_span is None
                                         or hi - lo < best_span):
                    best, best_span = q, hi - lo
            return best

        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            chain = attr_chain(node.func)
            if chain is None:
                continue
            parts = chain.split(".")
            if parts[-1] not in REGISTER_METHODS or len(parts) < 2:
                continue
            recv = parts[-2]
            if "registry" not in recv.lower() and recv != "r":
                # REGISTRY.counter / self.registry.gauge style receivers
                # only — plain .counter() methods elsewhere don't count
                continue
            if not node.args or not isinstance(node.args[0], ast.Constant) \
                    or not isinstance(node.args[0].value, str):
                continue
            name = node.args[0].value
            labels = None
            for kw in node.keywords:
                if kw.arg == "labels" and isinstance(kw.value, ast.Tuple):
                    if all(isinstance(e, ast.Constant)
                           for e in kw.value.elts):
                        labels = tuple(e.value for e in kw.value.elts)
            if labels is None and len(node.args) >= 3 and isinstance(
                    node.args[2], ast.Tuple):
                if all(isinstance(e, ast.Constant)
                       for e in node.args[2].elts):
                    labels = tuple(e.value for e in node.args[2].elts)
            regs.append((name, REGISTER_METHODS[parts[-1]], labels,
                         mod.relpath, node.lineno, scope_of(node.lineno)))
    return regs


def collect_knob_reads(ctx: AnalysisContext):
    """[(knob, default|None, file, line)] for every GREPTIME_* string
    literal outside docstrings.  When the literal is the first argument
    of a call whose second argument is a constant, that constant is
    recorded as the default (the `environ.get(name, default)` shape)."""
    reads = []
    for mod in ctx.modules:
        if mod.relpath == "analysis/passes/hygiene.py":
            continue  # KNOB_DOCS itself is documentation, not a reader
        doclines = _docstring_lines(mod.tree)
        seen: set[int] = set()  # id() of constants consumed via calls
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) and node.args and isinstance(
                    node.args[0], ast.Constant) and isinstance(
                    node.args[0].value, str) and KNOB_RE.match(
                    node.args[0].value):
                default = None
                if len(node.args) >= 2 and isinstance(
                        node.args[1], ast.Constant):
                    default = node.args[1].value
                seen.add(id(node.args[0]))
                reads.append((node.args[0].value, default, mod.relpath,
                              node.lineno))
        for node in ast.walk(mod.tree):
            if (isinstance(node, ast.Constant)
                    and isinstance(node.value, str)
                    and KNOB_RE.match(node.value)
                    and id(node) not in seen
                    and node.lineno not in doclines):
                reads.append((node.value, None, mod.relpath, node.lineno))
    return reads


# ---------------------------------------------------------------------------
# Runtime twin (shared with tests/test_telemetry.py)
# ---------------------------------------------------------------------------


def check_registry(registry, norm=None) -> list[str]:
    """Problems in a LIVE registry: recorded collisions, name/label
    convention violations, self-export table collisions (optionally
    normalizer round-trip when ``norm`` is given).  The tier-1 telemetry
    test imports every metric-registering module and then asserts this
    returns []."""
    problems = list(registry.collisions)
    tables: set[str] = set()
    for name, m in registry._metrics.items():
        if not NAME_RE.match(name):
            problems.append(f"bad metric name {name!r}")
        for ln in m.label_names:
            if not NAME_RE.match(ln):
                problems.append(f"bad label {ln!r} on {name}")
        if norm is not None and norm(name) != name:
            problems.append(f"{name!r} mutates through the OTLP normalizer")
        exploded = ([name + s for s in ("_bucket", "_sum", "_count")]
                    if m.kind == "histogram" else [name])
        for t in exploded:
            if t in tables:
                problems.append(f"self-export table collision: {t}")
            tables.add(t)
    return problems


# ---------------------------------------------------------------------------
# CONFIG.md generation
# ---------------------------------------------------------------------------


def render_config_md(ctx: AnalysisContext | None = None) -> str:
    from greptimedb_tpu.analysis.core import load_package

    ctx = ctx or load_package()
    reads = collect_knob_reads(ctx)
    by_knob: dict[str, dict] = {}
    for knob, default, relpath, _line in reads:
        e = by_knob.setdefault(knob, {"default": None, "readers": set()})
        e["readers"].add(relpath)
        if default is not None and e["default"] is None:
            e["default"] = default
    lines = [
        "# CONFIG — `GREPTIME_*` environment knobs",
        "",
        "Generated by the greptime-lint knob pass "
        "(`python -m greptimedb_tpu.analysis --write-config`).",
        "Do not edit by hand: the tier-1 gate regenerates this table and "
        "fails on drift —",
        "a knob read in code but absent here is a GL-K001 finding.",
        "",
        "| Knob | Default | Read by | Effect |",
        "|---|---|---|---|",
    ]
    for knob in sorted(set(by_knob) | set(KNOB_DOCS)):
        info = by_knob.get(knob, {"default": None, "readers": set()})
        default = info["default"]
        if default is None:
            default_s = "unset"
        elif default == "":
            default_s = '`""`'
        else:
            default_s = f"`{default}`"
        readers = ", ".join(f"`{r}`" for r in sorted(info["readers"])) \
            or "—"
        doc = KNOB_DOCS.get(knob, "**UNDOCUMENTED (GL-K001)**")
        lines.append(f"| `{knob}` | {default_s} | {readers} | {doc} |")
    return "\n".join(lines) + "\n"


@register
class HygienePass(Pass):
    name = "hygiene"
    title = "metric-name + env-knob hygiene"
    codes = {
        "GL-T001": "metric registered with conflicting kind/labels",
        "GL-T002": "metric/label name violates the naming convention",
        "GL-T003": "histogram self-export tables collide with a metric",
        "GL-K001": "GREPTIME_* knob read in code but undocumented",
        "GL-K002": "documented knob never read by any code",
    }

    def run(self, ctx: AnalysisContext) -> list[Finding]:
        findings: list[Finding] = []
        regs = collect_metric_registrations(ctx)
        first_site: dict[str, tuple] = {}
        for name, kind, labels, relpath, line, scope in regs:
            if not NAME_RE.match(name) or not name.startswith(METRIC_PREFIX):
                findings.append(Finding(
                    code="GL-T002", file=relpath, line=line, scope=scope,
                    key=name,
                    message=f"metric name {name!r} violates the "
                            f"'{METRIC_PREFIX}[a-z0-9_]*' convention"))
            for ln in labels or ():
                if not NAME_RE.match(str(ln)):
                    findings.append(Finding(
                        code="GL-T002", file=relpath, line=line, scope=scope,
                        key=f"{name}:{ln}",
                        message=f"label {ln!r} on {name!r} violates the "
                                "naming convention"))
            prev = first_site.get(name)
            if prev is None:
                first_site[name] = (kind, labels, relpath, line)
            else:
                pkind, plabels, pfile, pline = prev
                if pkind != kind or (labels is not None
                                     and plabels is not None
                                     and labels != plabels):
                    findings.append(Finding(
                        code="GL-T001", file=relpath, line=line, scope=scope,
                        key=name,
                        message=(f"{name!r} registered as {pkind}"
                                 f"{plabels} at {pfile}:{pline}, "
                                 f"re-registered as {kind}{labels}")))
        # histogram explosion vs literal names
        names = set(first_site)
        for name, (kind, _labels, relpath, line) in first_site.items():
            if kind != "histogram":
                continue
            for suffix in ("_bucket", "_sum", "_count"):
                if name + suffix in names:
                    findings.append(Finding(
                        code="GL-T003", file=relpath, line=line,
                        scope="<module>", key=name + suffix,
                        message=(f"histogram {name!r} self-export table "
                                 f"{name + suffix!r} collides with a "
                                 "registered metric")))
        # knobs
        reads = collect_knob_reads(ctx)
        flagged: set[str] = set()
        for knob, _default, relpath, line in reads:
            if knob not in KNOB_DOCS and knob not in flagged:
                flagged.add(knob)
                findings.append(Finding(
                    code="GL-K001", file=relpath, line=line,
                    scope="<module>", key=knob,
                    message=(f"knob {knob} read here but missing from "
                             "analysis KNOB_DOCS / CONFIG.md")))
        read_names = {k for k, _d, _f, _l in reads}
        # stale-doc detection only makes sense over the WHOLE package
        # (fixture snippets would mark every documented knob stale)
        whole_package = ctx.module("analysis/passes/hygiene.py") is not None
        for knob in sorted(set(KNOB_DOCS) - read_names
                           if whole_package else ()):
            findings.append(Finding(
                code="GL-K002", file="analysis/passes/hygiene.py", line=1,
                scope="KNOB_DOCS", key=knob,
                message=f"documented knob {knob} is never read by any "
                        "code"))
        return findings
