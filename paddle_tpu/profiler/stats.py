"""Cross-stack metrics registry (reference: `platform/monitor.h`
StatValue registry + the bvar counters behind brpc's /vars page).

One small metrics core shared by every layer that reports:

* `Counter` / `Histogram` — thread-safe named stats. The histogram is
  the SAME fixed 32-bucket log2 layout as the native core
  (`csrc/ptpu_stats.h`): bucket 0 counts value 0, bucket b counts
  values in ``[2**(b-1), 2**b)``, the last bucket is the overflow
  tail. Identical layouts mean native snapshots (predictor, PS data
  plane) and Python snapshots (PS fallback plane, hapi callbacks)
  merge bucket-for-bucket.
* `Registry.snapshot()` — a plain-dict view (ints for counters,
  ``{"count", "sum", "buckets"}`` dicts for histograms) that travels
  over the PS control plane's ``"stats"`` op as ordinary wire data.
* `merge()` — sum any number of such snapshots (native + fallback,
  or successive polls) field-for-field.
* `prometheus_text()` — render a snapshot in Prometheus exposition
  format; `tools/ps_stats.py --prom` serves it for scraping.
"""
from __future__ import annotations

import os
import threading
from typing import Dict, Optional

HIST_BUCKETS = 32  # == ptpu::kHistBuckets (csrc/ptpu_stats.h)


def hist_bucket_of(v: int) -> int:
    """Bucket index of a non-negative integer value (log2 layout)."""
    if v <= 0:
        return 0
    return min(int(v).bit_length(), HIST_BUCKETS - 1)


class Counter:
    """Monotonic counter. `add` is exact under threads (the PS serve
    threads bump these concurrently), so it locks — the lock is shared
    per registry and uncontended at PS frame rates."""

    __slots__ = ("_lock", "_v")

    def __init__(self, lock: threading.Lock):
        self._lock = lock
        self._v = 0

    def add(self, n: int = 1) -> None:
        with self._lock:
            self._v += n

    @property
    def value(self) -> int:
        return self._v

    def reset(self) -> None:
        with self._lock:
            self._v = 0


class Histogram:
    """Fixed-bucket log2 histogram (native-layout twin)."""

    __slots__ = ("_lock", "buckets", "count", "sum")

    def __init__(self, lock: threading.Lock):
        self._lock = lock
        self.buckets = [0] * HIST_BUCKETS
        self.count = 0
        self.sum = 0

    def observe(self, v) -> None:
        v = int(v)
        with self._lock:
            self.buckets[hist_bucket_of(v)] += 1
            self.count += 1
            self.sum += v

    def to_dict(self) -> dict:
        return {"count": self.count, "sum": self.sum,
                "buckets": list(self.buckets)}

    def reset(self) -> None:
        with self._lock:
            self.buckets = [0] * HIST_BUCKETS
            self.count = 0
            self.sum = 0


class Registry:
    """Named Counter/Histogram set with a dict snapshot. Stats are
    created on first use, so call sites never pre-declare."""

    def __init__(self):
        self._lock = threading.Lock()
        self._stats: Dict[str, object] = {}

    def counter(self, name: str) -> Counter:
        s = self._stats.get(name)
        if s is None:
            with self._lock:
                s = self._stats.setdefault(name, Counter(self._lock))
        if not isinstance(s, Counter):
            raise TypeError(f"stat {name!r} is not a Counter")
        return s

    def histogram(self, name: str) -> Histogram:
        s = self._stats.get(name)
        if s is None:
            with self._lock:
                s = self._stats.setdefault(name, Histogram(self._lock))
        if not isinstance(s, Histogram):
            raise TypeError(f"stat {name!r} is not a Histogram")
        return s

    def snapshot(self) -> dict:
        out = {}
        for name, s in list(self._stats.items()):
            out[name] = s.value if isinstance(s, Counter) else s.to_dict()
        return out

    def reset(self) -> None:
        for s in list(self._stats.values()):
            s.reset()


# Process-default registry: trainer-side metrics (hapi callbacks etc.)
# land here so one prometheus_text(REGISTRY.snapshot()) exposes them.
REGISTRY = Registry()


def static(name: str, value: int) -> None:
    """A counter that states a size the program fixed when it was built
    or traced (experts held, rows of a buffer, a top-k): set, not added
    to, so building twice reads the same."""
    c = REGISTRY.counter(name)
    c.reset()
    c.add(int(value))


def merge(*snapshots) -> dict:
    """Sum snapshot dicts field-for-field: numbers add, bucket lists
    add element-wise, nested dicts (histograms, per-table sections)
    recurse. `None` entries are skipped, so
    `merge(py_side, native_side_or_None)` just works. Non-summable
    values (backend tags, bools, rank labels…) keep the FIRST
    occurrence — merging two full `stats_snapshot()` dicts never
    concatenates strings or adds flags."""
    def summable(v):
        return isinstance(v, (int, float)) and not isinstance(v, bool)

    out: dict = {}
    for snap in snapshots:
        if not snap:
            continue
        for k, v in snap.items():
            if k not in out:
                out[k] = [x + 0 for x in v] if isinstance(v, list) else \
                    (merge(v) if isinstance(v, dict) else v)
            elif isinstance(v, dict) and isinstance(out[k], dict):
                out[k] = merge(out[k], v)
            elif isinstance(v, list) and isinstance(out[k], list):
                out[k] = [a + b for a, b in zip(out[k], v)]
            elif summable(v) and summable(out[k]):
                out[k] = out[k] + v
            # else: tag/flag (or type mismatch) — first occurrence wins
    return out


def _prom_name(*parts: str) -> str:
    name = "_".join(p for p in parts if p)
    return "".join(c if c.isalnum() or c == "_" else "_" for c in name)


def _is_hist(v) -> bool:
    return isinstance(v, dict) and set(v) >= {"count", "sum", "buckets"}


def _prom_emit(lines, name, v, labels: str, seen: set):
    """One metric family sample set in proper exposition form:
    histograms render CUMULATIVE ``le``-edged ``_bucket`` series plus
    ``_sum``/``_count`` (so ``histogram_quantile`` works in Grafana),
    and each family gets exactly ONE ``# TYPE`` line even when it
    repeats under different label sets (per-table metrics)."""
    if _is_hist(v):
        if name not in seen:
            seen.add(name)
            lines.append(f"# TYPE {name} histogram")
        cum = 0
        nb = len(v["buckets"])
        for b, c in enumerate(v["buckets"]):
            cum += c
            # log2 bucket b covers [2**(b-1), 2**b): upper edge is
            # 2**b - 1; the overflow tail is +Inf
            le = "0" if b == 0 else ("+Inf" if b == nb - 1
                                     else str(2 ** b - 1))
            sep = "," if labels else ""
            lines.append(f'{name}_bucket{{{labels}{sep}le="{le}"}} {cum}')
        lines.append(f"{name}_sum{{{labels}}} {v['sum']}" if labels
                     else f"{name}_sum {v['sum']}")
        lines.append(f"{name}_count{{{labels}}} {v['count']}" if labels
                     else f"{name}_count {v['count']}")
    else:
        if name not in seen:
            seen.add(name)
            lines.append(f"# TYPE {name} counter")
        lines.append(f"{name}{{{labels}}} {v}" if labels
                     else f"{name} {v}")


def prometheus_text(snapshot: dict, prefix: str = "ptpu",
                    labels: Optional[Dict[str, str]] = None) -> str:
    """Render a (possibly nested) snapshot in Prometheus exposition
    format. Nested dict keys join the metric name with ``_``, except a
    ``"tables"`` level: its children become a ``table="<name>"`` label
    (per-table stats stay one metric family).

    The C twin (``csrc/ptpu_trace.cc PromFromStatsJson``, behind the
    servers' ``GET /metrics``) walks the same snapshot the same way —
    the two outputs are byte-identical for identical snapshots
    (tested in tests/test_trace.py)."""
    base = ",".join(f'{k}="{v}"' for k, v in (labels or {}).items())
    lines: list = []
    seen: set = set()

    def walk(path, node, lbl):
        for k, v in node.items():
            if k == "tables" and isinstance(v, dict) and not _is_hist(v):
                for tname, tnode in v.items():
                    sep = "," if lbl else ""
                    walk(path + ["table"], tnode,
                         f'{lbl}{sep}table="{tname}"')
            elif isinstance(v, dict) and not _is_hist(v):
                walk(path + [k], v, lbl)
            elif isinstance(v, (int, float)) or _is_hist(v):
                _prom_emit(lines, _prom_name(prefix, *path, k), v, lbl,
                           seen)
            # strings/None (backend tags etc.) are not metrics: skipped

    walk([], snapshot, base)
    return "\n".join(lines) + "\n"


# --------------------------------------------------------------------
# Counter-conservation invariants (ISSUE 20) — the Python twin of the
# native manifest in csrc/ptpu_invar.h. The two strings are
# TOKEN-IDENTICAL (enforced by `python3 tools/ptpu_check.py --check
# invar` and, against a live .so, by ptpu_invar_manifest()), so this
# evaluator needs neither codegen nor a csrc/ checkout. Grammar and
# quiesce semantics: see the header comment of csrc/ptpu_invar.h.

INVAR_MANIFEST = """\
# ptpu_invar manifest — counter conservation laws (twin: profiler/stats.py)

# ---- serving + PS shared net plane (csrc/ptpu_net.cc) ----
counter serving,ps server.conns_accepted csrc/ptpu_net.cc stats_->conns_accepted
counter serving,ps server.conns_closed csrc/ptpu_net.cc stats_->conns_closed
counter serving,ps server.handshake_fails csrc/ptpu_net.cc stats_->handshake_fails
counter serving,ps server.handshake_timeouts csrc/ptpu_net.cc stats_->handshake_timeouts
gauge serving,ps server.conns_active csrc/ptpu_net.cc active_conns

# every framed conn accepted is either still active or was closed —
# exact because accept pairs accepted++ with active++ and FinishClose
# pairs closed++ with active-- (telemetry HTTP conns are exempt and
# uncounted on both sides)
invar serving,ps conn_balance server.conns_accepted == server.conns_active + server.conns_closed
# handshake failures/timeouts are close reasons of counted conns
# (idle_closes is NOT listed: HTTP conns may idle-close uncounted)
invar serving,ps close_reasons server.conns_closed >= server.handshake_fails + server.handshake_timeouts

# ---- serving request plane (csrc/ptpu_serving.cc) ----
counter serving server.requests csrc/ptpu_serving.cc stats.requests
counter serving server.replies csrc/ptpu_serving.cc stats.replies
counter serving server.req_errors csrc/ptpu_serving.cc stats.req_errors
counter serving server.op_errors csrc/ptpu_serving.cc stats.op_errors
counter serving server.err_frames csrc/ptpu_serving.cc stats.err_frames
# the PS data plane reuses the err_frames name for its own ledger
counter ps server.err_frames csrc/ptpu_ps_server.cc stats.err_frames

# the zero-stuck-requests proof: every accepted INFER request is
# answered exactly once — a reply or an error frame (replies are
# counted at send-decision time, so a killed conn still balances;
# decode/meta op errors land in op_errors, not here)
invar serving req_balance server.requests == server.replies + server.req_errors
# every ERR frame is attributed to exactly one plane: INFER
# (req_errors) or decode/meta op (op_errors) — proto errors close
# the conn without an ERR frame and count in neither
invar serving err_split server.err_frames == server.req_errors + server.op_errors
pair csrc/ptpu_serving.cc stats.req_errors stats.err_frames
pair csrc/ptpu_serving.cc stats.op_errors stats.err_frames

# ---- decode session ledger (csrc/ptpu_serving.cc, dstats) ----
counter serving decode.opens csrc/ptpu_serving.cc dstats.opens
counter serving decode.closes csrc/ptpu_serving.cc dstats.closes
counter serving decode.evictions csrc/ptpu_serving.cc dstats.evictions
counter serving decode.hibernates csrc/ptpu_serving.cc dstats.hibernates
counter serving decode.restores csrc/ptpu_serving.cc dstats.restores
counter serving decode.forks csrc/ptpu_serving.cc dstats.forks
gauge serving decode.sessions_active csrc/ptpu_serving.cc sessions_active
gauge serving decode.sessions_hibernated csrc/ptpu_serving.cc sessions_hibernated

# every session ever opened is live, hibernated, or exited exactly
# once as a close or an eviction (tombstones count at eviction time;
# closing a tombstone later is NOT a second exit)
invar serving session_balance decode.opens == decode.closes + decode.evictions + decode.sessions_active + decode.sessions_hibernated
invar serving hibernate_flow decode.hibernates >= decode.restores
# a fork IS an open (fork path bumps both)
invar serving forks_are_opens decode.opens >= decode.forks
pair csrc/ptpu_serving.cc dstats.forks dstats.opens

# ---- KV pool page + hibernation ledgers (csrc/ptpu_predictor.cc) ----
gauge serving decode.pool.pages_total csrc/ptpu_predictor.cc npages_
gauge serving decode.pool.pages_in_use csrc/ptpu_predictor.cc npages_
gauge serving decode.pool.pages_free csrc/ptpu_predictor.cc free_
gauge serving decode.pool.pages_cached csrc/ptpu_predictor.cc pages_cached
gauge serving decode.pool.sessions_hibernated csrc/ptpu_predictor.cc hib_
counter serving decode.pool.hibernates csrc/ptpu_predictor.cc hibernates_
counter serving decode.pool.restores csrc/ptpu_predictor.cc restores_
counter serving decode.pool.hib_drops csrc/ptpu_predictor.cc hib_drops_
gauge serving decode.pool.spill_slots_total csrc/ptpu_predictor.cc slots_total
gauge serving decode.pool.spill_slots_in_use csrc/ptpu_predictor.cc slots_in_use

# page conservation: the pool never leaks or invents a page —
# rendered under one mu_ hold, so this is exact at ANY instant
invar serving page_balance decode.pool.pages_total == decode.pool.pages_in_use + decode.pool.pages_free
# cached (published, ref==1) pages are a subset of in-use pages
invar serving cache_subset decode.pool.pages_in_use >= decode.pool.pages_cached
# every hibernation record ever created was restored, dropped, or is
# still resident in the registry — exact under mu_
invar serving pool_hib_balance decode.pool.hibernates == decode.pool.restores + decode.pool.hib_drops + decode.pool.sessions_hibernated
invar serving spill_slots decode.pool.spill_slots_total >= decode.pool.spill_slots_in_use
"""


def _invar_laws():
    """Parse the ``invar`` lines of INVAR_MANIFEST (counter/gauge/pair
    declarations feed the static checker, not the evaluator)."""
    laws = []
    for line in INVAR_MANIFEST.splitlines():
        line = line.split("#", 1)[0]
        tok = line.split()
        if len(tok) < 6 or tok[0] != "invar":
            continue
        rhs = [t for t in tok[5:] if t != "+"]
        laws.append({
            "planes": tok[1].split(","),
            "name": tok[2],
            "lhs": tok[3],
            "exact": tok[4] == "==",
            "rhs": rhs,
            "text": f"{tok[3]} {tok[4]} " + " + ".join(rhs),
        })
    return laws


def _invar_resolve(snapshot, path):
    """Dot-path lookup; ``None`` when a step is missing or the leaf is
    not an integer (histogram dicts, strings)."""
    node = snapshot
    for key in path.split("."):
        if not isinstance(node, dict) or key not in node:
            return None
        node = node[key]
    if isinstance(node, bool) or not isinstance(node, int):
        return None
    return node


def invar_check(snapshot, plane: str = "auto") -> dict:
    """Evaluate every conservation law against a stats snapshot dict.

    Returns the same report shape as the native evaluator
    (``ptpu_invar_check_json``): ``{"enabled": 0|1, "plane": ...,
    "checked": N, "skipped": N, "violations": {name: {"law": ...,
    "detail": ...}}}``. ``==`` laws are authoritative only at quiesce;
    ``>=`` laws hold at any instant (csrc/ptpu_invar.h). The
    ``PTPU_INVAR_OFF=1`` kill switch disables the gate here exactly as
    it does natively."""
    off = os.environ.get("PTPU_INVAR_OFF", "")
    if off and off != "0":
        return {"enabled": 0, "plane": plane, "checked": 0,
                "skipped": 0, "violations": {}}
    violations: dict = {}
    checked = skipped = 0
    if not isinstance(snapshot, dict):
        violations["snapshot"] = {
            "law": "parse",
            "detail": "stats snapshot is not restricted JSON"}
        plane = plane if plane not in ("", "auto") else "auto"
    else:
        if plane in ("", "auto"):
            plane = "serving" if "batcher" in snapshot else "ps"
        for law in _invar_laws():
            if plane not in law["planes"]:
                continue
            lhs = _invar_resolve(snapshot, law["lhs"])
            if lhs is None:
                skipped += 1  # optional subsystem: law inactive
                continue
            checked += 1
            total = 0
            missing = None
            for term in law["rhs"]:
                v = _invar_resolve(snapshot, term)
                if v is None:
                    missing = term
                    break
                total += v
            if missing is not None:
                violations[law["name"]] = {
                    "law": law["text"],
                    "detail": f"term {missing} missing from snapshot"}
                continue
            holds = lhs == total if law["exact"] else lhs >= total
            if not holds:
                cmp = "!=" if law["exact"] else "<"
                violations[law["name"]] = {
                    "law": law["text"],
                    "detail": (f"{law['lhs']} = {lhs} {cmp} {total}"
                               " = sum(rhs)")}
    return {"enabled": 1, "plane": plane, "checked": checked,
            "skipped": skipped, "violations": violations}


def invar_assert(snapshot, where: str = "", plane: str = "auto") -> dict:
    """Gate form of :func:`invar_check` — the Python-twin analogue of
    ``ptpu::invar::GateQuiesced``. Raises ``AssertionError`` naming
    every violated law; returns the clean report otherwise. Benches
    and the drill soak call this at their quiesce points instead of
    re-deriving counter arithmetic by hand."""
    report = invar_check(snapshot, plane)
    if report["violations"]:
        detail = "; ".join(
            f"{name}: {v['detail']}"
            for name, v in sorted(report["violations"].items()))
        raise AssertionError(
            f"ptpu_invar[{where or report['plane']}]: {detail} "
            f"(PTPU_INVAR_OFF=1 disables)")
    return report
