"""Query telemetry warehouse: one durable, minable row per query.

Every query — collected plan or ``TpuProcessCluster.run_query``,
whether it completed, was cancelled, degraded down the ladder, or
crashed — leaves behind exactly ONE JSON row recording what it cost
and why: tenant, plan/SQL fingerprints, ``device_kind``, admission
wait, compile-vs-execute split (``split.jit_s``, the host's trace,
lowering and compile seconds, beside ``dispatch_s`` and
``op_time_s``), per-operator time/rows, bytes moved per transport
(host file / ICI / gang-DCN), spill read+write bytes, scan
device/fallback chunk counts, fused dispatch, JIT-variant and re-jit
(``fusion.jit_misses``) counts, degradation rungs walked, and the
classified cancel/fallback reasons.  The rows are the substrate the
cost-model fitting (ROADMAP item 3) reads and the load harness (item 2)
gates on; on a CPU-only host they are the *only* trustworthy
regression signal (re-anchor note: structural counters, never wall
time).

Durability: rows append to sealed JSONL segments — every append
rewrites the current segment through ``shuffle/integrity.py``'s
tmp + CRC32C footer + ``os.replace`` protocol, so a crash mid-append
leaves either the previous sealed segment or the new one, never a
half row.  Readers verify the seal and fall back to line-by-line
salvage on a torn/corrupt tail (the classified-read analog of the
flight recorder's torn-ring tolerance).  Retention follows
``spark.rapids.trace.maxFiles`` semantics: oldest segments beyond
``spark.rapids.warehouse.maxFiles`` are pruned at write time.

On top of the rows, the **drift sentinel** (``profiling warehouse`` /
``profiling drift``) mines rollups per tenant and per plan
fingerprint and flags *structural* regressions between runs on the
same ``device_kind`` — fused-dispatch count up, fallback chunks
appearing, JIT-variant bound exceeded, bytes-moved delta beyond
tolerance — refusing cross-``device_kind`` comparisons with the same
rc-3 semantics as ``profiling compare``.
"""
from __future__ import annotations

import glob
import json
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

from ..config import register

WAREHOUSE_ENABLED = register(
    "spark.rapids.warehouse.enabled", True,
    "Emit one sealed telemetry-warehouse row per query (completed, "
    "cancelled, degraded, or failed) when spark.rapids.warehouse.dir "
    "is set. The writer is a single host-side JSON append per query "
    "(no device syncs); disable only to A/B its overhead.")
WAREHOUSE_DIR = register(
    "spark.rapids.warehouse.dir", "",
    "Directory for warehouse segments (wh-<pid>-<ms>.jsonl, sealed "
    "with the shuffle-block CRC32C footer). Empty disables the "
    "warehouse entirely.")
WAREHOUSE_MAX_FILES = register(
    "spark.rapids.warehouse.maxFiles", 64,
    "On-disk retention: oldest warehouse segments beyond this count "
    "are pruned at write time (spark.rapids.trace.maxFiles "
    "semantics), bounding a long-lived session's footprint.")
WAREHOUSE_SEGMENT_ROWS = register(
    "spark.rapids.warehouse.segment.maxRows", 128,
    "Rows per segment before the writer rolls to a new file. Each "
    "append rewrites the current segment through the sealed tmp+"
    "rename protocol, so smaller segments bound the rewrite cost.")
DRIFT_BYTES_TOLERANCE = register(
    "spark.rapids.warehouse.drift.bytesTolerance", 0.25,
    "Drift sentinel: relative increase in total bytes moved "
    "(transports + spill) between two runs of the same plan "
    "fingerprint on the same device_kind that counts as a "
    "structural regression.")
DRIFT_VARIANT_BOUND = register(
    "spark.rapids.warehouse.drift.variantBound", 8,
    "Drift sentinel: a run whose live JIT-variant count exceeds this "
    "bound is flagged (the PR 15 fusion design holds variants to a "
    "handful; unbounded growth means the quantized-arena keying "
    "regressed).")
STATUS_ROWS = register(
    "spark.rapids.warehouse.statusRows", 5,
    "How many most-recent warehouse rows the /status endpoint "
    "embeds (query id, tenant, outcome, wall seconds).")

#: bump when row fields change shape incompatibly
ROW_VERSION = 1

__all__ = [
    "WAREHOUSE_ENABLED", "WAREHOUSE_DIR", "WAREHOUSE_MAX_FILES",
    "WAREHOUSE_SEGMENT_ROWS", "DRIFT_BYTES_TOLERANCE",
    "DRIFT_VARIANT_BOUND", "STATUS_ROWS", "ROW_VERSION",
    "WarehouseReadError", "warehouse_dir", "append_row", "read_rows",
    "tail_rows", "render_warehouse", "drift_report",
]


class WarehouseReadError(Exception):
    """Classified segment read failure (missing|torn|corrupt|io)."""

    def __init__(self, kind: str, path: str, detail: str = ""):
        self.kind = kind
        self.path = path
        self.detail = detail
        super().__init__(f"warehouse segment {kind}: {path} ({detail})")


def warehouse_dir(conf) -> Optional[str]:
    """The resolved warehouse directory, or None when disabled."""
    try:
        if not conf.get(WAREHOUSE_ENABLED):
            return None
        d = conf.get(WAREHOUSE_DIR)
    except Exception:  # noqa: BLE001 — foreign conf objects
        return None
    return d or None


# --- writer -----------------------------------------------------------------

class _Segment:
    __slots__ = ("path", "lines", "pid")

    def __init__(self, path: str):
        self.path = path
        self.lines: List[str] = []
        self.pid = os.getpid()


_seg_lock = threading.Lock()
_segments: Dict[str, _Segment] = {}


def _new_segment(d: str) -> _Segment:
    base = f"wh-{os.getpid()}-{int(time.time() * 1000)}"
    path = os.path.join(d, base + ".jsonl")
    n = 0
    while os.path.exists(path):  # same-ms roll: disambiguate
        n += 1
        path = os.path.join(d, f"{base}-{n}.jsonl")
    return _Segment(path)


def append_row(conf, row: Dict) -> Optional[str]:
    """Append one query row to the current sealed segment; returns the
    segment path (None when the warehouse is disabled). Crash-safe:
    the segment is rewritten through tmp + CRC footer + rename, so a
    crash mid-append can never tear an existing row."""
    d = warehouse_dir(conf)
    if d is None:
        return None
    from ..shuffle.integrity import write_sealed_file
    from .recorder import prune_oldest
    row = dict(row)
    row.setdefault("version", ROW_VERSION)
    row.setdefault("ts", time.time())
    line = json.dumps(row, sort_keys=True, default=str)
    os.makedirs(d, exist_ok=True)
    with _seg_lock:
        seg = _segments.get(d)
        if seg is None or seg.pid != os.getpid() \
                or len(seg.lines) >= max(1, conf.get(WAREHOUSE_SEGMENT_ROWS)):
            seg = _new_segment(d)
            _segments[d] = seg
        seg.lines.append(line)
        payload = ("\n".join(seg.lines) + "\n").encode()
        try:
            # tpu-lint: allow[blocking-under-lock] the lock serializes the segment rewrite itself; one row per QUERY, never on a task path
            write_sealed_file(seg.path, payload)
        except OSError:
            # disk trouble must never fail the query it attributes;
            # drop the in-memory line too so state matches disk
            seg.lines.pop()
            return None
        # tpu-lint: allow[blocking-under-lock] retention unlink rides the same once-per-query append; contention is nil by construction
        prune_oldest(d, conf.get(WAREHOUSE_MAX_FILES),
                     prefix="wh-", suffix=".jsonl")
    return seg.path


# --- reader -----------------------------------------------------------------

def _segment_rows(path: str) -> Tuple[List[Dict], bool]:
    """Rows of one segment. Verified read first; a torn/corrupt seal
    falls back to raw line-by-line salvage (unparseable tail lines —
    including the binary footer — are skipped). Returns
    (rows, salvaged)."""
    from ..shuffle.integrity import read_sealed_file
    raw: Optional[bytes] = None
    salvaged = False
    try:
        raw = bytes(read_sealed_file(
            path, lambda kind, detail: WarehouseReadError(
                kind, path, detail)))
    except WarehouseReadError as e:
        if e.kind == "missing":
            return [], False
        salvaged = True
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except OSError:
            return [], True
    rows: List[Dict] = []
    for ln in raw.split(b"\n"):
        if not ln.strip():
            continue
        try:
            doc = json.loads(ln)
        except (ValueError, UnicodeDecodeError):
            continue  # torn tail / sealed footer bytes
        if isinstance(doc, dict):
            rows.append(doc)
    return rows, salvaged


def read_rows(d: str) -> List[Dict]:
    """Every row across every segment, oldest first (by ``ts``).
    Torn/corrupt segments contribute their salvageable prefix."""
    rows: List[Dict] = []
    for path in sorted(glob.glob(os.path.join(d, "wh-*.jsonl"))):
        rows.extend(_segment_rows(path)[0])
    rows.sort(key=lambda r: r.get("ts", 0.0))
    return rows


def tail_rows(d: str, n: int) -> List[Dict]:
    """The newest ``n`` rows, compacted for the /status endpoint."""
    out = []
    for r in read_rows(d)[-max(0, n):]:
        out.append({k: r.get(k) for k in
                    ("query_id", "tenant", "outcome", "wall_s",
                     "device_kind", "fingerprint")})
    return out


# --- rollups + drift sentinel ----------------------------------------------

def _total_bytes(row: Dict) -> int:
    b = row.get("bytes") or {}
    s = row.get("spill") or {}
    return int(sum(int(v or 0) for v in b.values())
               + sum(int(v or 0) for v in s.values()))


def _rollup(rows: List[Dict]) -> Tuple[Dict, Dict]:
    """(per-tenant, per-fingerprint) aggregates."""
    tenants: Dict[str, Dict] = {}
    plans: Dict[str, List[Dict]] = {}
    for r in rows:
        t = tenants.setdefault(str(r.get("tenant") or "default"), {
            "queries": 0, "outcomes": {}, "wall_s": 0.0,
            "admission_wait_s": 0.0, "bytes": 0, "spill_write": 0})
        t["queries"] += 1
        oc = str(r.get("outcome") or "unknown")
        t["outcomes"][oc] = t["outcomes"].get(oc, 0) + 1
        t["wall_s"] += float(r.get("wall_s") or 0.0)
        t["admission_wait_s"] += float(r.get("admission_wait_s") or 0.0)
        t["bytes"] += _total_bytes(r)
        t["spill_write"] += int((r.get("spill") or {})
                                .get("write_bytes") or 0)
        fp = r.get("fingerprint")
        if fp:
            plans.setdefault(str(fp), []).append(r)
    return tenants, plans


def render_warehouse(d: str) -> str:
    """Human rollup: per-tenant cost table + per-plan-fingerprint
    structural summary over every readable row."""
    rows = read_rows(d)
    out = [f"=== telemetry warehouse ({d}) ===",
           f"rows: {len(rows)}"]
    if not rows:
        return "\n".join(out)
    tenants, plans = _rollup(rows)
    out.append("")
    out.append("-- per tenant --")
    for name in sorted(tenants):
        t = tenants[name]
        ocs = ",".join(f"{k}={v}" for k, v in sorted(t["outcomes"].items()))
        out.append(
            f"  {name:<12} queries={t['queries']:<4} [{ocs}] "
            f"wall={t['wall_s']:.3f}s adm_wait={t['admission_wait_s']:.3f}s "
            f"bytes={t['bytes']} spill_w={t['spill_write']}")
    out.append("")
    out.append("-- per plan fingerprint --")
    for fp in sorted(plans):
        runs = plans[fp]
        last = runs[-1]
        f = last.get("fusion") or {}
        sc = last.get("scan") or {}
        out.append(
            f"  {fp:<18} runs={len(runs):<3} "
            f"device_kind={last.get('device_kind')} "
            f"dispatches={f.get('fused_dispatches', 0)} "
            f"variants={f.get('jit_variants', 0)} "
            f"fallback_chunks={sc.get('fallback_chunks', 0)} "
            f"bytes={_total_bytes(last)}")
    return "\n".join(out)


def drift_report(d: str, *, bytes_tolerance: Optional[float] = None,
                 variant_bound: Optional[int] = None,
                 allow_cross_device: bool = False) -> Tuple[str, int]:
    """Structural drift between the latest run of each plan
    fingerprint and its most recent prior run on the SAME
    ``device_kind``. Returns ``(report, rc)``: rc 0 clean, rc 1
    regressions flagged, rc 3 refused (only a cross-``device_kind``
    baseline exists — matching ``profiling compare`` semantics;
    ``allow_cross_device`` downgrades the refusal to a warning)."""
    if bytes_tolerance is None:
        bytes_tolerance = DRIFT_BYTES_TOLERANCE.default
    if variant_bound is None:
        variant_bound = DRIFT_VARIANT_BOUND.default
    rows = read_rows(d)
    _, plans = _rollup(rows)
    flagged: List[str] = []
    refused: List[str] = []
    warnings: List[str] = []
    compared = 0
    for fp in sorted(plans):
        runs = plans[fp]
        latest = runs[-1]
        kind = latest.get("device_kind")
        base = None
        cross = None
        for prev in reversed(runs[:-1]):
            if prev.get("device_kind") == kind:
                base = prev
                break
            if cross is None:
                cross = prev
        if base is None and cross is not None:
            if not allow_cross_device:
                refused.append(
                    f"  {fp}: latest device_kind={kind!r} has only a "
                    f"{cross.get('device_kind')!r} baseline")
                continue
            warnings.append(
                f"  WARNING {fp}: comparing across device_kind "
                f"({cross.get('device_kind')!r} -> {kind!r}) — "
                f"structural counters may legitimately differ")
            base = cross
        if base is None:
            continue  # first run of this plan: nothing to compare
        compared += 1
        lf = latest.get("fusion") or {}
        bf = base.get("fusion") or {}
        ls = latest.get("scan") or {}
        bs = base.get("scan") or {}
        ld, bd = int(lf.get("fused_dispatches") or 0), \
            int(bf.get("fused_dispatches") or 0)
        if ld > bd:
            flagged.append(
                f"  REGRESSION {fp} fusedDispatches: {bd} -> {ld} "
                f"(+{ld - bd})")
        lfb, bfb = int(ls.get("fallback_chunks") or 0), \
            int(bs.get("fallback_chunks") or 0)
        if lfb > 0 and lfb > bfb:
            flagged.append(
                f"  REGRESSION {fp} fallbackChunks: {bfb} -> {lfb} "
                f"(scan left the device)")
        lv = int(lf.get("jit_variants") or 0)
        if lv > int(variant_bound):
            flagged.append(
                f"  REGRESSION {fp} jitVariants: {lv} exceeds bound "
                f"{int(variant_bound)}")
        lb, bb = _total_bytes(latest), _total_bytes(base)
        if bb > 0 and (lb - bb) / bb > float(bytes_tolerance):
            flagged.append(
                f"  REGRESSION {fp} bytesMoved: {bb} -> {lb} "
                f"(+{(lb - bb) / bb:.0%} > {float(bytes_tolerance):.0%} "
                f"tolerance)")
    if refused:
        head = ["=== drift REFUSED: device_kind mismatch ===",
                *refused,
                "",
                "Structural counters are only comparable on the same "
                "device_kind (see `profiling compare`). Re-run the "
                "baseline on this hardware, or pass "
                "--allow-cross-device to force."]
        return "\n".join(head), 3
    out = [f"=== warehouse drift ({d}) ===",
           f"fingerprints: {len(plans)}  compared: {compared}"]
    out.extend(warnings)
    if flagged:
        out.extend(flagged)
        out.append(f"drift: {len(flagged)} structural regression(s)")
        return "\n".join(out), 1
    out.append("drift: clean (no structural regressions)")
    return "\n".join(out), 0
