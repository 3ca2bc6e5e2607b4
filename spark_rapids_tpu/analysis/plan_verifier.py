"""Static plan verifier: pre-execution operator-contract checking.

The reference stack declares what every GPU operator supports and audits
that surface at build time (SURVEY.md §2.2-F); machine-generated plans
(the SQL frontend, external bridge clients) make the same guarantees
necessary at PLAN time here. `verify_plan` runs a bottom-up pass over a
physical exec tree — in `planner.py` before execution, on by default
under ``spark.rapids.sql.verifyPlan`` — and rejects broken plans with a
*named* reason instead of letting a kernel throw (or the device OOM)
mid-query.

Contracts are declared on the `TpuExec` subclasses themselves
(`exec/base.py::OpContract` + per-operator overrides), so this verifier
and the SUPPORTED_OPS.md generator read the same source of truth.

Checked defect classes (the ``reason`` names are stable API — tests,
the event log, and CI match on them):

- ``schema_mismatch``       — an operator's declared output schema
  disagrees with what its current children imply, or a bound expression
  references an ordinal/dtype its input schema does not have (the
  stale-rebuild class: `with_new_children` over different-shaped
  children).
- ``nullability_lie``       — an output field or bound reference claims
  non-nullable over a nullable input (downstream kernels would elide
  null handling and return wrong data).
- ``missing_exchange``      — a hash join whose children are both
  shuffle exchanges with disagreeing partitioning (scheme or partition
  count): rows with equal keys would land in different partitions.
- ``malformed_aqe_wrapper`` — a planner-inserted adaptive wrapper over
  the wrong child type (AQE read not over an exchange, AQE join switch
  not over a shuffled hash join).
- ``hbm_over_budget``       — a resident-footprint operator (broadcast
  build, single-pass aggregate) whose static byte estimate exceeds the
  memory-ledger HBM budget: the plan cannot fit and would OOM after
  doing work.
- ``unsupported_dtype``     — sort/group/join/partition keys of a type
  no engine path can compare or hash (map types, at any nesting depth).

The report is machine-readable (`VerifyReport.to_dict`) and the module
is runnable: ``python -m spark_rapids_tpu.analysis.plan_verifier
--smoke`` verifies the whole NDS corpus clean and asserts one seeded
defect is rejected (tier-1 holds both: tests/test_plan_verifier.py).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from .. import datatypes as dt
from ..config import RapidsConf

__all__ = ["PlanVerificationError", "PlanVerifier", "VerifyReport",
           "verify_plan"]


@dataclasses.dataclass
class Violation:
    reason: str   # stable defect-class name (see module docstring)
    op: str       # node label, e.g. ShuffledHashJoinExec#12
    detail: str

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)


class VerifyReport:
    def __init__(self):
        self.violations: List[Violation] = []
        self.nodes_checked = 0
        self.hbm_estimate_bytes: Optional[int] = None
        self.hbm_budget_bytes: Optional[int] = None

    @property
    def ok(self) -> bool:
        return not self.violations

    def add(self, reason: str, node, detail: str):
        self.violations.append(Violation(reason, node.node_label(), detail))

    def reasons(self) -> List[str]:
        return sorted({v.reason for v in self.violations})

    def to_dict(self) -> Dict:
        return {
            "ok": self.ok,
            "nodes_checked": self.nodes_checked,
            "violations": [v.to_dict() for v in self.violations],
            "hbm_estimate_bytes": self.hbm_estimate_bytes,
            "hbm_budget_bytes": self.hbm_budget_bytes,
        }

    def summary(self) -> str:
        if self.ok:
            return f"plan ok ({self.nodes_checked} nodes)"
        return "; ".join(f"[{v.reason}] {v.op}: {v.detail}"
                         for v in self.violations)


class PlanVerificationError(RuntimeError):
    """A plan failed static verification; `.report` has the details."""

    def __init__(self, report: VerifyReport):
        self.report = report
        super().__init__(f"plan rejected by the static verifier: "
                         f"{report.summary()}")


def _contains_map(t: dt.DataType) -> bool:
    if isinstance(t, dt.MapType):
        return True
    if isinstance(t, dt.ArrayType):
        return _contains_map(t.element_type)
    if isinstance(t, dt.StructType):
        return any(_contains_map(f.dtype) for f in t.fields)
    return False


def _walk_expr(expr):
    out = [expr]
    for c in getattr(expr, "children", ()):
        out.extend(_walk_expr(c))
    return out


def _schema_sig(schema: dt.Schema) -> List[Tuple[str, dt.DataType]]:
    return [(f.name, f.dtype) for f in schema.fields]


class PlanVerifier:
    """Bottom-up contract checking over one physical plan tree."""

    def __init__(self, conf: Optional[RapidsConf] = None):
        self.conf = conf or RapidsConf()

    # --- entry point ------------------------------------------------------

    def verify(self, root) -> VerifyReport:
        report = VerifyReport()
        from ..memory import resolve_device_budget
        report.hbm_budget_bytes = resolve_device_budget(self.conf)
        self._visit(root, report)
        return report

    def _visit(self, node, report: VerifyReport) -> Optional[int]:
        """Post-order: returns the node's static output byte estimate
        (None = unknown) while running every contract check."""
        child_bytes = [self._visit(c, report) for c in node.children]
        report.nodes_checked += 1
        self._check_wrapper(node, report)
        self._check_schemas(node, report)
        self._check_expr_bindings(node, report)
        self._check_key_dtypes(node, report)
        self._check_copartition(node, report)
        return self._check_footprint(node, child_bytes, report)

    # --- structural checks ------------------------------------------------

    def _check_wrapper(self, node, report):
        want = node.contract().wrapper_over
        if not want:
            return
        child = node.children[0] if node.children else None
        got = type(child).__name__ if child is not None else "<none>"
        if got != want:
            report.add(
                "malformed_aqe_wrapper", node,
                f"{type(node).__name__} requires a {want} child, got "
                f"{got}")

    def _check_schemas(self, node, report):
        try:
            declared = node.output_schema
        except Exception as e:  # noqa: BLE001 — a schema that cannot
            report.add("schema_mismatch", node,   # even be computed
                       f"output schema raises: {e}")
            return
        if node.contract().schema_preserving and node.children:
            self._compare_schemas(node, node.children[0].output_schema,
                                  declared, report, origin="child")
        try:
            expected = node.expected_output_schema()
        except Exception as e:  # noqa: BLE001 — a hook that cannot even
            # derive a schema from the current children IS the defect
            # (stale rebuild); it must surface as a named rejection,
            # not a raw traceback
            report.add("schema_mismatch", node,
                       f"output schema cannot be derived from the "
                       f"current children: {e}")
            return
        if expected is not None:
            self._compare_schemas(node, expected, declared, report,
                                  origin="derived")

    def _compare_schemas(self, node, expected: dt.Schema,
                         declared: dt.Schema, report, origin: str):
        if _schema_sig(expected) != _schema_sig(declared):
            report.add(
                "schema_mismatch", node,
                f"declared output schema {declared!r} does not agree "
                f"with the {origin} schema {expected!r}")
            return
        for ef, df in zip(expected.fields, declared.fields):
            if ef.nullable and not df.nullable:
                report.add(
                    "nullability_lie", node,
                    f"output field {df.name} declared non-nullable but "
                    f"the {origin} schema says {ef.name} is nullable")

    def _check_expr_bindings(self, node, report):
        from ..expr.base import BoundReference
        try:
            bindings = list(node.expr_bindings())
        except Exception as e:  # noqa: BLE001 — same rationale as the
            report.add("schema_mismatch", node,  # schema hook guard
                       f"expression bindings cannot be derived from "
                       f"the current children: {e}")
            return
        for expr, schema in bindings:
            if expr is None or schema is None:
                continue
            for e in _walk_expr(expr):
                if not isinstance(e, BoundReference):
                    continue
                if not (0 <= e.ordinal < len(schema.fields)):
                    report.add(
                        "schema_mismatch", node,
                        f"expression {e!r} references ordinal "
                        f"{e.ordinal} but the input schema has "
                        f"{len(schema.fields)} columns")
                    continue
                f = schema.fields[e.ordinal]
                if e.dtype != f.dtype:
                    report.add(
                        "schema_mismatch", node,
                        f"expression {e!r} expects "
                        f"{e.dtype.simple_string()} at ordinal "
                        f"{e.ordinal} but the input column {f.name} is "
                        f"{f.dtype.simple_string()}")
                elif f.nullable and not e.nullable:
                    report.add(
                        "nullability_lie", node,
                        f"expression {e!r} claims non-nullable but "
                        f"input column {f.name} is nullable")

    def _key_exprs(self, node):
        """(kind, key expressions) whose dtypes must be comparable /
        hashable on some engine path."""
        name = type(node).__name__
        if name in ("TpuSortExec", "_PerBatchTopN"):
            return [("sort key", o.child) for o in node.orders]
        if name == "TpuTopNExec":
            # the per-batch/sort/limit wiring is internal (not in
            # node.children), so the bound orders are read off the
            # inner sort directly
            return [("sort key", o.child) for o in node._sort.orders]
        if name == "TpuWindowExec":
            return ([("window partition key", e)
                     for e in node.part_exprs]
                    + [("window order key", o.child)
                       for o in node.orders])
        if name == "TpuHashAggregateExec":
            return [("group key", e) for e in node.group_exprs]
        if name == "TpuShuffleExchangeExec":
            part = node.partitioning
            keys = getattr(part, "key_exprs", None) or \
                [o.child for o in getattr(part, "orders", [])]
            return [("partition key", e) for e in keys]
        if hasattr(node, "left_keys") and hasattr(node, "right_keys"):
            return [("join key", e)
                    for e in list(node.left_keys) + list(node.right_keys)]
        return []

    def _check_key_dtypes(self, node, report):
        for kind, e in self._key_exprs(node):
            try:
                t = e.dtype
            except Exception:  # noqa: BLE001 — unresolvable keys are
                continue       # caught by the binding checks above
            if _contains_map(t):
                report.add(
                    "unsupported_dtype", node,
                    f"{kind} {e!r} has type {t.simple_string()}: map "
                    "types cannot be compared or hashed on any engine "
                    "path")

    def _check_copartition(self, node, report):
        if not node.contract().requires_copartition:
            return
        if len(node.children) != 2:
            return
        exchanges = [self._unwrap_exchange(c) for c in node.children]
        if any(e is None for e in exchanges):
            # a non-exchange child is the local/broadcast shape — the
            # single-process join core handles it; nothing to prove
            return
        lp, rp = (e.partitioning for e in exchanges)
        if type(lp) is not type(rp):
            report.add(
                "missing_exchange", node,
                f"join children are exchanges with different "
                f"partitioning schemes ({type(lp).__name__} vs "
                f"{type(rp).__name__})")
        elif lp.num_partitions != rp.num_partitions:
            report.add(
                "missing_exchange", node,
                f"join children are hash exchanges with different "
                f"partition counts ({lp.num_partitions} vs "
                f"{rp.num_partitions}); equal keys would land in "
                "different partitions")

    @staticmethod
    def _unwrap_exchange(node):
        from ..exec.aqe import TpuAQEShuffleReadExec
        from ..exec.exchange import TpuShuffleExchangeExec
        if isinstance(node, TpuAQEShuffleReadExec):
            node = node.children[0] if node.children else node
        return node if isinstance(node, TpuShuffleExchangeExec) else None

    # --- static HBM footprint ---------------------------------------------

    def _check_footprint(self, node, child_bytes, report) -> Optional[int]:
        own = node.static_bytes_estimate()
        if own is None:
            known = [b for b in child_bytes if b is not None]
            own = sum(known) if known else None
        if own is not None:
            report.hbm_estimate_bytes = max(
                report.hbm_estimate_bytes or 0, own)
        try:
            resident = node.resident_footprint()
        except Exception:  # noqa: BLE001 — a broken hook must not mask
            resident = False  # the schema findings already collected
        if resident and own is not None \
                and report.hbm_budget_bytes is not None \
                and own > report.hbm_budget_bytes:
            report.add(
                "hbm_over_budget", node,
                f"static estimate {own} bytes must be device-resident "
                f"at once (no out-of-core path) but the HBM ledger "
                f"budget is {report.hbm_budget_bytes} bytes")
        return own


def verify_plan(root, conf: Optional[RapidsConf] = None) -> VerifyReport:
    """Run the contract pass; raises nothing — callers decide whether a
    non-ok report is fatal (planner.py raises PlanVerificationError)."""
    return PlanVerifier(conf).verify(root)


def report_rejection(conf: RapidsConf, report: VerifyReport, root,
                     query_id: str = "") -> None:
    """Make a rejection observable: a ``plan_rejected`` entry in the
    always-on flight-recorder ring (harvested into incident bundles, so
    ``profiling triage`` can show why a query never ran) plus a
    ``plan_rejected`` event-log line when the event log is enabled."""
    from ..obs.recorder import RECORDER
    RECORDER.configure(conf)
    if RECORDER.enabled:
        RECORDER.record(
            "plan", ev="plan_rejected", query=query_id,
            n_violations=len(report.violations),
            reasons=",".join(report.reasons()),
            detail=report.summary()[:600])
    from ..tools.event_log import log_plan_rejected
    log_plan_rejected(conf, report, root, query_id=query_id)


# --- command-line smoke ------------------------------------------------------

def _smoke() -> int:
    """Verify the whole NDS corpus clean, then seed one broken plan and
    require its rejection."""
    import json

    from ..session import TpuSession
    from ..tools import nds
    conf = RapidsConf()
    session = TpuSession(conf)
    tables = nds.gen_tables(1 << 10)
    results = {}
    bad = 0
    for name in sorted(nds.QUERIES):
        plan = nds.build_query(name, session, tables)._node
        rep = verify_plan(plan, conf)
        results[name] = rep.to_dict()
        if not rep.ok:
            bad += 1
    # seeded defect: an AQE read wrapper over a non-exchange child
    from ..exec.aqe import TpuAQEShuffleReadExec
    some = nds.build_query("q3", session, tables)._node
    seeded = verify_plan(TpuAQEShuffleReadExec(some), conf)
    print(json.dumps({
        "nds_clean": bad == 0,
        "nds_queries": len(results),
        "seeded_rejected": not seeded.ok,
        "seeded_reasons": seeded.reasons(),
    }, indent=2))
    if bad:
        for name, rep in results.items():
            if not rep["ok"]:
                print(f"NOT CLEAN: {name}: {rep['violations']}")
        return 1
    if seeded.ok:
        print("seeded broken plan was NOT rejected")
        return 1
    return 0


if __name__ == "__main__":
    import sys
    if "--smoke" in sys.argv:
        sys.exit(_smoke())
    print("usage: python -m spark_rapids_tpu.analysis.plan_verifier "
          "--smoke", file=sys.stderr)
    sys.exit(2)
