"""tpu-lint: the package must be clean (zero unallowlisted,
unbaselined violations), and every rule must fire on a seeded specimen
of its bug class (analysis/lint.py; ISSUE 6 + ISSUE 10 — the dataflow
engine's own specimens live in tests/test_dataflow.py)."""
import json
import subprocess
import sys

import pytest

from spark_rapids_tpu.analysis.lint import (LINT_SCHEMA,
                                            conf_key_report,
                                            default_baseline_path,
                                            finding_fingerprint,
                                            lint_package, lint_paths,
                                            load_baseline, package_dir,
                                            registered_conf_keys)


def _lint_snippet(tmp_path, src, name="cluster.py"):
    """Lint one synthetic module; `name` controls module-scoped rules
    (cluster.py is inside the thread-heavy set)."""
    p = tmp_path / name
    p.write_text(src)
    return lint_paths([str(p)])


def _rules(out, allowlisted=False):
    return sorted({f["rule"] for f in out["findings"]
                   if f["allowlisted"] == allowlisted})


# --- the gate ---------------------------------------------------------------

@pytest.fixture(scope="module")
def package_report():
    """ONE full-package lint shared by the gate tests (a package run
    costs ~10s; the baseline is applied per-test from the raw
    fingerprints, so sharing loses nothing)."""
    return lint_package()


def test_package_is_lint_clean(package_report):
    """Zero violations with the checked-in baseline applied: every
    remaining finding is either inline-allowlisted (with a reason) or
    fingerprinted in tools/tpu_lint_baseline.json."""
    base = load_baseline()
    offenders = []
    for f in package_report["findings"]:
        if f["allowlisted"]:
            continue
        if base.get(f["fingerprint"], 0) > 0:
            base[f["fingerprint"]] -= 1
            continue
        offenders.append(f)
    assert offenders == []
    assert package_report["schema"] == LINT_SCHEMA
    # the allowlist surface stays auditable: every suppression carries
    # a reason
    for f in package_report["findings"]:
        if f["allowlisted"]:
            assert f["allow_reason"], f


def test_checked_in_baseline_is_tight(package_report):
    """The baseline must not hoard headroom: every fingerprint in it
    corresponds to a live finding (a stale entry would let a future
    regression with the same fingerprint slip in unnoticed). An EMPTY
    baseline is the ideal end state and trivially tight."""
    base = load_baseline()
    live = {}
    for f in package_report["findings"]:
        if not f["allowlisted"]:
            live[f["fingerprint"]] = live.get(f["fingerprint"], 0) + 1
    for fp, count in base.items():
        assert live.get(fp, 0) >= count, \
            f"stale baseline entry {fp} (accepted {count}, live " \
            f"{live.get(fp, 0)}) — regenerate with --write-baseline"


def test_conf_registry_is_clean():
    rep = conf_key_report()
    assert len(rep["checked"]) > 70
    assert rep["unused"] == [], rep["unused"]
    assert rep["unregistered_reads"] == [], rep["unregistered_reads"]


def test_validate_configs_delegates_to_ast_rule():
    from spark_rapids_tpu.tools.api_validation import validate_configs
    out = validate_configs()
    assert out["unused"] == []
    assert out["unregistered_reads"] == []
    assert len(out["checked"]) > 70


# --- per-rule specimens -----------------------------------------------------

def test_rule_wallclock_duration(tmp_path):
    out = _lint_snippet(tmp_path, (
        "import time\n"
        "def f():\n"
        "    t0 = time.time()\n"
        "    work()\n"
        "    return time.time() - t0\n"))
    assert _rules(out) == ["wallclock-duration"]
    # a bare wall stamp (no subtraction) is NOT a violation
    out = _lint_snippet(tmp_path, (
        "import time\n"
        "def f():\n"
        "    return {'ts': time.time()}\n"))
    assert out["findings"] == []


def test_rule_unregistered_conf_key(tmp_path):
    out = _lint_snippet(tmp_path, (
        "def f(conf):\n"
        "    return conf.get('spark.rapids.sql.noSuchKnob')\n"))
    assert _rules(out) == ["unregistered-conf-key"]
    # registered keys pass (pulled from the live package registry)
    keys = registered_conf_keys()
    assert "spark.rapids.sql.verifyPlan" in keys
    out = _lint_snippet(tmp_path, (
        "def f(conf):\n"
        "    return conf.get('spark.rapids.sql.verifyPlan')\n"))
    assert out["findings"] == []


def test_rule_blocking_call_scoped_to_thread_modules(tmp_path):
    src = ("import time\n"
           "def worker(fut, th):\n"
           "    time.sleep(5)\n"
           "    fut.result()\n"
           "    th.join()\n"
           "    th.join(10.0)\n"       # bounded: fine
           "    ','.join(['a'])\n")    # string join has args: fine
    out = _lint_snippet(tmp_path, src, name="cluster.py")
    flagged = [f["line"] for f in out["findings"]]
    assert flagged == [3, 4, 5]
    # the same source outside the thread-heavy module set is untouched
    out = _lint_snippet(tmp_path, src, name="other.py")
    assert out["findings"] == []


def test_rule_host_sync_in_jit(tmp_path):
    src = ("import jax\n"
           "import numpy as np\n"
           "def decode(blob):\n"
           "    return np.asarray(blob) + 1\n"
           "fn = jax.jit(decode)\n"
           "def host_helper(x):\n"      # NOT jitted: np.asarray fine
           "    return np.asarray(x)\n")
    out = _lint_snippet(tmp_path, src, name="parquet_device.py")
    assert _rules(out) == ["host-sync-in-jit"]
    assert [f["line"] for f in out["findings"]] == [4]
    # tpu-lint 2.0: taint is package-wide — the old two-module
    # file-list scoping is gone, any module is checked
    out = _lint_snippet(tmp_path, src, name="some_module.py")
    assert _rules(out) == ["host-sync-in-jit"]
    assert [f["line"] for f in out["findings"]] == [4]


def test_rule_unlocked_shared_mutation(tmp_path):
    src = ("import threading\n"
           "class Store:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self.total = 0\n"
           "    def add(self, n):\n"
           "        with self._lock:\n"
           "            self.total += n\n"
           "    def reset(self):\n"
           "        self.total = 0\n")  # outside the lock: violation
    out = _lint_snippet(tmp_path, src, name="whatever.py")
    assert _rules(out) == ["unlocked-shared-mutation"]
    assert [f["line"] for f in out["findings"]] == [10]


def test_rule_unlocked_shared_mutation_acquire_style(tmp_path):
    """The PR 6 false negative (ISSUE 10 satellite): acquire()-style
    critical sections guarded nothing, so an augmented assignment
    outside the lock was invisible. The dataflow port flags it."""
    src = ("import threading\n"
           "class Store:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self.x = 0\n"
           "    def f(self):\n"
           "        self._lock.acquire()\n"
           "        self.x += 1\n"
           "        self._lock.release()\n"
           "    def g(self):\n"
           "        self.x += 1\n")
    out = _lint_snippet(tmp_path, src, name="whatever.py")
    assert _rules(out) == ["unlocked-shared-mutation"]
    assert [f["line"] for f in out["findings"]] == [11]


def test_rule_exit_without_flush(tmp_path):
    out = _lint_snippet(tmp_path, (
        "import os\n"
        "def die():\n"
        "    os._exit(3)\n"), name="anything.py")
    assert _rules(out) == ["exit-without-flush"]
    out = _lint_snippet(tmp_path, (
        "import os\n"
        "def die(ring):\n"
        "    flush_worker_ring(ring)\n"
        "    os._exit(3)\n"), name="anything.py")
    assert out["findings"] == []


# --- allowlist syntax -------------------------------------------------------

def test_allowlist_same_line_and_line_above(tmp_path):
    src = ("import time\n"
           "def f(th, fut):\n"
           "    time.sleep(1)  # tpu-lint: allow[blocking-call-in-thread] poll loop\n"
           "    # tpu-lint: allow[blocking-call-in-thread] must drain\n"
           "    fut.result()\n"
           "    th.join()\n")
    out = _lint_snippet(tmp_path, src, name="pipeline.py")
    allowed = [f for f in out["findings"] if f["allowlisted"]]
    hard = [f for f in out["findings"] if not f["allowlisted"]]
    assert [f["line"] for f in allowed] == [3, 5]
    assert [f["allow_reason"] for f in allowed] == ["poll loop",
                                                    "must drain"]
    assert [f["line"] for f in hard] == [6]
    assert out["violations"] == 1


def test_allowlist_does_not_bleed_to_next_line(tmp_path):
    """A trailing allow on line N blesses line N only — a new violation
    directly below an allowlisted site must still fail the gate."""
    src = ("import time\n"
           "def f():\n"
           "    time.sleep(1)  # tpu-lint: allow[blocking-call-in-thread] poll\n"
           "    time.sleep(2)\n")
    out = _lint_snippet(tmp_path, src, name="cluster.py")
    assert out["violations"] == 1
    hard = [f for f in out["findings"] if not f["allowlisted"]]
    assert [f["line"] for f in hard] == [4]


def test_allowlist_requires_reason_and_matching_rule(tmp_path):
    src = ("import time\n"
           "def f():\n"
           "    time.sleep(1)  # tpu-lint: allow[blocking-call-in-thread]\n"
           "    time.sleep(2)  # tpu-lint: allow[wallclock-duration] wrong rule\n")
    out = _lint_snippet(tmp_path, src, name="cluster.py")
    assert out["violations"] == 2  # empty reason + wrong rule: both fatal


# --- baseline ratchet -------------------------------------------------------

def test_baseline_marks_known_findings_and_fails_new(tmp_path):
    src = ("import time\n"
           "def f():\n"
           "    time.sleep(1)\n")
    p = tmp_path / "cluster.py"
    p.write_text(src)
    out = lint_paths([str(p)])
    assert out["violations"] == 1
    fp = out["findings"][0]["fingerprint"]
    assert fp == finding_fingerprint(
        out["findings"][0]["rule"], out["findings"][0]["path"],
        out["findings"][0]["message"])
    # baselined: the same finding no longer counts
    out = lint_paths([str(p)], baseline={fp: 1})
    assert out["violations"] == 0 and out["baselined"] == 1
    assert out["findings"][0]["baselined"] is True
    # a NEW finding (second sleep) exceeds the accepted count and fails
    p.write_text(src + "    time.sleep(2)\n")
    out = lint_paths([str(p)], baseline={fp: 1})
    assert out["violations"] == 1 and out["baselined"] == 1


def test_baseline_fingerprint_survives_line_drift(tmp_path):
    p = tmp_path / "cluster.py"
    p.write_text("import time\ndef f():\n    time.sleep(1)\n")
    fp1 = lint_paths([str(p)])["findings"][0]["fingerprint"]
    # shift the finding down 40 lines: same fingerprint
    p.write_text("import time\n" + "# pad\n" * 40
                 + "def f():\n    time.sleep(1)\n")
    fp2 = lint_paths([str(p)])["findings"][0]["fingerprint"]
    assert fp1 == fp2


def test_baseline_does_not_cover_allowlisted_or_other_rules(tmp_path):
    p = tmp_path / "cluster.py"
    p.write_text("import time\n"
                 "def f(th):\n"
                 "    th.join()\n")
    out = lint_paths([str(p)])
    fp = out["findings"][0]["fingerprint"]
    # a different rule's fingerprint never matches
    other = finding_fingerprint("wallclock-duration",
                                out["findings"][0]["path"], "x - y")
    out = lint_paths([str(p)], baseline={other: 5})
    assert out["violations"] == 1 and out["baselined"] == 0
    out = lint_paths([str(p)], baseline={fp: 1})
    assert out["violations"] == 0


# --- CLI --------------------------------------------------------------------

def test_cli_json_schema_and_exit_codes(tmp_path):
    import os
    root = os.path.dirname(package_dir())
    cli = os.path.join(root, "tools", "tpu_lint.py")
    r = subprocess.run([sys.executable, cli, "--json", "--baseline",
                        os.path.join(root, "tools",
                                     "tpu_lint_baseline.json")],
                       capture_output=True, text=True, cwd=root)
    assert r.returncode == 0, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert doc["schema"] == LINT_SCHEMA
    assert doc["violations"] == 0
    assert doc["allowlisted"] >= 1
    # every accepted fingerprint is spent exactly once (0 when the
    # baseline reaches the ideal empty state)
    assert doc["baselined"] == sum(load_baseline().values())
    assert set(doc["rules"]) >= {"lock-order-cycle", "ledger-leak-path",
                                 "blocking-under-lock",
                                 "host-sync-in-jit"}
    for f in doc["findings"]:
        assert f["fingerprint"]
    # ... and it is the report the operator's validator accepts
    from asserts import obs_checker
    report = tmp_path / "lint.json"
    report.write_text(r.stdout)
    assert obs_checker().check_lint_report(str(report)) == []
    bad = tmp_path / "cluster.py"
    bad.write_text("import time\n"
                   "def f(th):\n"
                   "    th.join()\n")
    r = subprocess.run([sys.executable, cli, str(bad)],
                       capture_output=True, text=True, cwd=root)
    assert r.returncode == 1
    assert "blocking-call-in-thread" in r.stdout


def test_cli_write_baseline_roundtrip(tmp_path):
    import os
    root = os.path.dirname(package_dir())
    cli = os.path.join(root, "tools", "tpu_lint.py")
    out = tmp_path / "base.json"
    r = subprocess.run([sys.executable, cli, "--write-baseline",
                        str(out)],
                       capture_output=True, text=True, cwd=root)
    assert r.returncode == 0, r.stdout + r.stderr
    doc = json.loads(out.read_text())
    assert doc["schema"] == LINT_SCHEMA
    # the written baseline immediately yields a clean run
    r = subprocess.run([sys.executable, cli, "--baseline", str(out)],
                       capture_output=True, text=True, cwd=root)
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_lock_graph(tmp_path):
    import os
    root = os.path.dirname(package_dir())
    cli = os.path.join(root, "tools", "tpu_lint.py")
    r = subprocess.run([sys.executable, cli, "--lock-graph"],
                       capture_output=True, text=True, cwd=root)
    assert r.returncode == 0, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert doc["cycles"] == []
    assert "DeviceMemoryManager._lock" in doc["locks"]
    assert any(e["from"] == "SpillableBatch._state_lock"
               and e["to"] == "DeviceMemoryManager._lock"
               for e in doc["edges"])


def test_cli_check_docs():
    import os
    root = os.path.dirname(package_dir())
    cli = os.path.join(root, "tools", "tpu_lint.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, cli, "--check-docs"],
                       capture_output=True, text=True, cwd=root, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "in sync" in r.stdout
