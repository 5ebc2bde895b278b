"""ECLint (tools/lint_ec.py): the tree is lint-clean (zero unwaived
findings, zero stale waivers, every waiver justified), each rule fires
on a synthetic positive, and the CLI's JSON contract is pinned.
"""

import ast
import json
import os
import subprocess
import sys

import pytest

from tools.lint_ec import (
    DEFAULT_WAIVERS,
    IMPORT_RULES,
    REPO_ROOT,
    RULES,
    ImportRule,
    check_ec101,
    check_ec102,
    check_ec103,
    check_ec104,
    check_ec105,
    check_ec106,
    check_ec107,
    parse_waivers,
    registered_options,
    run_lint,
)


# -- the tier-1 gate -------------------------------------------------------

def test_tree_is_lint_clean():
    """Zero unwaived findings over ceph_tpu/, zero stale waivers,
    every waiver justified — the tier-1 lint gate."""
    res = run_lint()
    assert not res.unwaived, [
        f"{f.key}: {f.message}" for f in res.unwaived
    ]
    assert not res.stale_waivers, res.stale_waivers
    assert not res.unjustified_waivers, res.unjustified_waivers
    assert res.ok
    assert res.files_linted > 100  # the whole package, not a subset


def test_removing_any_waiver_reproduces_its_finding():
    """Acceptance: every waiver line in tools/lint_waivers.txt is
    load-bearing — running WITHOUT waivers surfaces a finding for
    exactly each waived key."""
    waivers, _ = parse_waivers(DEFAULT_WAIVERS)
    res = run_lint(waivers_path=None)
    keys = {f.key for f in res.findings}
    for waiver_key in waivers:
        assert waiver_key in keys, (
            f"waiver {waiver_key!r} matches no finding — stale"
        )


# -- rule positives (each rule proves it can fire) -------------------------

def _tree(src: str) -> ast.AST:
    return ast.parse(src)


def test_ec101_fires_on_banned_import():
    hits = check_ec101(
        "pipeline/x.py",
        _tree("import ceph_tpu.checksum.host\n"),
    )
    assert len(hits) == 1 and "banned" in hits[0][1]
    # allowed home: silent
    assert not check_ec101(
        "checksum/x.py", _tree("import ceph_tpu.checksum.host\n")
    )
    # relative form resolves too
    hits = check_ec101(
        "pipeline/x.py",
        _tree("from ..checksum import host\n"),
    )
    assert len(hits) == 1
    # attribute-chain use without an import line
    hits = check_ec101(
        "store/x.py",
        _tree("import ceph_tpu\nx = ceph_tpu.checksum.host.crc32c\n"),
    )
    assert len(hits) == 1


def test_ec101_layering_rule_fires():
    hits = check_ec101(
        "pipeline/x.py", _tree("from ceph_tpu.cluster import Monitor\n")
    )
    assert len(hits) == 1 and "layering" in hits[0][1]
    assert not check_ec101(
        "loadgen/x.py", _tree("from ceph_tpu.cluster import Monitor\n")
    )


def test_ec101_rule_table_is_declarative():
    """The hygiene rules live in ONE place (the table), and the
    checksum.host rule — the original test_import_hygiene rule —
    is still declared there."""
    assert any(
        r.module == "ceph_tpu.checksum.host" and r.allowed
        for r in IMPORT_RULES
    )
    custom = (ImportRule(module="ceph_tpu.gf", banned=("msg/",),
                         reason="test rule"),)
    hits = check_ec101(
        "msg/x.py", _tree("import ceph_tpu.gf.tables\n"), custom
    )
    assert len(hits) == 1 and "test rule" in hits[0][1]


def test_ec102_fires_on_unregistered_option():
    options = registered_options()
    assert "lockdep" in options  # this PR's option is registered
    src = (
        "from ceph_tpu.utils import config\n"
        "a = config.get('no_such_option_xyz')\n"
        "b = config.get('lockdep')\n"
        "with config.override(osd_op_coalescing=False):\n"
        "    pass\n"
        "with config.override(typo_option=1):\n"
        "    pass\n"
    )
    hits = check_ec102("cluster/x.py", _tree(src), options)
    assert len(hits) == 2, hits
    assert "no_such_option_xyz" in hits[0][1]
    assert "typo_option" in hits[1][1]


def test_ec103_fires_on_undeclared_counter():
    counters = ({"declared_one"}, [r"^fam_.+$"])
    src = (
        "pc.inc('declared_one')\n"
        "pc.inc('fam_dynamic')\n"
        "pc.inc('ghost_counter')\n"
        "pc.hinc('ghost_hist', 1.0)\n"
    )
    hits = check_ec103("cluster/x.py", _tree(src), counters)
    assert [h[1].split("'")[1] for h in hits] == [
        "ghost_counter", "ghost_hist"
    ]


def test_ec104_fires_on_bare_lock_in_scope():
    src = (
        "import threading\n"
        "a = threading.Lock()\n"
        "b = threading.RLock()\n"
    )
    assert len(check_ec104("cluster/x.py", _tree(src))) == 2
    assert len(check_ec104("msg/x.py", _tree(src))) == 2
    # out of scope: codecs may keep plain locks
    assert not check_ec104("codecs/x.py", _tree(src))
    # the wrapper module itself is exempt
    assert not check_ec104("utils/lockdep.py", _tree(src))
    # from-import form
    src2 = "from threading import Lock\nc = Lock()\n"
    assert len(check_ec104("store/x.py", _tree(src2))) == 1


def test_ec105_fires_in_deterministic_plane():
    src = (
        "import random, time\n"
        "a = random.random()\n"
        "b = random.Random(42)\n"      # seeded: fine
        "c = random.Random()\n"        # unseeded
        "d = time.time()\n"
        "e = time.monotonic()\n"       # fine
    )
    hits = check_ec105("loadgen/spec.py", _tree(src))
    assert len(hits) == 3, hits
    # outside the deterministic planes: silent
    assert not check_ec105("cluster/x.py", _tree(src))


def test_ec106_fires_on_sleep_under_lock():
    src = (
        "import time\n"
        "def f(self):\n"
        "    with self._lock:\n"
        "        time.sleep(1)\n"
        "        self.sock.sendall(b'x')\n"
        "        def later():\n"
        "            time.sleep(2)\n"  # nested def: runs later
        "    time.sleep(3)\n"          # outside the lock
    )
    hits = check_ec106("msg/x.py", _tree(src))
    assert len(hits) == 2, hits


def test_ec107_fires_on_bare_except():
    src = (
        "def loop():\n"
        "    try:\n"
        "        pass\n"
        "    except:\n"
        "        pass\n"
        "    try:\n"
        "        pass\n"
        "    except Exception:\n"
        "        pass\n"
    )
    assert len(check_ec107("cluster/x.py", _tree(src))) == 1
    assert not check_ec107("codecs/x.py", _tree(src))


# -- waiver machinery ------------------------------------------------------

def test_stale_waiver_fails_the_run(tmp_path):
    wf = tmp_path / "waivers.txt"
    wf.write_text("EC104 ceph_tpu/ghost/file.py:1  # no such finding\n")
    res = run_lint(waivers_path=str(wf))
    assert res.stale_waivers == ["EC104 ceph_tpu/ghost/file.py:1"]
    assert not res.ok


def test_unjustified_waiver_fails_the_run(tmp_path):
    wf = tmp_path / "waivers.txt"
    wf.write_text("EC106 ceph_tpu/msg/messenger.py:521\n")
    res = run_lint(waivers_path=str(wf))
    assert res.unjustified_waivers == [
        "EC106 ceph_tpu/msg/messenger.py:521"
    ]
    assert not res.ok


# -- CLI / JSON contract ---------------------------------------------------

def _run_cli(*args) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools", "lint_ec.py"),
         *args],
        capture_output=True, text=True, cwd=REPO_ROOT,
    )


def test_cli_json_contract():
    """The JSON shape is an interface (soak/CI parse it): version,
    rules, findings[{code,path,line,message,key,waived}], counts,
    ok — pinned here."""
    proc = _run_cli("ceph_tpu/", "--json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["version"] == 1
    assert set(doc["rules"]) == set(RULES)
    assert doc["ok"] is True
    assert set(doc["counts"]) == {
        "total", "unwaived", "waived", "stale_waivers"
    }
    assert doc["counts"]["unwaived"] == 0
    for f in doc["findings"]:
        assert set(f) == {
            "code", "path", "line", "message", "key", "waived"
        }
        assert f["key"] == f"{f['code']} {f['path']}:{f['line']}"


def test_cli_exit_one_on_findings(tmp_path):
    proc = _run_cli("ceph_tpu/", "--waivers", "none")
    # the tree has >= 1 finding that is only green via its waiver
    assert proc.returncode == 1
    assert "EC106" in proc.stdout


def test_cli_list_rules():
    proc = _run_cli("--list-rules")
    assert proc.returncode == 0
    for code in RULES:
        assert code in proc.stdout


# -- the profiler has two ways in and no third (PR 47) -------------------

def test_only_pyprof_and_its_admin_command_name_sys_monitoring():
    """``utils/pyprof.py`` is the one module that touches
    ``sys.monitoring``, and the admin socket's ``pyprof`` commands are
    the one place in the package that reaches it: a run nobody profiles
    holds no tool and sets no event, because nothing else can."""
    allowed = {
        os.path.join("ceph_tpu", "utils", "pyprof.py"),
        os.path.join("ceph_tpu", "utils", "admin_socket.py"),
    }
    named = []
    for folder, _dirs, files in os.walk(os.path.join(REPO_ROOT, "ceph_tpu")):
        for name in files:
            path = os.path.join(folder, name)
            rel = os.path.relpath(path, REPO_ROOT)
            if not name.endswith(".py") or rel in allowed:
                continue
            with open(path) as f:
                tree = ast.parse(f.read(), rel)
            for node in ast.walk(tree):
                if (
                    isinstance(node, ast.Attribute) and node.attr == "monitoring"
                ) or (
                    isinstance(node, (ast.Import, ast.ImportFrom))
                    and any("pyprof" in a.name or a.name == "monitoring"
                            or "pyprof" in (getattr(node, "module", "") or "")
                            for a in node.names)
                ):
                    named.append(f"{rel}:{node.lineno}")
    assert not named, named
    # and the admin socket reaches it only inside the three commands
    with open(os.path.join(REPO_ROOT, "ceph_tpu", "utils", "admin_socket.py")) as f:
        source = f.read()
    assert "sys.monitoring" not in source
    assert source.count("import pyprof") == 1
