"""Regression: importing ceph_tpu must not initialize a jax backend.

Round-1 failure: a module-import-time jax array (checksum/u64.py)
plus eager admin-socket builtin registration initialized the default
(TPU) backend before the
driver's dryrun could force a virtual CPU mesh. These subprocess
checks pin the fix.
"""

import subprocess
import sys

_CHECK = """
import ceph_tpu
import ceph_tpu.checksum, ceph_tpu.codecs, ceph_tpu.cluster, ceph_tpu.msg
import ceph_tpu.loadgen
import ceph_tpu.parallel, ceph_tpu.pipeline, ceph_tpu.store, ceph_tpu.utils
import jax._src.xla_bridge as xb
assert not xb._backends, f"backend initialized at import: {list(xb._backends)}"
"""


def _run(code: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True
    )


def test_import_initializes_no_backend():
    proc = _run(_CHECK)
    assert proc.returncode == 0, proc.stderr


def test_no_host_crc_imports_outside_checksum():
    """The host crc32c fallback lives BEHIND the Checksummer facade
    (checksum.crc32c_scalar / crc32c_stream record which backend ran):
    pipeline/store/msg code importing ``checksum.host`` directly would
    let the ~0.5 GB/s host path silently creep back into hot paths the
    fused encode+csum kernel just cleared.

    Round 16: the ad-hoc source grep this test used to carry migrated
    into ECLint's declarative EC101 rule table (tools/lint_ec.py
    IMPORT_RULES) so the hygiene rules live in ONE place — this test
    now drives that rule over the tree and pins that the
    ``checksum.host`` entry is still declared."""
    from tools.lint_ec import IMPORT_RULES, run_lint

    rule = next(
        (r for r in IMPORT_RULES
         if r.module == "ceph_tpu.checksum.host"), None
    )
    assert rule is not None, (
        "the checksum.host hygiene rule left the EC101 table"
    )
    assert rule.allowed == ("checksum/",)
    res = run_lint(rules={"EC101"}, waivers_path=None)
    offenders = [f"{f.key}: {f.message}" for f in res.findings]
    assert not offenders, (
        f"EC101 import-hygiene findings: {offenders}; route host CRC "
        "through ceph_tpu.checksum.crc32c_scalar/crc32c_stream"
    )


def test_ec101_rule_actually_fires():
    """Guard against the rule table rotting: a synthetic offender in
    pipeline/ must trip the checksum.host rule."""
    import ast

    from tools.lint_ec import check_ec101

    hits = check_ec101(
        "pipeline/synthetic.py",
        ast.parse("from ceph_tpu.checksum import host\n"),
    )
    assert len(hits) == 1 and "Checksummer facade" in hits[0][1]


def test_admin_socket_first_use_still_works():
    # Lazy builtin registration must still expose the command table.
    proc = _run(
        _CHECK
        + """
from ceph_tpu.utils import admin_socket
assert "perf dump" in admin_socket.help()
admin_socket.execute("config get", name="ec_use_pallas")
"""
    )
    assert proc.returncode == 0, proc.stderr
