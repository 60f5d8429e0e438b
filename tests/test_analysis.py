"""Gates on the static-analysis subsystem itself (`repro.analysis`).

Three layers of proof:

1. Every lint rule fires on a seeded-violation snippet and stays
   silent on the clean twin — via `lint_text`, the in-memory fixture
   entry point, so no bad code ever touches the tree.  The alias
   fixtures pin the exact blind spot the old regex scans had
   (``from os import environ as e``).
2. The engine mechanics: suppression comments (same line, line above,
   file-wide, ``all``), the rule catalog contract (>= 8 rules, unique
   ids, complete metadata), parse-error surfacing, and — the gate CI
   rides on — the repo itself lints clean.
3. The HLO collective auditor: the CLI's full audit pins the EXACT
   inventory (kind, dtype, bytes, count, group span) of every
   registered DP wire at b in {2, 4, 8} on the 4-device ring, and a
   deliberately-broken wire (an f32 psum smuggled past its manifest)
   fails with a diff that names the unexpected op (slow tier —
   subprocess compiles, like every host-mesh regression).
"""
import json
import os
import subprocess
import sys

import pytest

from repro.analysis import (get_rule, iter_rules, lint_text, run_lint,
                            run_rule)
from workerproc import run_worker

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# 1. every rule: one snippet that MUST flag, one that MUST pass
# ---------------------------------------------------------------------------

CASES = [
    ("no-unfused-quantize",
     "from repro.core import quantization as QQ\n"
     "def send(x):\n"
     "    return QQ.quantize(x, bits=2)\n",
     "src/repro/training/newmod.py",
     "from repro.core import boundary as B\n"
     "def send(x, key):\n"
     "    return B.roundtrip(x, bits=2, stochastic=False, key=key)\n",
     "src/repro/training/newmod.py"),
    ("no-stray-env-read",
     "from os import environ as e\n"
     "FLAG = e['REPRO_DEBUG']\n",
     "src/repro/newmod.py",
     "import os\n"
     "HOME = os.environ['HOME']\n",          # non-REPRO_* read is fine
     "src/repro/newmod.py"),
    ("no-legacy-comm-kwargs",
     "cfg = PipelineConfig(dp_wire='ring', dp_grad_bits=4)\n",
     "examples/newmod.py",
     "cfg = PipelineConfig(comm=CommConfig(dp=PlaneConfig(bits=4)))\n",
     "examples/newmod.py"),
    ("registry-completeness",
     "W.register_wire('x', plane='dp-grad', collective=fn)\n",
     "src/repro/comm/newwire.py",
     "W.register_wire('x', plane='dp-grad', collective=fn,\n"
     "                wire_bytes=bb, sim_allreduce=sim,\n"
     "                expected_collectives=manifest)\n",
     "src/repro/comm/newwire.py"),
    ("no-host-callables-in-jit",
     "import time\n"
     "import jax\n"
     "@jax.jit\n"
     "def f(x):\n"
     "    return x + time.time()\n",
     "src/repro/core/newmod.py",
     "import time\n"
     "import jax\n"
     "@jax.jit\n"
     "def f(x):\n"
     "    return x + 1\n"
     "def bench(x):\n"
     "    t0 = time.time()\n"                  # outside jit: supported
     "    return f(x), time.time() - t0\n",
     "src/repro/core/newmod.py"),
    ("no-silent-dtype-upcast",
     "import numpy as np\n"
     "def f(x):\n"
     "    return np.asarray(x, dtype=np.float64)\n",
     "src/repro/core/newmod.py",
     "import numpy as np\n"
     "def f(x):\n"
     "    return np.asarray(x, dtype=np.float32)\n",
     "src/repro/core/newmod.py"),
    ("no-raw-shard-map-import",
     "from jax.experimental.shard_map import shard_map\n",
     "src/repro/training/newmod.py",
     "from repro.launch.mesh import shard_map\n",
     "src/repro/training/newmod.py"),
    ("no-getsource-scan",
     "import inspect\n"
     "src = inspect.getsource(object)\n",
     "tests/test_newmod.py",
     "import inspect\n"
     "sig = inspect.signature(object)\n",
     "tests/test_newmod.py"),
    ("no-direct-collective",
     "import jax\n"
     "def f(x):\n"
     "    return jax.lax.psum(x, 'd')\n",
     "src/repro/models/newmod.py",
     "from repro.core import collectives as C\n"
     "def f(x, err, key):\n"
     "    return C.compressed_ring_allreduce(x, err, 'd', 4, key)\n",
     "src/repro/models/newmod.py"),
]


@pytest.mark.parametrize("rule_id,bad,bad_path,clean,clean_path",
                         CASES, ids=[c[0] for c in CASES])
def test_rule_fires_and_stays_silent(rule_id, bad, bad_path, clean,
                                     clean_path):
    rules = [get_rule(rule_id)]
    hits = lint_text(bad, bad_path, rules)
    assert hits, f"{rule_id} missed its seeded violation"
    assert all(f.rule == rule_id for f in hits)
    assert all(f.fix_hint for f in hits)
    assert lint_text(clean, clean_path, rules) == [], \
        f"{rule_id} false-positive on the clean snippet"


@pytest.mark.parametrize("snippet,rule_id,path", [
    # the exact blind spot of check_docs.py's old regex scan
    ("from os import environ as e\nx = e['REPRO_X']\n",
     "no-stray-env-read", "src/repro/newmod.py"),
    ("from os import getenv as g\nx = g('REPRO_X')\n",
     "no-stray-env-read", "src/repro/newmod.py"),
    ("import os as o\nx = o.environ.get('REPRO_X')\n",
     "no-stray-env-read", "src/repro/newmod.py"),
    # aliased from-import of a banned quantization op
    ("from repro.core.quantization import qdq as q\ny = q(x, 2)\n",
     "no-unfused-quantize", "src/repro/training/newmod.py"),
    # aliased getsource
    ("import inspect as insp\ns = insp.getsource(object)\n",
     "no-getsource-scan", "tests/test_newmod.py"),
], ids=["env-alias", "getenv-alias", "os-alias", "quant-from-import",
        "inspect-alias"])
def test_import_aliases_cannot_dodge(snippet, rule_id, path):
    hits = lint_text(snippet, path, [get_rule(rule_id)])
    assert hits and hits[0].rule == rule_id


# ---------------------------------------------------------------------------
# 2. engine mechanics
# ---------------------------------------------------------------------------

_BAD = ("import inspect\n"
        "src = inspect.getsource(object)\n")
_RULE = "no-getsource-scan"


def _hits(text):
    return lint_text(text, "tests/test_newmod.py", [get_rule(_RULE)])


def test_suppression_same_line():
    text = _BAD.replace(
        "src = inspect.getsource(object)",
        "src = inspect.getsource(object)"
        "  # repro-lint: disable=no-getsource-scan")
    assert _hits(_BAD) and _hits(text) == []


def test_suppression_comment_line_above():
    text = _BAD.replace(
        "src = inspect.getsource(object)\n",
        "# repro-lint: disable=no-getsource-scan\n"
        "src = inspect.getsource(object)\n")
    assert _hits(text) == []


def test_suppression_file_wide_and_all():
    assert _hits("# repro-lint: disable-file=no-getsource-scan\n"
                 + _BAD) == []
    assert _hits(_BAD.replace(
        "src = inspect.getsource(object)",
        "src = inspect.getsource(object)  # repro-lint: disable=all")
    ) == []


def test_suppression_for_other_rule_does_not_apply():
    text = _BAD.replace(
        "src = inspect.getsource(object)",
        "src = inspect.getsource(object)"
        "  # repro-lint: disable=no-stray-env-read")
    assert len(_hits(text)) == 1


def test_rule_catalog_contract():
    """>= 8 rules (the ISSUE floor), unique ids, complete metadata."""
    rules = iter_rules()
    assert len(rules) >= 8
    assert len({r.id for r in rules}) == len(rules)
    for r in rules:
        assert r.summary and r.rationale and r.fix_hint
        assert r.severity in ("error", "warning")


def test_unknown_rule_is_loud():
    with pytest.raises(ValueError, match="unknown lint rule"):
        get_rule("no-such-rule")


def test_parse_error_surfaces_as_finding(tmp_path):
    (tmp_path / "src").mkdir()
    (tmp_path / "src" / "broken.py").write_text("def f(:\n")
    findings = run_lint(tmp_path)
    assert [f.rule for f in findings] == ["parse-error"]
    assert findings[0].path == "src/broken.py"


def test_repo_lints_clean():
    """The gate CI rides on: the tree itself has zero findings (the
    getsource scans this subsystem replaced are gone, the deliberate
    raise-path fixtures carry suppressions)."""
    assert run_lint() == []


def test_run_rule_is_the_one_line_gate():
    """`run_rule` is the entry point the old getsource tests were
    replaced with — scoped to one rule, empty on a clean tree."""
    assert run_rule("no-unfused-quantize") == []


# ---------------------------------------------------------------------------
# 3. CLI and the HLO collective auditor
# ---------------------------------------------------------------------------

def _cli(*args, timeout=120):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=ROOT)


def test_cli_lint_layer_exits_clean():
    r = _cli("--skip-collectives")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 lint finding(s)" in r.stdout


def test_cli_lists_rule_catalog():
    r = _cli("--list-rules")
    assert r.returncode == 0
    lines = [ln for ln in r.stdout.splitlines() if "[error]" in ln
             or "[warning]" in ln]
    assert len(lines) >= 8


@pytest.mark.slow
def test_cli_full_audit_pins_every_wire_inventory(tmp_path):
    """`python -m repro.analysis --json` (the CI invocation) must exit
    0 with every registered DP wire's collective inventory matching
    its manifest at b in {2, 4, 8} — and the b=2 inventories are
    pinned here op-by-op, so neither the manifests nor the lowering
    can drift without this test naming the change."""
    out = tmp_path / "report.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--json", str(out)],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
    assert r.returncode == 0, r.stdout + r.stderr[-3000:]
    data = json.loads(out.read_text())
    assert data["ok"] and not data["lint"]["findings"]
    assert len(data["lint"]["rules"]) >= 8

    audits = {(a["wire"], a["bits"]): a for a in data["collectives"]}
    assert {w for w, _ in audits} == {"ring", "psum", "ring-sharded",
                                      "fp16"}
    assert len(audits) == 12 and all(a["ok"] for a in audits.values())
    for a in audits.values():           # every op spans the full ring
        assert all(c["groups"] == 4 for c in a["inventory"])

    def inv(wire, bits):
        return sorted((c["kind"], c["dtype"], c["bytes"], c["count"])
                      for c in audits[(wire, bits)]["inventory"])

    # (128, 256) bucket, n=4, b=2 — scale pmax + 3 code hops + 3
    # packed-sum hops for the ring; i32-lane psum; ZeRO ring; fp16
    assert inv("ring", 2) == [("all-reduce", "f32", 512, 1),
                              ("collective-permute", "u8", 2048, 3),
                              ("collective-permute", "u8", 4096, 3)]
    assert inv("psum", 2) == [("all-reduce", "f32", 512, 1),
                              ("all-reduce", "s32", 131072, 1)]
    assert inv("ring-sharded", 2) == [
        ("all-reduce", "f32", 512, 1),
        ("collective-permute", "u8", 2048, 3)]
    assert inv("fp16", 2) == [("all-reduce", "f16", 65536, 1)]


@pytest.mark.slow
def test_auditor_fires_on_smuggled_collective():
    """The seeded auditor violation: a wire whose collective smuggles
    an f32 psum its manifest never declared must FAIL with a diff that
    names the unexpected all-reduce (and the PR-4 compressed-path
    callout); a wire with no manifest at all must fail too."""
    stdout = run_worker("analysis_worker.py", "run", timeout=900)
    line = [ln for ln in stdout.splitlines()
            if ln.startswith("ANALYSIS ")][0]
    out = json.loads(line[len("ANALYSIS "):])

    broken = out["broken"]
    assert not broken["ok"]
    assert broken["jaxpr"].get("psum", 0) >= 1      # traced request
    msgs = "\n".join(broken["problems"])
    assert "unexpected collective" in msgs
    assert "all-reduce f32 131072" in msgs          # 128*256*4 B
    assert "PR-4" in msgs                           # compressed-path
    # the legitimate fp16 payload still matches — ONLY the smuggled op
    # is flagged, so the diff points at the bug, not at noise
    assert not any("missing collective" in p for p in broken["problems"])

    naked = out["naked"]
    assert not naked["ok"]
    assert any("no expected_collectives manifest" in p
               for p in naked["problems"])
