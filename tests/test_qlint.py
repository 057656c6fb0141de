"""Regression tests for the repro.analysis qlint subsystem.

Each pass must (a) fire on a seeded violation and (b) stay quiet on the
equivalent clean program; the repo at HEAD must be clean modulo the
checked-in qlint_baseline.json.  The seeded programs here are the
acceptance set: an injected key collision, a redundant quantize round-trip,
a u8 wire buffer widened before its collective, a cost-model count
mismatch, and a host sync in the scheduler loop.
"""
import json
import os
import subprocess
import sys
import textwrap
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.analysis import key_audit, source_lint
from repro.analysis.collective_audit import (diff_gather_counts,
                                             diff_wire_bytes)
from repro.analysis.findings import load_baseline, partition_findings
from repro.analysis.jaxpr_audit import audit_jaxpr
from repro.analysis.key_audit import MASTER_SALT, KeyUse, check_key_uses
from repro.core.quant import QuantConfig, dequantize, quantize
from repro.launch.mesh import make_mesh

ROOT = Path(__file__).resolve().parents[1]
CFG = QuantConfig(bits=8, bucket_size=64, mode="nearest")


def _rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# source lint (QS4xx)
# ---------------------------------------------------------------------------


def _seed_tree(tmp_path):
    files = {
        "serve/scheduler.py": """\
            import jax

            class ContinuousScheduler:
                def __init__(self):
                    self.n = jax.device_get(0)  # exempt: setup, not the loop

                def step(self, tokens):
                    done = jax.device_get(tokens)
                    return float(tokens.item())
            """,
        "core/lib.py": """\
            import jax

            def default_key():
                return jax.random.PRNGKey(0)
            """,
        "train/bad_import.py": """\
            from repro.kernels.quantize import quantize_kernel
            """,
    }
    for rel, body in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(body))
    return tmp_path


def test_lint_fires_on_seeded_tree(tmp_path):
    findings = source_lint.lint_source(_seed_tree(tmp_path))
    assert _rules(findings) == {"QS401", "QS402", "QS403"}
    qs401 = [f for f in findings if f.rule == "QS401"]
    # device_get + .item() inside step(); the __init__ sync is exempt
    assert len(qs401) == 2
    assert all("ContinuousScheduler.step" in f.site for f in qs401)


def test_lint_head_clean_modulo_baseline():
    findings = source_lint.run()
    baseline = load_baseline(str(ROOT / "qlint_baseline.json"))
    new, suppressed, unused = partition_findings(findings, baseline)
    assert new == [], [str(f) for f in new]
    assert unused == [], unused  # every suppression still earns its keep
    assert len(suppressed) == len(baseline)


# ---------------------------------------------------------------------------
# key audit (QK2xx)
# ---------------------------------------------------------------------------


def test_key_audit_fires_on_injected_collision():
    uses = [KeyUse("loss", 7, "layers.0.wq", "scan", False),
            KeyUse("loss", 7, "layers.1.wq", "scan", False)]
    assert _rules(check_key_uses(uses)) == {"QK201"}


def test_key_audit_fires_on_hash_collision():
    uses = [KeyUse("master", 0xDEAD, "wq", "_h(name)", True),
            KeyUse("master", 0xDEAD, "wk", "_h(name)", True)]
    assert _rules(check_key_uses(uses)) == {"QK202"}


def test_key_audit_flags_reserved_salt_overlap():
    uses = [KeyUse("step", MASTER_SALT, "master-requant", "salt", False),
            KeyUse("step", MASTER_SALT, "micro[3824617]", "index", False)]
    assert "QK203" in _rules(check_key_uses(uses))


def test_key_audit_distinct_constants_clean():
    uses = [KeyUse("loss", 7, "layers.0.wq", "scan", False),
            KeyUse("loss", 8, "layers.1.wq", "scan", False)]
    assert check_key_uses(uses) == []


def test_key_audit_head_clean():
    # full param trees: the dense family plus the enc/dec audio family whose
    # shared-short-name collision this subsystem originally caught
    findings = key_audit.run(archs=["gpt-125m", "seamless-m4t-large-v2"])
    assert findings == [], [str(f) for f in findings]


# ---------------------------------------------------------------------------
# jaxpr audit (QJ1xx)
# ---------------------------------------------------------------------------


def test_jaxpr_audit_fires_on_redundant_roundtrip():
    def seeded(x):
        d = dequantize(quantize(x, CFG))
        return quantize(d.reshape(-1), CFG)  # re-quantizing decoded values

    closed = jax.make_jaxpr(seeded)(jnp.ones((256,), jnp.float32))
    findings = audit_jaxpr(closed, "seeded")
    assert "QJ101" in _rules(findings)


def test_jaxpr_audit_clean_when_values_change():
    def clean(x):
        d = dequantize(quantize(x, CFG))
        return quantize(d * 1.5 + 1.0, CFG)  # real compute between the two

    closed = jax.make_jaxpr(clean)(jnp.ones((256,), jnp.float32))
    assert audit_jaxpr(closed, "clean") == []


def test_jaxpr_audit_fires_on_u8_widening_before_collective():
    mesh = make_mesh((1,), ("x",))

    @partial(shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
             check_vma=False)
    def seeded(x):
        q = quantize(x, CFG)
        wide = q.codes.astype(jnp.float32)  # 4x the wire bytes
        return jax.lax.all_gather(wide, "x")

    closed = jax.make_jaxpr(seeded)(jnp.ones((256,), jnp.float32))
    findings = audit_jaxpr(closed, "seeded")
    assert "QJ102" in _rules(findings)


def test_jaxpr_audit_clean_when_gathering_u8():
    mesh = make_mesh((1,), ("x",))

    @partial(shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
             check_vma=False)
    def clean(x):
        q = quantize(x, CFG)
        gathered = jax.lax.all_gather(q.codes, "x")  # u8 on the wire
        return gathered.astype(jnp.float32)

    closed = jax.make_jaxpr(clean)(jnp.ones((256,), jnp.float32))
    assert audit_jaxpr(closed, "clean") == []


# ---------------------------------------------------------------------------
# collective audit (QC3xx)
# ---------------------------------------------------------------------------


def test_collective_audit_fires_on_extra_gather():
    findings = diff_gather_counts({"all-gather": 2}, 1, "t")
    assert _rules(findings) == {"QC301"}


def test_collective_audit_fires_on_unexpected_kind():
    findings = diff_gather_counts({"all-gather": 1, "all-to-all": 1}, 1, "t")
    assert _rules(findings) == {"QC301"}
    assert any("all-to-all" in f.site for f in findings)


def test_collective_audit_matching_counts_clean():
    assert diff_gather_counts({"all-gather": 1, "reduce-scatter": 2}, 1,
                              "t") == []


def test_collective_audit_wire_budget():
    assert _rules(diff_wire_bytes(2_000_000, 1_000_000, "t")) == {"QC302"}
    assert diff_wire_bytes(1_000_000, 1_000_000, "t") == []  # within slack


# ---------------------------------------------------------------------------
# CLI end-to-end
# ---------------------------------------------------------------------------


def _run_cli(args, cwd=None):
    env = {**os.environ,
           "PYTHONPATH": str(ROOT / "src") + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis.qlint", *args],
        cwd=cwd or ROOT, env=env, capture_output=True, text=True,
        timeout=300)


def test_cli_head_exits_zero_with_checked_in_baseline():
    r = _run_cli(["--passes", "lint",
                  "--baseline", str(ROOT / "qlint_baseline.json")])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "new=0" in r.stdout


def test_cli_seeded_tree_exits_nonzero_then_baselines(tmp_path):
    tree = _seed_tree(tmp_path / "tree")
    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps({"version": 1, "suppressions": []}))

    r = _run_cli(["--passes", "lint", "--root", str(tree),
                  "--baseline", str(baseline),
                  "--report", str(tmp_path / "report.json")])
    assert r.returncode == 1, r.stdout + r.stderr
    assert "NEW QS401" in r.stdout
    report = json.loads((tmp_path / "report.json").read_text())
    assert report["ok"] is False
    assert {f["rule"] for f in report["new"]} == {"QS401", "QS402", "QS403"}

    r = _run_cli(["--passes", "lint", "--root", str(tree),
                  "--baseline", str(baseline), "--update-baseline"])
    assert r.returncode == 1  # still new THIS run; baseline now records them
    r = _run_cli(["--passes", "lint", "--root", str(tree),
                  "--baseline", str(baseline)])
    assert r.returncode == 0, r.stdout + r.stderr
