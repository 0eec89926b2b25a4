"""ds_tpu_lint — seeded-violation fixtures for every rule, waiver
round-trip, and the clean-repo gate (both planes) under tier-1.

Structure:
- Plane B (AST) rules against inline source fixtures: raw collective
  outside comm/, host sync inside jitted/shard_mapped code, ownerless
  gauge, unknown config key — each with a matching negative case.
- Plane A (HLO) rules against synthetic module texts: orphaned async
  start, non-partitioning/overlapping replica_groups, iota expansion,
  subaxis inconsistency, cross-program issue-order divergence,
  undonated StableHLO args, dispatch-conformance bypass.
- Waiver machinery: reasons are mandatory, fnmatch keys round-trip,
  stale waivers are named.
- The real repo: the AST plane plus the HLO auditors over the ACTUAL
  lowered ZeRO-3 bucketed train step and fused decode step produce
  zero non-waived findings with the checked-in lint_waivers.json
  (ISSUE 11 acceptance), and the CLI exits 0 on the repo / non-zero on
  a seeded violation.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from deepspeed_tpu.analysis import (apply_waivers,  # noqa: E402
                                    default_waivers_path, harvest_config_keys,
                                    lint_fingerprint, lint_source,
                                    load_waivers, run_ast_lint, run_hlo_audit,
                                    unused_waivers, HloArtifact)
from deepspeed_tpu.analysis.findings import Finding  # noqa: E402
from deepspeed_tpu.analysis.pylint_rules import check_config_doc  # noqa: E402
from deepspeed_tpu.telemetry.hlo_cost import (  # noqa: E402
    collect_replica_groups, module_num_partitions)


def _rules(findings):
    return sorted({f.rule for f in findings})


# ------------------------------------------------------------ AST plane

def test_ast_raw_collective_flagged_outside_comm():
    src = "from jax import lax\ndef f(x):\n    return lax.psum(x, 'data')\n"
    f = lint_source(src, "deepspeed_tpu/runtime/foo.py")
    assert _rules(f) == ["AST001"]
    assert f[0].waiver_key == "AST001:deepspeed_tpu/runtime/foo.py:lax.psum"
    # the same call is the implementation layer under comm/ and ops/
    assert lint_source(src, "deepspeed_tpu/comm/foo.py") == []
    assert lint_source(src, "deepspeed_tpu/ops/foo.py") == []


def test_ast_raw_collective_jax_lax_spelling():
    src = "import jax\ndef f(x):\n    return jax.lax.ppermute(" \
          "x, 'pipe', [(0, 1)])\n"
    f = lint_source(src, "benchmarks/foo.py")
    assert _rules(f) == ["AST001"] and "ppermute" in f[0].waiver_key


def test_ast_host_sync_in_jitted_fn():
    src = (
        "import jax, time\nimport numpy as np\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    t = time.time()\n"
        "    y = np.asarray(x)\n"
        "    return float(x) + x.sum().item() + t\n")
    f = lint_source(src, "deepspeed_tpu/runtime/foo.py")
    assert _rules(f) == ["AST002"]
    syms = {x.waiver_key.rsplit(":", 1)[1] for x in f}
    assert syms == {"time.time", "np.asarray", "float", ".item"}


def test_ast_host_sync_only_in_traced_functions():
    # identical calls OUTSIDE any jitted/shard_mapped function: clean
    src = ("import time\nimport numpy as np\n"
           "def host(x):\n"
           "    return float(x) + np.asarray(x).item() + time.time()\n")
    assert lint_source(src, "deepspeed_tpu/runtime/foo.py") == []


def test_ast_host_sync_in_shard_mapped_and_wrapped_fn():
    src = (
        "import jax\nfrom jax import shard_map\n"
        "def body(x):\n"
        "    return x.sum().item()\n"
        "out = shard_map(body, mesh=None, in_specs=None, out_specs=None)\n"
        "also = jax.jit(lambda x: x.sum().item())\n")
    f = lint_source(src, "deepspeed_tpu/runtime/foo.py")
    assert len(f) == 2 and _rules(f) == ["AST002"]


def test_ast_ownerless_gauge():
    src = ("def publish(tracer, v):\n"
           "    tracer.set_counter('x/y', v)\n"
           "    tracer.set_counter('x/z', v, owner=object())\n")
    f = lint_source(src, "deepspeed_tpu/telemetry/foo.py")
    assert len(f) == 1 and f[0].rule == "AST003"
    assert f[0].waiver_key.endswith(":x/y")


def test_ast_unknown_config_key():
    known = harvest_config_keys(REPO)
    assert {"zero_optimization", "overlap_schedule", "comm_compression",
            "slo", "num_slots"} <= known
    src = ("import deepspeed_tpu\n"
           "cfg = {'zero_optimisation': {'stage': 3},\n"
           "       'train_micro_batch_size_per_gpu': 2}\n"
           "eng = deepspeed_tpu.initialize(model=None, config=cfg)\n")
    f = lint_source(src, "benchmarks/foo.py", known_config_keys=known)
    assert len(f) == 1 and f[0].rule == "AST004"
    assert "zero_optimisation" in f[0].message


def test_ast_unknown_config_key_json_doc():
    known = harvest_config_keys(REPO)
    findings = []
    check_config_doc({"telemetry": {}, "zerro": {}}, known,
                     "examples/configs/x.json", findings)
    assert len(findings) == 1 and findings[0].waiver_key.endswith(":zerro")


def test_ast_clean_repo_with_checked_in_waivers():
    """The whole scan set is lint-clean against lint_waivers.json —
    new AST violations fail CI here."""
    findings = run_ast_lint(REPO)
    waivers = load_waivers(default_waivers_path(REPO))
    apply_waivers(findings, waivers)
    bad = [f for f in findings if not f.waived]
    assert not bad, "non-waived AST findings:\n" + "\n".join(
        f"  {f.waiver_key}: {f.message}" for f in bad)


# ---------------------------------------------------- replica-group parse

def test_collect_replica_groups_explicit_and_iota():
    hlo = (
        "HloModule m, num_partitions=8\n"
        "ENTRY %main (p: f32[8]) -> f32[8] {\n"
        "  %ar = f32[8] all-reduce(f32[8] %p), "
        "replica_groups={{0,1,2,3},{4,5,6,7}}\n"
        "  %ag = f32[8] all-gather(f32[8] %p), "
        "replica_groups=[2,4]<=[8]\n"
        "  %rs = f32[8] reduce-scatter(f32[8] %p), "
        "replica_groups=[2,4]<=[4,2]T(1,0)\n"
        "  ROOT %a2 = f32[8] all-reduce(f32[8] %p), replica_groups={}\n"
        "}\n")
    assert module_num_partitions(hlo) == 8
    recs = collect_replica_groups(hlo)
    assert [r["op"] for r in recs] == ["all-reduce", "all-gather",
                                      "reduce-scatter", "all-reduce"]
    assert recs[0]["groups"] == [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert recs[1]["groups"] == [[0, 1, 2, 3], [4, 5, 6, 7]]
    # transposed iota: [2,4]<=[4,2]T(1,0) interleaves hosts
    assert recs[2]["groups"] == [[0, 2, 4, 6], [1, 3, 5, 7]]
    assert recs[3]["groups"] is None and recs[3]["form"] == "all"


# ------------------------------------------------------------ HLO plane

def _art(hlo, name="fixture", **kw):
    return HloArtifact(name=name, hlo_texts=[hlo], **kw)


def test_hlo_orphaned_async_start():
    hlo = ("HloModule m, num_partitions=8\n"
           "ENTRY %main (p: f32[8]) -> f32[8] {\n"
           "  %s = f32[8] all-gather-start(f32[8] %p), "
           "replica_groups={{0,1,2,3,4,5,6,7}}\n"
           "  ROOT %r = f32[8] add(f32[8] %p, f32[8] %p)\n"
           "}\n")
    f = run_hlo_audit([_art(hlo)])
    assert _rules(f) == ["HLO001"]
    assert f[0].waiver_key == "HLO001:fixture:all-gather"


def test_hlo_replica_groups_must_partition():
    base = ("HloModule m, num_partitions=8\n"
            "ENTRY %main (p: f32[8]) -> f32[8] {{\n"
            "  ROOT %ar = f32[8] all-reduce(f32[8] %p), "
            "replica_groups={groups}\n"
            "}}\n")
    # overlapping membership
    f = run_hlo_audit([_art(base.format(groups="{{0,1},{1,2}}"))])
    assert any(x.rule == "HLO002" and "more than one group" in x.message
               for x in f)
    # unequal group sizes
    f = run_hlo_audit([_art(base.format(groups="{{0,1,2},{3}}"))])
    assert any(x.rule == "HLO002" and "unequal" in x.message for x in f)
    # gap: device 7 in no group
    f = run_hlo_audit([_art(base.format(
        groups="{{0,1},{2,3},{4,5}}"))])
    assert any(x.rule == "HLO002" and "participate in no group" in x.message
               for x in f)
    # a real partition is clean
    assert run_hlo_audit([_art(base.format(
        groups="{{0,2},{1,3},{4,6},{5,7}}"))]) == []


def test_hlo_subaxis_consistency():
    hlo = ("HloModule m, num_partitions=4\n"
           "ENTRY %main (p: f32[4]) -> f32[4] {\n"
           "  %a = f32[4] all-reduce(f32[4] %p), "
           "replica_groups={{0,1},{2,3}}\n"
           "  ROOT %b = f32[4] all-reduce(f32[4] %a), "
           "replica_groups={{0,2},{1,3}}\n"
           "}\n")
    f = run_hlo_audit([_art(hlo)], rules=["HLO003"])
    assert _rules(f) == ["HLO003"] and "2x2" in f[0].waiver_key


def test_hlo_issue_order_divergence():
    def prog(first, second):
        return ("HloModule m, num_partitions=4\n"
                "ENTRY %main (p: f32[4]) -> f32[4] {\n"
                f"  %a = f32[4] {first}(f32[4] %p), "
                "replica_groups={{0,1,2,3}}\n"
                f"  ROOT %b = f32[4] {second}(f32[4] %a), "
                "replica_groups={{0,1,2,3}}\n"
                "}\n")
    same = HloArtifact(name="x", hlo_texts=[
        prog("all-gather", "all-reduce"), prog("all-gather", "all-reduce")])
    assert run_hlo_audit([same], rules=["HLO004"]) == []
    flipped = HloArtifact(name="x", hlo_texts=[
        prog("all-gather", "all-reduce"), prog("all-reduce", "all-gather")])
    f = run_hlo_audit([flipped], rules=["HLO004"])
    assert _rules(f) == ["HLO004"] and "deadlock" in f[0].message


def test_hlo_undonated_buffer_names_role():
    stablehlo = (
        'module @jit_step {\n'
        '  func.func public @main('
        '%arg0: tensor<1024x1024xf32> {mhlo.sharding = '
        '"{devices=[8,1]<=[8]}", tf.aliasing_output = 0 : i32}, '
        '%arg1: tensor<1024x1024xf32> {mhlo.sharding = '
        '"{devices=[8,1]<=[8]}"}, '
        '%arg2: tensor<8x16xi32>) -> (tensor<1024x1024xf32>) {\n'
        '  }\n}\n')
    art = HloArtifact(
        name="fixture", stablehlo=stablehlo,
        arg_roles=[("params", 1), ("optimizer_state", 1), ("batch", 1)],
        donatable_roles={"params", "optimizer_state"},
        donation_min_bytes=1 << 20)
    f = run_hlo_audit([art], rules=["HLO005"])
    # arg0 donated, arg2 is small batch -> exactly the optimizer leaf
    assert len(f) == 1
    assert f[0].waiver_key == "HLO005:fixture:optimizer_state:1"
    assert "optimizer_state" in f[0].message and "4.0 MiB" in f[0].message


def test_hlo_dispatch_conformance_names_bypass():
    hlo = ("HloModule m, num_partitions=8\n"
           "ENTRY %main (p: f32[8,8]) -> f32[8,8] {\n"
           "  ROOT %x = f32[8,8] all-to-all(f32[8,8] %p), "
           "replica_groups={{0,1,2,3,4,5,6,7}}\n"
           "}\n")
    # traced reduce_scatter legitimizes a2a (hierarchical RS legs)...
    ok = _art(hlo, traced_per_op={"reduce_scatter": 2})
    assert run_hlo_audit([ok], rules=["HLO006"]) == []
    # ...but an artifact whose dispatch traced nothing is a bypass
    bad = _art(hlo, traced_per_op={})
    f = run_hlo_audit([bad], rules=["HLO006"])
    assert _rules(f) == ["HLO006"]
    assert f[0].waiver_key == "HLO006:fixture:all-to-all"


# ------------------------------------------------------------- waivers

def test_waiver_round_trip_and_stale_detection(tmp_path):
    wpath = tmp_path / "waivers.json"
    wpath.write_text(json.dumps({"version": 1, "waivers": [
        {"key": "AST001:pkg/a.py:*", "reason": "measured raw on purpose"},
        {"key": "HLO006:never:*", "reason": "stale entry"},
    ]}))
    waivers = load_waivers(str(wpath))
    findings = [
        Finding(rule="AST001", severity="error", path="pkg/a.py", line=3,
                message="m", waiver_key="AST001:pkg/a.py:lax.psum"),
        Finding(rule="AST003", severity="error", path="pkg/b.py", line=9,
                message="m", waiver_key="AST003:pkg/b.py:t"),
    ]
    apply_waivers(findings, waivers)
    assert findings[0].waived and \
        findings[0].waiver_reason == "measured raw on purpose"
    assert not findings[1].waived
    assert unused_waivers(waivers) == ["HLO006:never:*"]


def test_waiver_without_reason_rejected(tmp_path):
    wpath = tmp_path / "waivers.json"
    wpath.write_text(json.dumps({"waivers": [{"key": "AST001:*"}]}))
    with pytest.raises(ValueError, match="no reason"):
        load_waivers(str(wpath))


def test_lint_fingerprint_counts_rules_and_waivers():
    fp = lint_fingerprint(REPO)
    n = len(load_waivers(default_waivers_path(REPO)))
    assert fp == f"ds_tpu_lint v1: 10 rules, {n} waivers"


def test_statusz_carries_lint_fingerprint():
    from deepspeed_tpu.telemetry.statusz import StatuszServer
    doc = StatuszServer().status()
    assert doc["process"]["lint"].startswith("ds_tpu_lint v")


# ---------------------------------------------------------------- CLI

def _run_cli(*args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "ds_tpu_lint"), *args],
        capture_output=True, text=True, cwd=REPO, timeout=240)


def test_cli_repo_clean_exit_zero():
    """ISSUE 11 acceptance: ds_tpu_lint exits 0 on the repo with the
    checked-in waiver file (AST plane; the HLO plane's clean run is
    test_hlo_audit_real_artifacts_clean below)."""
    res = _run_cli("--json")
    assert res.returncode == 0, res.stdout + res.stderr
    doc = json.loads(res.stdout)
    assert doc["non_waived"] == 0
    assert doc["fingerprint"].startswith("ds_tpu_lint v1")


def test_cli_seeded_violation_exits_nonzero(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("from jax import lax\n"
                   "def f(x):\n    return lax.all_to_all(x, 'expert')\n")
    res = _run_cli("--waivers", "none", str(bad))
    assert res.returncode == 1
    assert "AST001" in res.stdout


def test_cli_hlo_file_audit(tmp_path):
    hlo = tmp_path / "bad.hlo"
    hlo.write_text("HloModule m, num_partitions=4\n"
                   "ENTRY %main (p: f32[4]) -> f32[4] {\n"
                   "  ROOT %ar = f32[4] all-reduce(f32[4] %p), "
                   "replica_groups={{0,1},{1,2}}\n"
                   "}\n")
    res = _run_cli("--waivers", "none", "--hlo-file", str(hlo))
    assert res.returncode == 1 and "HLO002" in res.stdout


def test_cli_list_rules():
    res = _run_cli("--list-rules")
    assert res.returncode == 0
    for rid in ("AST001", "AST004", "HLO001", "HLO006"):
        assert rid in res.stdout


# --------------------------------------------- real artifacts (Plane A)

@pytest.fixture(scope="module")
def real_artifacts():
    from deepspeed_tpu.analysis.artifacts import (lower_decode_step,
                                                  lower_spec_draft_step,
                                                  lower_spec_verify_step,
                                                  lower_train_step)
    return [lower_train_step("tiny"), lower_decode_step(),
            lower_spec_verify_step(), lower_spec_draft_step()]


def test_hlo_audit_real_artifacts_clean(real_artifacts):
    """ISSUE 11/12 acceptance: the REAL bucketed+compressed ZeRO-3
    train step, the fused decode step, and the speculative verify +
    draft-propose steps audit clean — async pairs matched,
    replica_groups partition the 8-way mesh, params/optimizer state
    donated, target AND draft KV pools donated, every HLO collective
    kind reconciled with the comm dispatch trace — with zero waivers
    needed."""
    findings = run_hlo_audit(real_artifacts)
    assert findings == [], "\n".join(
        f"{f.waiver_key}: {f.message}" for f in findings)


def test_train_artifact_shape(real_artifacts):
    train = real_artifacts[0]
    # the explicit exchange really ran through the dispatch at trace time
    assert train.traced_per_op.get("all_gather", 0) > 1
    assert train.traced_per_op.get("reduce_scatter", 0) > 1
    assert train.comm_delta["bytes"] > 0
    # and the compiled module really contains grouped collectives over
    # the full 8-device mesh (the thing HLO002 verified above)
    recs = collect_replica_groups(train.hlo_texts[0])
    assert recs and module_num_partitions(train.hlo_texts[0]) == 8


def _assert_pool_donated(art):
    """Every kv_slots argument of ``art`` is marked donated in the
    StableHLO signature AND aliased to an output by the compiled module
    (XLA drops an alias silently where shardings or layouts differ).
    Returns the number of pool leaves."""
    from deepspeed_tpu.analysis import collect_donation
    from deepspeed_tpu.analysis.hlo_audit_rules import donated_params_from_hlo
    args = collect_donation(art.stablehlo)
    kv, off = [], 0
    for role, count in art.arg_roles:
        if role == "kv_slots":
            kv += args[off:off + count]
        off += count
    assert kv and all(a["donated"] for a in kv), art.name
    assert {a["index"] for a in kv} <= \
        donated_params_from_hlo(art.hlo_texts[0]), art.name
    return len(kv)


@pytest.mark.parametrize("index,name", [(1, "decode_with_slots"),
                                        (2, "spec_verify"),
                                        (3, "spec_draft")])
def test_serving_artifact_pool_donated(real_artifacts, index, name):
    """The donation fixes, pinned: every KV-lane argument of the fused
    decode step (the auditor found it undonated — a pool-sized HBM
    double per tick) and of the speculative verify (TARGET pool) and
    draft-propose (DRAFT pool) steps is donated and stays aliased."""
    art = real_artifacts[index]
    assert art.name == name
    _assert_pool_donated(art)


@pytest.mark.parametrize("quantize", (False, True), ids=("fp", "q8"))
@pytest.mark.parametrize("program", ["slot_prefill", "slot_suffix_prefill",
                                     "slot_copy_lane", "slot_insert_lane"])
def test_lane_writer_artifact_clean_and_aliased(program, quantize):
    """ISSUE 28: every program that takes the pool and returns it audits
    clean under HLO005, fp and int8 pool alike, and XLA kept one alias
    per pool leaf — the lane write is in place, not a second pool."""
    from deepspeed_tpu.analysis.artifacts import lower_prefill_step
    art = lower_prefill_step(program, quantize=quantize)
    findings = run_hlo_audit([art])
    assert findings == [], "\n".join(
        f"{f.waiver_key}: {f.message}" for f in findings)
    assert _assert_pool_donated(art) == (4 if quantize else 2)


def test_hlo005_names_an_undonated_prefill_pool(monkeypatch):
    """The rule bites: the same prefill program jitted without
    ``donate_argnums`` (what ISSUE 28 found) is an HLO005 error on each
    pool leaf."""
    import jax
    from deepspeed_tpu.analysis.artifacts import lower_prefill_step
    jit = jax.jit
    monkeypatch.setattr(
        jax, "jit", lambda fun, **kw: jit(
            fun, **{k: v for k, v in kw.items() if k != "donate_argnums"}))
    findings = run_hlo_audit([lower_prefill_step()])
    assert [f.rule for f in findings] == ["HLO005", "HLO005"]
    assert all("kv_slots" in f.message for f in findings)


