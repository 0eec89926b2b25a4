"""Perf plane (PR 19): step/tick anatomy, roofline attribution, and the
ds_tpu_perfdiff regression gate.

Contracts under test: every bucket decomposition sums to its program
total EXACTLY (by construction, not within epsilon); the checked-in
anatomy baseline's embedded invariants hold (including the KV-scaling
evidence ROADMAP item 2 banks on); an identical tree diffs clean while
the rigged regression — the ZeRO-3 train step compiled WITHOUT the
overlap schedule — fails the gate BY COLLECTIVE BUCKET NAME; the plane
is off by default and allocates nothing (train and serving both, and
arming it without the compile plane is a config error); a recompile
that shifts a bucket beyond the band edge-triggers ``perf_regression``
while the first sight of a label never fires; gauges ride the owner
lifecycle; /statusz and ds_tpu_top render the anatomy section and
degrade on snapshots that predate it; and the CLI refuses to baseline
itself, pins with --update-baseline, and rejects non-anatomy docs.
"""

import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.runtime.config import ConfigError
from deepspeed_tpu.telemetry import get_tracer, prometheus_dump
from deepspeed_tpu.telemetry import perfplane as pp

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BASELINE = os.path.join(REPO, "benchmarks", "anatomy_baseline.json")
PERFDIFF = os.path.join(REPO, "bin", "ds_tpu_perfdiff")

#: a minimal module exercising the taxonomy: attention dot + MLP add
#: (classified from the named-scope op_name metadata XLA preserves) and
#: one collective
SYNTH_HLO = """HloModule synth

ENTRY %main (p0: f32[128,128]) -> f32[128,128] {
  %p0 = f32[128,128] parameter(0)
  %dot.1 = f32[128,128] dot(f32[128,128] %p0, f32[128,128] %p0), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/attn/qk" source_file="m.py"}
  %add.1 = f32[128,128] add(f32[128,128] %dot.1, f32[128,128] %p0), metadata={op_name="jit(step)/mlp/up"}
  ROOT %ar = f32[128,128] all-reduce(f32[128,128] %add.1), replica_groups={}
}
"""


def _baseline():
    with open(BASELINE) as f:
        return json.load(f)


def _run_perfdiff(*argv):
    return subprocess.run([sys.executable, PERFDIFF, *argv],
                          capture_output=True, text=True, timeout=60)


# ------------------------------------------------------- static anatomy

def test_anatomy_buckets_sum_to_total_exactly():
    """The by-construction contract: total_ms IS the bucket sum —
    re-summing in the same order gives bit-identical equality, not
    approx."""
    anat = pp.anatomy_from_hlo(SYNTH_HLO)
    resum = float(sum(anat["buckets"][n]["ms"]
                      for n in sorted(anat["buckets"])))
    assert resum == anat["total_ms"]
    assert anat["buckets"]["attn"]["ms"] > 0
    assert anat["buckets"]["mlp"]["ms"] > 0
    assert anat["buckets"]["coll_all_reduce"]["ms"] > 0
    assert "host_gap" in anat["buckets"]          # always present (0 here)
    # the dot: 2 * 128^2 result * 128 contraction = 4.19 MFLOP
    assert anat["buckets"]["attn"]["flops"] == 2 * 128 * 128 * 128
    assert 0.0 <= anat["memory_bound_fraction"] <= 1.0


def test_checked_in_baseline_sums_and_invariants():
    """The pinned benchmarks/anatomy_baseline.json re-sums exactly for
    EVERY program and carries both embedded invariants green — the
    KV-scaling evidence included (dense-pool decode reads double when
    max_len doubles: the number the paged pool must beat)."""
    doc = _baseline()
    assert doc["kind"] == pp.ANATOMY_KIND
    for name, prog in doc["programs"].items():
        resum = float(sum(prog["buckets"][b]["ms"]
                          for b in sorted(prog["buckets"])))
        assert resum == prog["total_ms"], name
    inv = pp.check_anatomy_invariants(doc)
    assert inv["sum_to_total"]["ok"]
    assert inv["kv_read_scales_with_max_len"]["ok"]
    assert 1.8 <= inv["kv_read_scales_with_max_len"]["ratio"] <= 2.2
    # the gate programs the issue names are all pinned
    for prog in ("train_step_zero3", "decode_tick", "decode_tick_x2",
                 "spec_verify_tick", "chunked_prefill_tick", "moe_step"):
        assert prog in doc["programs"], prog
    # satellite (a): decode bytes attribution rides in extras, int8-aware
    extras = doc["programs"]["decode_tick"]["extras"]
    assert extras["kv_read_bytes_per_tick"] > 0
    assert extras["weight_stream_bytes_per_tick"] > 0
    # satellite (b): the MoE expert all-to-all has a first-class bucket
    # next to the PR-18 logical wire bytes (HLO006 tracking note)
    moe = doc["programs"]["moe_step"]
    assert moe["buckets"]["coll_all_to_all"]["ms"] > 0
    assert moe["extras"]["record_wire_bytes_per_step"] > 0


def test_roofline_reconciliation():
    anat = pp.anatomy_from_hlo(SYNTH_HLO)
    rows = pp.reconcile_anatomy(anat)
    by_bucket = {r["bucket"]: r for r in rows}
    ridge = anat["device_model"]["peak_flops"] / \
        anat["device_model"]["hbm_bandwidth"]
    for r in rows:
        assert r["memory_bound"] == (r["arithmetic_intensity"] < ridge)
        assert r["predicted_ms"] >= 0.0
    # attn: 4.19 MFLOP over 3*64KiB — intensity ~21 flops/byte, below
    # the 125 flops/byte ridge of the default model
    assert by_bucket["attn"]["arithmetic_intensity"] == pytest.approx(
        (2 * 128 ** 3) / (3 * 128 * 128 * 4), rel=1e-3)
    # with a measured anatomy, skew rows appear (skew = predicted /
    # measured: a device twice as slow as the model reads 0.5)
    measured = {"buckets_ms": {"attn": by_bucket["attn"]["predicted_ms"] *
                               2.0}}
    rows = pp.reconcile_anatomy(anat, measured)
    attn = next(r for r in rows if r["bucket"] == "attn")
    assert attn["measured_ms"] > 0
    assert attn["skew"] == pytest.approx(0.5, rel=1e-2)


# ------------------------------------------------------------- the gate

def test_diff_identical_tree_passes():
    doc = _baseline()
    rows, ok = pp.diff_anatomy(doc, doc)
    assert ok and rows
    assert all(r["ok"] for r in rows)
    table = pp.format_diff(rows)
    assert "FAIL" not in table and "metric" in table


def test_diff_names_the_regressed_bucket():
    """A de-overlapped collective fails by ITS name; every other
    program's rows stay green."""
    base = _baseline()
    cand = copy.deepcopy(base)
    prog = cand["programs"]["train_step_zero3"]
    prog["buckets"]["coll_all_gather"]["ms"] *= 3.0
    # keep the sum-to-total invariant intact: the regression under test
    # is the bucket band, not a corrupted doc
    prog["total_ms"] = float(sum(prog["buckets"][b]["ms"]
                                 for b in sorted(prog["buckets"])))
    rows, ok = pp.diff_anatomy(base, cand)
    assert not ok
    bad = [r["metric"] for r in rows if not r["ok"]]
    assert "train_step_zero3.coll_all_gather.ms" in bad
    for metric in bad:
        assert metric.startswith("train_step_zero3"), (
            f"unrelated program flagged: {metric}")
    assert all(r["ok"] for r in rows if r["metric"].startswith("decode") or
               r["metric"].startswith("moe_step"))
    assert "FAIL" in pp.format_diff(rows)


def test_diff_hard_gates():
    base = _baseline()
    # a doc whose buckets do not re-sum cannot pass, whatever the bands
    cand = copy.deepcopy(base)
    cand["programs"]["decode_tick"]["total_ms"] += 1.0
    rows, ok = pp.diff_anatomy(base, cand)
    assert not ok
    assert any(r["metric"] == "invariant:sum_to_total" and not r["ok"]
               for r in rows)
    # a baseline program missing from the candidate is a hard fail
    cand = copy.deepcopy(base)
    del cand["programs"]["moe_step"]
    rows, ok = pp.diff_anatomy(base, cand)
    assert not ok
    assert any(r["metric"] == "moe_step" and not r["ok"] for r in rows)
    # a non-anatomy doc is rejected before any comparison
    rows, ok = pp.diff_anatomy(base, {"kind": "dstpu_soak_scorecard"})
    assert not ok and rows[0]["metric"] == "kind"


def test_rigged_overlap_off_regression_caught_by_bucket(tmp_path):
    """THE acceptance scenario, end-to-end through the real compiler:
    the SAME tiny ZeRO-3 train step lowered with the overlap schedule
    disabled must fail the gate — named by collective bucket — against
    the overlap-on baseline, because de-overlapping inflates the
    exposed ``coll_*`` ms even under the static model."""
    from deepspeed_tpu.analysis.artifacts import lower_train_step

    def doc_for(overlap):
        art = lower_train_step("tiny", overlap=overlap)
        anat = pp.anatomy_from_hlo(art.hlo_texts[0])
        prog = {"buckets": {n: {"ms": b["ms"], "flops": b["flops"],
                                "bytes": b["bytes"], "ops": b["ops"]}
                            for n, b in anat["buckets"].items()},
                "total_ms": anat["total_ms"], "flops": anat["flops"],
                "bytes": anat["bytes"],
                "static_overlap_fraction": anat["static_overlap_fraction"],
                "memory_bound_fraction": anat["memory_bound_fraction"]}
        doc = {"kind": pp.ANATOMY_KIND, "size": "tiny",
               "device_model": dict(pp.DEVICE_MODEL),
               "programs": {"train_step_zero3": prog}}
        doc["invariants"] = pp.check_anatomy_invariants(doc)
        return doc, anat

    base, anat_on = doc_for(overlap=True)
    rig, anat_off = doc_for(overlap=False)
    # the schedule is the only knob turned: without bucketing, the ZeRO
    # exchange collapses into a handful of full-tensor collectives whose
    # exposed wire time dwarfs the bucketed form's
    coll_ms = lambda a: sum(b["ms"] for n, b in a["buckets"].items()  # noqa: E731
                            if n.startswith("coll_"))
    assert coll_ms(anat_off) > 1.5 * coll_ms(anat_on)
    rows, ok = pp.diff_anatomy(base, rig)
    assert not ok
    bad = [r["metric"] for r in rows if not r["ok"]]
    assert any(".coll_" in m for m in bad), bad
    # and the identity diff of the rigged doc is still clean (the gate
    # flags the delta, not the schedule itself)
    _rows, ok = pp.diff_anatomy(rig, rig)
    assert ok
    # same verdicts through the CLI on the written files
    bpath, cpath = tmp_path / "base.json", tmp_path / "rig.json"
    pp.write_anatomy(base, str(bpath))
    pp.write_anatomy(rig, str(cpath))
    out = _run_perfdiff(str(bpath), str(cpath))
    assert out.returncode == 1
    assert "perfdiff: FAIL" in out.stdout
    assert ".coll_" in out.stdout
    out = _run_perfdiff(str(bpath), str(bpath))
    assert out.returncode == 0
    assert "perfdiff: PASS" in out.stdout


def test_perfdiff_cli_smoke(tmp_path):
    doc = _baseline()
    cand = tmp_path / "anatomy.json"
    pp.write_anatomy(doc, str(cand))
    # refuse-to-self-baseline: a gate run with no pinned baseline fails
    # loudly instead of silently minting one
    missing = tmp_path / "no_baseline.json"
    out = _run_perfdiff(str(missing), str(cand))
    assert out.returncode == 1
    assert "cannot baseline itself" in out.stderr
    # --update-baseline pins the candidate...
    out = _run_perfdiff(str(missing), str(cand), "--update-baseline")
    assert out.returncode == 0 and missing.exists()
    # ...and the pinned pair now diffs clean, as JSON too
    out = _run_perfdiff(str(missing), str(cand), "--json")
    assert out.returncode == 0
    payload = json.loads(out.stdout)
    assert payload["ok"] and payload["rows"]
    # a non-anatomy doc cannot be pinned
    junk = tmp_path / "junk.json"
    junk.write_text(json.dumps({"kind": "not_anatomy"}))
    out = _run_perfdiff(str(missing), str(junk), "--update-baseline")
    assert out.returncode == 1
    assert "not an anatomy document" in out.stderr
    # nor diffed against
    out = _run_perfdiff(str(missing), str(junk))
    assert out.returncode == 1


# ------------------------------------------------ the PerfPlane runtime

class _StubRecorder:
    def __init__(self):
        self.triggers = []

    def trigger(self, kind, detail, step=None, **kw):
        self.triggers.append((kind, detail, step))


def test_recompile_regression_edge_trigger():
    """First sight of a label never fires (the overlap_drop pattern); a
    recompile that shifts a bucket beyond the band fires exactly once,
    names the bucket, and reaches the flight recorder."""
    from types import SimpleNamespace
    rec = _StubRecorder()
    # the default 0.05ms floor is sized for real programs; the synthetic
    # module's collectives live in microseconds, so tighten it — which
    # also proves the config plumbing end to end
    plane = pp.PerfPlane(SimpleNamespace(band=0.25, band_floor_ms=0.0005,
                                         history=32, device_model={}),
                         recorder=rec)
    plane.observe_program("step", SYNTH_HLO, kind="compile")
    assert plane.regressions == 0 and rec.triggers == []
    # recompile to the same program: inside the band, no trigger
    plane.observe_program("step", SYNTH_HLO, kind="recompile")
    assert plane.regressions == 0 and rec.triggers == []
    # recompile to a program whose collective quadrupled
    shifted = SYNTH_HLO.replace("f32[128,128] all-reduce",
                                "f32[512,128] all-reduce")
    plane.observe_program("step", shifted, kind="recompile", step=7)
    assert plane.regressions == 1
    assert len(rec.triggers) == 1
    kind, detail, step = rec.triggers[0]
    assert kind == "perf_regression" and step == 7
    assert "coll_all_reduce" in detail
    assert plane.last_regression["buckets"] == ["coll_all_reduce"]
    summary = plane.summary()
    assert summary["regressions"] == 1
    assert summary["last_regression"]["label"] == "step"
    # the bundle provider embeds the anatomy + roofline table
    bundle = plane.bundle_section()
    assert bundle["summary"]["programs_observed"] == 3
    assert any(r["bucket"] == "attn" for r in bundle["rooflines"]["step"])
    plane.close()


def test_disabled_allocates_nothing_train_and_serving():
    """perf_plane defaults off: no PerfPlane object on either engine,
    and arming it without the compile plane is a config error, not a
    silent no-op."""
    import jax
    model = GPT2Model(GPT2Config(vocab_size=64, n_positions=32, n_embd=32,
                                 n_layer=1, n_head=2,
                                 pad_vocab_to_multiple=8))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": jax.device_count() * 2,
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "steps_per_print": 0,
    })
    try:
        assert engine._perf_plane is None
    finally:
        engine.close()
    with pytest.raises(ConfigError, match="perf_plane requires"):
        deepspeed_tpu.initialize(model=model, config={
            "train_batch_size": jax.device_count() * 2,
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "steps_per_print": 0,
            "perf_plane": {"enabled": True},
        })
    from deepspeed_tpu.serving import ServingEngine
    inf = deepspeed_tpu.init_inference(
        GPT2Model(GPT2Config(vocab_size=64, n_positions=32, n_embd=32,
                             n_layer=1, n_head=2, pad_vocab_to_multiple=1,
                             dtype="float32")),
        config={"dtype": "float32"})
    srv = ServingEngine(inf, {"num_slots": 2, "max_model_len": 32})
    try:
        assert srv._perf_plane is None
    finally:
        srv.shutdown()
    with pytest.raises(ConfigError, match="serving.perf_plane requires"):
        ServingEngine(inf, {"num_slots": 2, "max_model_len": 32,
                            "perf_plane": {"enabled": True}})
    # unknown device-model keys are rejected at config time
    from deepspeed_tpu.runtime.config import PerfPlaneConfig
    with pytest.raises(ConfigError, match="unknown key"):
        PerfPlaneConfig.from_dict({"enabled": False,
                                   "device_model": {"peek_flops": 1.0}})


def test_engine_observes_train_program_and_releases_gauges():
    """Armed on a real training engine: the warmup compile's ledger
    event gets its anatomy attached, the statusz 'anatomy' section and
    dstpu_anat_* gauges go live, and engine.close() retracts them."""
    import jax
    tracer = get_tracer()
    prev = tracer.enabled
    tracer.clear()
    tracer.configure(enabled=True)
    model = GPT2Model(GPT2Config(vocab_size=64, n_positions=32, n_embd=32,
                                 n_layer=1, n_head=2,
                                 pad_vocab_to_multiple=8))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": jax.device_count() * 2,
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "steps_per_print": 0,
        "telemetry": {"enabled": True, "mfu": False},
        "compile_plane": {"enabled": True},
        "perf_plane": {"enabled": True},
    })
    try:
        rng = np.random.default_rng(0)
        engine.train_batch(batch={"input_ids": rng.integers(
            0, 63, size=(1, engine.train_batch_size, 16),
            dtype=np.int32)})
        plane = engine._perf_plane
        assert plane is not None and plane.programs_observed >= 1
        ev = engine._compile_plane.events()[-1]
        assert "anatomy" in ev
        assert ev["anatomy"]["total_ms"] == pytest.approx(float(sum(
            ev["anatomy"]["buckets"].values())), abs=1e-5)
        summary = plane.summary()
        assert "train_batch" in summary["programs"]
        dump = prometheus_dump(tracer)
        assert 'dstpu_anat_total_ms{program="train_batch"}' in dump
        assert 'dstpu_anat_memory_bound_fraction{program="train_batch"}' \
            in dump
    finally:
        engine.close()
    assert "dstpu_anat_" not in prometheus_dump(tracer)
    tracer.clear()
    tracer.configure(enabled=prev)


# ---------------------------------------------------- rendering surfaces

def _run_top(snapshot_path):
    top = os.path.join(REPO, "bin", "ds_tpu_top")
    return subprocess.run(
        [sys.executable, top, "--once", "--snapshot", str(snapshot_path)],
        capture_output=True, text=True, timeout=30)


def test_ds_tpu_top_renders_anatomy_panel(tmp_path):
    snap = {"counters": {},
            "sections": {"anatomy": {
                "programs_observed": 2, "regressions": 1, "band": 0.25,
                "programs": {"train_batch": {
                    "total_ms": 1.25, "memory_bound_fraction": 0.8,
                    "buckets_ms": {"attn": 0.5, "coll_all_gather": 0.45,
                                   "mlp": 0.3}}},
                "last_regression": {"label": "train_batch",
                                    "buckets": ["coll_all_gather"],
                                    "detail": "0.1ms -> 0.45ms"}}}}
    path = tmp_path / "snap.json"
    path.write_text(json.dumps(snap))
    out = _run_top(path)
    assert out.returncode == 0, out.stderr
    assert "anatomy (2 programs, 1 regressions)" in out.stdout
    assert "train_batch" in out.stdout
    assert "attn" in out.stdout and "coll_all_gather" in out.stdout
    assert "mem-bound" in out.stdout
    assert "PERF REGRESSION" in out.stdout


def test_ds_tpu_top_degrades_without_anatomy_section(tmp_path):
    """Pre-perf-plane snapshots render with no anatomy panel and no
    crash."""
    snap = {"counters": {"telemetry/step_time_ms": 12.0},
            "goodput": {"goodput_fraction": 0.9, "wall_s": 10.0,
                        "buckets": {"productive_step": 9.0}}}
    path = tmp_path / "old.json"
    path.write_text(json.dumps(snap))
    out = _run_top(path)
    assert out.returncode == 0, out.stderr
    assert "anatomy" not in out.stdout
