"""What PR 42 adds to the benchmark, as new files beside the old: the join of
a device trace to the program's scope tables (``layer_metrics/_scope_join``)
on a made-up trace with hand-made tables, the readers of
``layer_metrics/scope_time.py`` on it, the eleven entries a ``benchmark`` PR
appends to ``BENCHMARK.json`` for them (``scope_time.entries.json``), and
traced rehearsals under a manifest that holds those, whose result lines hold
every new metric of their cell and whose scopes add up to each program's busy
time."""

import json
import os
import re
import shutil
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.layer_metrics import _program_spans as P          # noqa: E402
from chipbench.layer_metrics import _scope_join as J             # noqa: E402
from chipbench.layer_metrics import scope_time                   # noqa: E402
from chipbench.trace import Device, Trace                        # noqa: E402

MS = 1e-3
#: two prefill buckets number their fusions differently under one name
TABLES = {
    "jit_pf": {("slot_prefill", 1024, 2048): {
                   "fusion.1": "layers/attn/kv_read", "fusion.2": "head",
                   "while.3": "layers"},
               ("slot_prefill", 2048, 2048): {
                   "fusion.1": "layers/mlp", "fusion.2": "layers/attn/kv_read",
                   "while.3": "layers", "sort.4": "sample"}},
    "jit_dec": {("slot_decode", 24, 2048): {
        "decode_attend.6": "layers/attn/kv_read", "fusion.1": "?layers/mlp",
        "sort.5": "sample", "copy.9": None, "while.3": "layers"}},
}


def made_up():
    """Two ticks. Tick 1: a bucket-1024 prefill (10 ms) and a decode step
    (5 ms); tick 2: a bucket-2048 prefill (20 ms), a decode step (5 ms) and
    a program the tables do not know (1 ms). The four known programs' bodies
    lie in a loop that takes 0.2 ms of its own."""
    def program(at, ops):
        out, t = [], at
        for name, ms in ops:
            out.append((t, t + ms * MS, name))
            t += ms * MS
        return out, (at, t)

    ops, mods = [], []
    for at, module, body in (
            (0.010, "jit_pf", [("fusion.1", 6), ("fusion.2", 4)]),
            (0.030, "jit_dec", [("fusion.1", 2),
                                ("decode_attend.6 tpu_custom_call", 1),
                                ("sort.5", 1.5), ("copy.9", 0.5)]),
            (0.110, "jit_pf", [("fusion.1", 8), ("fusion.2", 10),
                               ("sort.4", 2)]),
            (0.140, "jit_dec", [("fusion.1", 2),
                                ("decode_attend.6 tpu_custom_call", 1),
                                ("sort.5", 1.5), ("copy.9", 0.5)]),
            (0.150, "jit_convert", [("fusion.1", 1)])):
        inner, (s, e) = program(at, body)
        if module != "jit_convert":     # a loop around the body's operations
            s, e = s - 0.1 * MS, e + 0.1 * MS
            ops.append((s, e, "while.3"))
        ops += inner
        mods.append((s, e, module))
    ticks = [(0.0, 0.1, "serve/tick", 1, 6), (0.1, 0.2, "serve/tick", 2, 6)]
    records = [(0.001, 0.002, "serve/prefill_prep", 800, 1024),
               (0.101, 0.102, "serve/prefill_prep", 1500, 2048)]
    placed = P.Placed(sorted(ticks + records), ticks, 0.0)
    trace = Trace([Device("/device:TPU:0", sorted(ops), mods)],
                  [(0.0, 0.2, "window")])
    return trace, placed


def test_join_gives_each_piece_to_its_program_and_bucket():
    trace, placed = made_up()
    got = J.join(trace, TABLES, placed)
    assert got.calls == {"jit_pf": 2, "jit_dec": 2}
    assert got.no_table == {"jit_convert": 1}
    assert got.bucket_tokens == 1024 + 2048
    pf = got.seconds["jit_pf"]
    # fusion.1 is the attend in bucket 1024's program, the MLP in 2048's
    assert pf["layers/attn/kv_read"] == pytest.approx((6 + 10) * MS)
    assert pf["layers/mlp"] == pytest.approx(8 * MS)
    assert pf["head"] == pytest.approx(4 * MS)
    assert pf["sample"] == pytest.approx(2 * MS)
    dec = got.seconds["jit_dec"]
    assert dec["layers/attn/kv_read"] == pytest.approx(2 * MS)  # the marker
    assert dec["sample"] == pytest.approx(3 * MS)
    assert dec[None] == pytest.approx(1 * MS)
    assert dec["layers"] == pytest.approx(0.4 * MS)
    assert pf["layers"] == pytest.approx(0.4 * MS)
    assert got.unnamed == {("jit_dec", "copy.9"): pytest.approx(1 * MS)}
    assert got.absent == set()          # the table lists it, as ``None``
    assert got.untabled == pytest.approx(1 * MS)    # the unknown program
    # a scope behind ``?`` was inferred from the instruction's neighbours:
    # its time is under the scope, and counted apart
    assert dec["layers/mlp"] == pytest.approx(4 * MS)
    assert got.inferred == {"jit_dec": pytest.approx(4 * MS)}
    assert got.by_name[("jit_dec", "fusion.1")][1] == "?layers/mlp"  # logged
    for module in ("jit_pf", "jit_dec"):
        assert sum(got.seconds[module].values()) == \
            pytest.approx(got.busy[module], rel=1e-9)
    assert sum(sum(v.values()) for v in got.seconds.values()) + \
        got.untabled == pytest.approx(trace.busy_s(0))


def ctx_of(job="serve"):
    trace, placed = made_up()
    joined = J.join(trace, TABLES, placed)
    return types.SimpleNamespace(cell={"job": job}, log=lambda msg: None,
                                 state={"scope_time": joined}), {}, trace


@pytest.mark.parametrize("name, value", [
    ("prefill_attend_us_per_token", 16e3 / 3072),
    ("prefill_ffn_us_per_token", 8e3 / 3072),
    ("prefill_head_us_per_token", 6e3 / 3072),     # head and sample
    ("decode_sample_ms", 1.5),
    ("scope_unnamed_share.serve", 100 * 2 / 41.8),
])
def test_readers_on_the_made_up_trace(name, value):
    assert scope_time.METRICS[name](*ctx_of()) == pytest.approx(value)


@pytest.mark.parametrize("name", sorted(scope_time.METRICS))
def test_readers_leave_out_what_they_cannot_read(name):
    """Without a trace, for a program without ``scope_tables`` (the parent
    of this PR: ``load`` gives ``None``), and where the cell ran no call of
    the program the metric reads: ``None``, and nothing raises."""
    read = scope_time.METRICS[name]
    ctx, record, trace = ctx_of()
    ctx.state["scope_time"] = None
    assert read(ctx, record, trace) is None
    assert read(ctx, record, None) is None
    if name.startswith(("step_", "collective", "scope_unnamed_share.train")):
        assert read(*ctx_of()) is None      # a serving trace: no train step


def test_a_program_without_scope_tables_reads_none(monkeypatch):
    import deepspeed_tpu.telemetry as telemetry
    trace, _ = made_up()
    monkeypatch.setattr(telemetry, "get_tracer", lambda: object())
    ctx = types.SimpleNamespace(cell={"job": "serve"}, state={},
                                log=lambda msg: None)
    assert J.load(ctx, trace) is None
    assert scope_time.METRICS["decode_sample_ms"](ctx, {}, trace) is None


def no_modules():
    """``made_up`` as a rehearsal has it: no module interval, a dispatch
    record at each known program's start."""
    trace, placed = made_up()
    dev = trace.devices[0]
    by = {module: phase for phase, module in J.DISPATCHED.items()}
    phases = sorted(placed.phases + [
        (m[0], m[0] + 1e-4, by[m[2]], 0, 0)
        for m in dev.modules if m[2] in by])
    return (Trace([Device(dev.name, dev.ops, [])], trace.spans),
            P.Placed(phases, placed.units, 0.0))


def test_a_chip_trace_without_module_intervals_is_not_joined():
    """The host's dispatch records stand in for "XLA Modules" in a rehearsal
    and nowhere else: on the chip they would cut the device's time by host
    intervals and nothing in the result line would say so."""
    trace, placed = no_modules()
    logged = []
    assert J.join(trace, TABLES, placed, logged.append) is None
    assert "no program started" in logged[0]
    ctx = types.SimpleNamespace(cell={"job": "serve"}, log=lambda msg: None,
                                state={"scope_time": None})
    for read in scope_time.METRICS.values():
        assert read(ctx, {}, trace) is None


def test_a_rehearsal_takes_its_intervals_from_the_dispatch_records():
    trace, placed = no_modules()
    got = J.join(trace, TABLES, placed, rehearsal=True)
    assert got.calls == {"jit_pf": 2, "jit_dec": 2}
    assert got.bucket_tokens == 1024 + 2048
    for module in ("jit_pf", "jit_dec"):
        assert sum(got.seconds[module].values()) == \
            pytest.approx(got.busy[module], rel=1e-9)


def test_collective_time_alone_is_what_no_other_operation_covers():
    """A step of 10 ms: an all-gather of 4 ms of which a fusion covers 1,
    and an asynchronous reduce-scatter whose start-to-done span of 3 ms lies
    under a fusion but for its last 0.5 ms, which its ``done`` waits out."""
    ops = [(0.000, 0.010, "while.1"),
           (0.000, 0.004, "all-gather.2"), (0.003, 0.004, "fusion.7"),
           (0.004, 0.0085, "fusion.8"),
           (0.0085, 0.009, "reduce-scatter-done.3"),
           (0.009, 0.010, "fusion.9")]
    asyn = [(0.006, 0.009, "reduce-scatter-start.3")]
    trace = Trace([Device("/device:TPU:0", ops, [(0.0, 0.010,
                                                  "jit_train_step")], asyn)],
                  [(0.0, 0.010, "window")])
    tables = {"jit_train_step": {("train", None): {
        "all-gather.2": "forward/layers", "fusion.7": "forward/layers/mlp",
        "fusion.8": "backward/layers/mlp", "fusion.9": "optimizer",
        "reduce-scatter-start.3": "backward/layers",
        "reduce-scatter-done.3": "backward/layers", "while.1": None}}}
    logged = []
    ctx = types.SimpleNamespace(
        cell={"job": "train"}, log=logged.append,
        state={"scope_time": J.join(trace, tables, None)})
    assert scope_time.collective_exposed_ms(ctx, {}, trace) == \
        pytest.approx(3.0 + 0.5)
    line, = [x for x in logged if "collective time alone" in x]
    assert json.loads(line.split("scope: ")[1].split(";")[0]) == \
        {"backward": 0.5, "forward": 3.0}
    assert scope_time.METRICS["step_optimizer_ms"](ctx, {}, trace) == \
        pytest.approx(1.0)
    assert scope_time.METRICS["step_backward_ms"](ctx, {}, trace) == \
        pytest.approx(4.5 + 0.5)


# ---------------------------------------------------------------- manifest

def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def entries():
    """The eleven entries as they go at the END of ``per_layer``. They are
    not in ``BENCHMARK.json`` yet: PR 39's ``test_chipbench_kv_read.py`` pins
    its two as ``per_layer[-2:]``, the benchmark check takes a new entry at
    the end of its list alone, and only a ``benchmark`` PR may edit that
    test. Such a PR drops the pin and appends this file as it is."""
    with open(os.path.join(ROOT, "chipbench", "layer_metrics",
                           "scope_time.entries.json")) as f:
        return json.load(f)


SERVE = ["opt-1.3b.serve-chat", "olmoe-1b-7b.serve-chat-2k",
         "lfm2-24b-a2b.serve-agent-4k", "opt-1.3b.serve-longprompt",
         "k-exaone-236b-a23b.serve-longdoc-16k",
         "xing4.0-29b-a4b.serve-docqa"]         # the last since PR 57
TRAIN = ["gpt2-medium.train-z1", "opt-1.3b.train-z3-dp4"]


def test_the_eleven_entries_each_with_a_reader_fit_the_manifest():
    """By the rules ``test_chipbench_manifest.py`` holds the manifest to:
    just the keys an entry has, a layer the manifest names, a ``moves`` that
    every listed cell reports. Where ``BENCHMARK.json`` holds one of them
    already (after the ``benchmark`` PR), it is this file's, field for
    field."""
    man, mine = manifest(), entries()
    assert [m["name"] for m in mine] == list(scope_time.METRICS)
    cells = [w["name"] for w in man["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in man["end_to_end"]}
    layers = {m["layer"] for m in man["per_layer"]}
    had = {m["name"]: m for m in man["per_layer"]}
    for m in mine:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}, m
        assert (m["better"], m["source"]) == ("lower", "device_trace")
        assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}", m["name"])
        assert m["layer"] in layers | {"ZeRO planner / step"}, m
        assert all(c in e2e[m["moves"]] for c in m["workloads"]), m
        assert had.get(m["name"], m) == m
        want = TRAIN if m["moves"] == "train_tokens_per_s" else SERVE
        assert m["workloads"] == (want[1:] if m["name"].startswith(
            "collective") else want), m
    assert {m["name"]: m["unit"] for m in mine if m["unit"] != "ms"} == {
        "prefill_attend_us_per_token": "us", "prefill_ffn_us_per_token": "us",
        "prefill_head_us_per_token": "us", "scope_unnamed_share.serve": "%",
        "scope_unnamed_share.train": "%"}
    # cell 8 runs cell 2's program and reports no gap: it gets none of them
    assert not any("opt-1.3b.serve-backlog" in m["workloads"] for m in mine)


def test_entries_of_earlier_prs_stand_as_they_were():
    """Every entry the benchmark had before this PR is there, field for
    field, by name and wherever it stands in the list."""
    per_layer = manifest()["per_layer"]
    by = {m["name"]: m for m in per_layer}
    assert len(by) == len(per_layer)
    for name, moves in (("kv_read_share", "itl_p95_ms"),
                        ("kv_read_share.backlog", "serve_tokens_per_s")):
        m = by[name]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == ("%", "lower", "program_counter", "Kernels",
                                moves)
    assert by["kv_read_share.backlog"]["workloads"] == \
        ["opt-1.3b.serve-backlog"]
    assert "opt-1.3b.serve-chat" in by["kv_read_share"]["workloads"]


# --------------------------------------------------------------- rehearsals

@pytest.fixture(scope="module")
def overlay(tmp_path_factory):
    """A copy of ``chipbench/`` under a manifest that holds the eleven
    entries at the end of ``per_layer``, as the ``benchmark`` PR will leave
    it: ``run.py`` reads the ``BENCHMARK.json`` beside its own directory and
    calls the readers of the metrics that lists."""
    top = tmp_path_factory.mktemp("overlay")
    shutil.copytree(os.path.join(ROOT, "chipbench"), top / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    man = manifest()
    have = {m["name"] for m in man["per_layer"]}
    man["per_layer"] += [m for m in entries() if m["name"] not in have]
    (top / "BENCHMARK.json").write_text(json.dumps(man))
    return str(top)


def rehearse(top, cell, seconds):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(top, "chipbench", "run.py"),
         "--workload", cell, "--seed", str(2**31 + 42), "--seconds", seconds,
         "--trace", "1", "--rehearse"],
        cwd=top, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return proc.stdout, json.loads(proc.stdout.strip().splitlines()[-1])


#: what a rehearsal logs where the host was too loaded to serve its traffic:
#: the host's records could not be laid on the trace's clock, or the drain
#: ran into its limit with requests unfinished (``failed_requests``)
OVERLOADED = ("nothing joined", "drain limit reached")


def told(out):
    """The lines that say why a run is or is not ``correct``, and its end."""
    return "\n".join([x for x in out.splitlines() if "] check " in x
                      or "drain" in x] + [out[-2000:]])


@pytest.mark.parametrize("cell, seconds", [
    ("gpt2-medium.train-z1", "2"), ("opt-1.3b.serve-chat", "1"),
    ("olmoe-1b-7b.serve-chat-2k", "1"), ("opt-1.3b.train-z3-dp4", "2")])
def test_traced_rehearsal_gives_every_new_metric_of_the_cell(overlay, cell,
                                                             seconds):
    """The result line holds every one of the eleven entries that lists the
    cell; each program's scopes add up to its busy time within 0.5%.
    (Cells 1 and 4 drop their engine inside ``check``, before
    any reader runs: their tables were left with the tracer by
    ``close()``.) A serving cell's window is the one second that
    ``test_chipbench_traced_cost.py`` rehearses, with half the requests of
    three: the driver's run of this PR's first tree read ``correct`` false
    in cell 3 once, in a run of 123 s for 18 (six test workers on eight
    cores), and of a rehearsal's checks only ``failed_requests`` depends on
    the host: the requests not finished inside the drain's 20 s."""
    out, last = rehearse(overlay, cell, seconds)
    if any(word in out for word in OVERLOADED):
        out, last = rehearse(overlay, cell, seconds)     # once more
    assert last["correct"] is True and last["device"]["platform"] == "cpu", \
        told(out)
    mine = {m["name"] for m in entries() if cell in m["workloads"]}
    assert mine and mine <= set(last["metrics"]), \
        (mine - set(last["metrics"]), told(out))
    for name in mine:
        assert last["metrics"][name]["value"] >= 0
    # (no bound on ``scope_unnamed_share`` here: a rehearsal's programs are
    # told apart by the host's dispatch phases, and on a loaded CPU a
    # prefill's last operations run on under the decode step's, where their
    # names are looked up in the wrong table; the chip has module events)
    lines = [x for x in out.splitlines() if "scope time jit_" in x
             and "under a table" in x]
    modules = {x.split("scope time ")[1].split(":")[0] for x in lines}
    assert modules == ({"jit_train_step"} if "train" in cell
                       else {"jit_pf", "jit_dec"})
    for x in lines:
        tabled, busy = (float(v) for v in re.findall(
            r"([\d.]+) ms a call", x)[:2])
        assert tabled == pytest.approx(busy, rel=5e-3), x
    if cell == "opt-1.3b.train-z3-dp4":
        assert any("collective time alone" in x for x in out.splitlines())
