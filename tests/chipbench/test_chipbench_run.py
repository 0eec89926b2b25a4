"""The one command, end to end at rehearsal size on the CPU: every job's run
ends in one well-formed result line that names the platform it really ran
on; without a TPU and without ``--rehearse`` there is no result line; a new
cell, configuration, traffic mix, generator and per-layer metric are found
when they are dropped in as files and manifest entries only; a share over
100% fails the run."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(args, cwd=ROOT, pythonpath=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    if pythonpath:
        env["PYTHONPATH"] = pythonpath
    else:
        env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "chipbench", "run.py")] + args,
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


def result_of(proc):
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert last["device"]["platform"] == "cpu"      # never passes for a chip run
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] == m["value"]
    return last


def manifest_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  manifest_cells()["workloads"]])
def test_rehearsal_ends_in_one_result_line(cell):
    man = manifest_cells()
    last = result_of(run(["--workload", cell, "--seed", str(2**31 + 5),
                          "--seconds", "2", "--trace", "0", "--rehearse"]))
    want = {m["name"] for m in man["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(last["metrics"]) == want and "setup_s" in want


def test_traced_rehearsal_reports_per_layer_metrics_and_a_breakdown():
    last = result_of(run(["--workload", "gpt2-medium.train-z1", "--seed", "3",
                          "--seconds", "2", "--trace", "1", "--rehearse"]))
    assert {"compile_misses", "step_ms", "mfu", "device_idle.train"} <= \
        set(last["metrics"])
    dev = last["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert len(last["breakdown"]["device_ops"]) <= 10
    assert len(last["breakdown"]["idle_gaps"]) <= 10


@pytest.mark.parametrize("flags, marker", [
    (["--control"], "step_loss_rel_rms_err"),     # fp8 in the program's place
    (["--rate", "9"], "rate override")])
def test_control_and_rate_override_are_reported_not_correct(flags, marker):
    """Harness-only runs can never pass for the cell's own: the control
    reads over the limit of the number that holds the train program, and a
    run at another rate than the cell's is marked."""
    cell = "gpt2-medium.train-z1" if flags == ["--control"] else \
        "opt-1.3b.serve-chat"
    proc = run(["--workload", cell, "--seed", "4", "--seconds", "1",
                "--trace", "0", "--rehearse"] + flags)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1])["correct"] is False
    line = [x for x in proc.stdout.splitlines() if marker in x]
    assert line and ("NOT CORRECT" in line[0] or "not correct" in line[0])


def test_no_tpu_means_no_result_line():
    proc = run(["--workload", "gpt2-medium.train-z1", "--seed", "1",
                "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


@pytest.fixture()
def copy(tmp_path):
    """BENCHMARK.json and chipbench/ alone, as a later PR's checkout has them."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    return tmp_path


def test_bare_directory_fails_without_a_result(copy):
    proc = run(["--workload", "gpt2-medium.train-z1", "--seed", "1",
                "--seconds", "1", "--trace", "0", "--rehearse"],
               cwd=str(copy), pythonpath=None)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def _add(copy, share):
    """A cell, a configuration, a traffic mix, a generator and a per-layer
    metric as NEW files and NEW manifest entries; no existing file edited."""
    bench = copy / "chipbench"
    cfg = json.loads((bench / "configs" / "gpt2-medium.json").read_text())
    cfg["dims"].update(layers=3)
    cfg["program"]["kwargs"].update(n_layer=3)
    (bench / "configs" / "gpt2-3l.json").write_text(json.dumps(cfg))
    (bench / "generators" / "const_batches.py").write_text(
        "import numpy as np\n"
        "def generate(params, seed, vocab, seconds):\n"
        "    rng = np.random.default_rng(seed)\n"
        "    shape = (params['gas'], params['rows'], params['seq'])\n"
        "    return [{'input_ids': rng.integers(0, vocab, shape, dtype=np.int32)}\n"
        "            for _ in range(64)]\n")
    (bench / "traffic" / "uniform.json").write_text(json.dumps(
        {"generator": "const_batches", "gas": 2, "rows": 2, "seq": 64,
         "warm_batches": 2}))
    cell = json.loads((bench / "workloads" /
                       "gpt2-medium.train-z1.json").read_text())
    cell.update(config="gpt2-3l", traffic="uniform")
    cell["rehearse"]["traffic"] = {}
    cell["rehearse"]["cell"]["check"]["step_loss_rel_rms_err"] = 1e-3
    (bench / "workloads" / "gpt2-3l.uniform.json").write_text(json.dumps(cell))
    (bench / "layer_metrics" / "added.py").write_text(
        f"METRICS = {{'added_share': lambda ctx, record, trace: {share},\n"
        f"           'absent': lambda ctx, record, trace: None}}\n")
    man = json.loads((copy / "BENCHMARK.json").read_text())
    name = "gpt2-3l.uniform"
    man["configs"].append({"name": "gpt2-3l", "source": "test", "reduced":
                           ["n_layer"], "file": "chipbench/configs/gpt2-3l.json",
                           "why": "test"})
    man["workloads"].append({"name": name, "config": "gpt2-3l",
                             "traffic": "uniform", "chips": 1, "why": "test"})
    for m in man["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append(name)
    for metric in ("added_share", "absent"):
        man["per_layer"].append(
            {"name": metric, "unit": "%", "better": "higher", "source":
             "host_clock", "layer": "Train engine", "moves":
             "train_tokens_per_s", "workloads": [name]})
    (copy / "BENCHMARK.json").write_text(json.dumps(man))
    return name


def test_added_files_are_found_and_run(copy):
    name = _add(copy, 42.0)
    last = result_of(run(["--workload", name, "--seed", "9", "--seconds", "1",
                          "--trace", "1", "--rehearse"], cwd=str(copy)))
    assert last["metrics"]["added_share"] == {"value": 42.0, "unit": "%"}
    assert "absent" not in last["metrics"]      # nothing to read: left out
    assert "step_ms" not in last["metrics"]     # lists other cells only
    assert last["metrics"]["compile_misses"]["unit"] == "count"


def test_share_over_100_fails_the_run(copy):
    name = _add(copy, 150.0)
    proc = run(["--workload", name, "--seed", "9", "--seconds", "1",
                "--trace", "1", "--rehearse"], cwd=str(copy))
    assert proc.returncode != 0 and "over 100%" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
