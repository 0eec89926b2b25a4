"""The sessions traffic (``generators/openloop_sessions.py``) and its cell,
``opt-1.3b.serve-sessions``: what a seed changes and what it does not, what
turns share, how the list opens on a pool in use, and that the ladder and
the history together reach every program a window can (nothing here needs
JAX but the last test, the cell's rehearsal under a laid-over manifest:
PR 46 left the cell out of ``BENCHMARK.json``, ``PERF.md`` section 7)."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.generators import openloop_sessions as S          # noqa: E402
from chipbench.model import load_json                            # noqa: E402
from deepspeed_tpu.serving.fleet.prefix_cache import (            # noqa: E402
    RadixPrefixCache, reuse_plan)

CELL = "opt-1.3b.serve-sessions"
VOCAB = 50272


@pytest.fixture(scope="module")
def cell():
    return load_json("workloads", CELL + ".json")


@pytest.fixture(scope="module")
def traffic(cell):
    return load_json("traffic", cell["traffic"] + ".json")


@pytest.fixture(scope="module")
def made(traffic):
    return {seed: S.generate(traffic, seed, VOCAB, 40.0)
            for seed in (2**31 + 46, 7)}


def common(a, b):
    n = min(len(a), len(b))
    diff = np.nonzero(a[:n] != b[:n])[0]
    return int(diff[0]) if len(diff) else n


def test_the_traffic_is_the_issues(traffic, cell):
    """ISSUE 46's cell 2: 8 system prompts of 512-1,024 by Zipf(1.0), 3-5
    turns, the two lognormals, think 2-6 s, the model's 2,048 positions, the
    prefix cache on and nothing else, the rate inside 0.70-0.85 of the knee
    swept on the parent."""
    assert traffic["generator"] == "openloop_sessions"
    assert traffic["system_prompts"] == {"count": 8, "min": 512, "max": 1024,
                                         "zipf": 1.0}
    assert traffic["turns"] == {"min": 3, "max": 5}
    assert traffic["user"] == {"median": 64, "sigma": 0.6, "min": 16,
                               "max": 256}
    assert traffic["answer"] == {"median": 96, "sigma": 0.7, "min": 16,
                                 "max": 256}
    assert traffic["think_s"] == {"min": 2.0, "max": 6.0}
    assert 0.70 <= traffic["rate_per_s"] / traffic["knee"]["knee_per_s"] \
        <= 0.85
    serving = cell["serving"]
    assert serving["max_model_len"] == 2048 == traffic["max_model_len"]
    assert serving["prefix_cache"]["enabled"] is True
    assert set(serving) == {"num_slots", "max_model_len", "max_queue",
                            "prefix_cache"}
    assert cell["job"] == "serve" and cell["chips"] == 1
    assert cell["config"] == "opt-1.3b"


def test_a_seed_changes_the_ids_and_nothing_else(made):
    a, b = made.values()
    assert [r["due"] for r in a] == [r["due"] for r in b]
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in b]
    assert [r["max_new"] for r in a] == [r["max_new"] for r in b]
    assert any((x["prompt"] != y["prompt"]).any() for x, y in zip(a, b))
    again = S.generate(load_json("traffic", "serve-sessions.json"),
                       2**31 + 46, VOCAB, 40.0)
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, again))
    assert max(int(r["prompt"].max()) for r in a) < VOCAB


def test_another_rate_is_the_same_sessions_closer_together(traffic):
    slow, _ = S.sessions(traffic, 40.0, 4.0)
    fast, _ = S.sessions(traffic, 40.0, 8.0)
    assert len(fast) > 1.7 * len(slow)
    for s, f in zip(slow[:50], fast[:50]):
        assert (s["system"], s["user"], s["answer"]) == \
            (f["system"], f["user"], f["answer"])
    sent = lambda rate: sum(0 <= r["due"] < 40.0 for r in S.generate(
        traffic, 1, VOCAB, 40.0, rate=rate))
    counts = [sent(r) for r in (4.0, 5.0, 6.0, 7.0, 8.0)]
    assert counts == sorted(counts) and counts[-1] > 1.8 * counts[0]
    # a window holds 0.85-0.95 of the nominal rate: a session that would
    # pass the model's positions stops early
    assert all(0.85 < n / (40.0 * r) < 0.95
               for n, r in zip(counts, (4.0, 5.0, 6.0, 7.0, 8.0)))


def test_a_turn_opens_with_the_turn_before_it_and_with_its_system_prompt(
        made, traffic):
    reqs = [r for r in made[7] if "session" in r]
    by = {}
    for r in reqs:
        by.setdefault(r["session"], []).append(r)
    followed = 0
    for turns in by.values():
        for a, b in zip(turns, turns[1:]):
            if b["turn"] != a["turn"] + 1 or a["due"] < 0:
                continue            # a turn before the history, or moved ids
            followed += 1
            assert common(a["prompt"], b["prompt"]) == len(a["prompt"])
            grown = len(b["prompt"]) - len(a["prompt"])
            assert a["max_new"] + 16 <= grown <= a["max_new"] + 256
            # after the answer has streamed at the nominal pace, 2-6 s more
            think = b["due"] - a["due"] - \
                a["max_new"] * traffic["tick_ms"] / 1e3
            assert 2.0 <= think <= 6.0
    assert followed > 50
    firsts = [r for r in reqs if r["turn"] == 0]
    shared = sorted({common(x["prompt"], y["prompt"])
                     for x in firsts[:40] for y in firsts[:40] if x is not y})
    # nothing, or a whole system prompt of 512-1,024
    assert shared[0] == 0 and all(512 <= n <= 1024 for n in shared[1:])
    assert len(shared) > 2
    assert max(len(r["prompt"]) + r["max_new"] for r in reqs) <= 2048
    assert min(len(r["prompt"]) for r in reqs) >= 512 + 16


def test_the_list_opens_on_a_pool_in_use(made, traffic):
    reqs = made[7]
    assert [r["due"] for r in reqs] == sorted(r["due"] for r in reqs)
    ladder = [r for r in reqs if r.get("ladder")]
    assert ladder == reqs[:len(ladder)] and all(r["due"] < -traffic[
        "history_s"] for r in ladder)
    spec = traffic["ladder"]
    own = [len(r["prompt"]) - spec["prefix"] for r in ladder]
    assert own == [0, 16, 32, 64, 128, 256, 512, 1024]
    assert all(common(ladder[0]["prompt"], r["prompt"]) == spec["prefix"]
               for r in ladder[1:])
    before = [r for r in reqs if r["due"] < 0 and not r.get("ladder")]
    ended = [r for r in before if r["max_new"] == S.DONOR_TOKENS]
    streaming = [r for r in before if r["max_new"] > S.DONOR_TOKENS]
    assert len(ended) > 100 and 3 <= len(streaming) <= 24
    # one still streaming was due within its answer's time at the nominal pace
    assert all(-r["due"] < 256 * traffic["tick_ms"] / 1e3 for r in streaming)
    assert min(r["due"] for r in before) >= -traffic["history_s"]
    # a donor's lane is bound before it ends: the scheduler parks no other
    assert S.DONOR_TOKENS == 2 and all(r["max_new"] >= 2 for r in reqs)


def test_set_up_reaches_every_program_a_window_can(made, traffic, cell):
    """The cell's requests through the prefix cache itself, one admission a
    tick, finished lanes donated, the least recently used evicted: every
    suffix bucket and whole-prefill bucket an admission of the window runs
    was run by an admission of set-up (the ladder, the history) or by the
    cell's ``warm_prompt_lengths``. The order of a real run's donations
    differs; the ladder makes the set the whole of what can come."""
    pow2 = lambda n: 1 << max(0, n - 1).bit_length()
    slots = cell["serving"]["num_slots"]
    cache = RadixPrefixCache(type("C", (), {
        "min_prefix_len": cell["serving"]["prefix_cache"]["min_prefix_len"],
        "max_cached_slots": 0}))
    free, seen = list(range(slots)), {"set-up": set(), "window": set()}
    for r in made[7]:
        slot = free.pop() if free else cache.evict_lru()
        hit = cache.lookup(r["prompt"])
        offset = 0
        if hit is not None:
            offset, _ = reuse_plan(len(r["prompt"]), hit.matched, 2048)
            cache.release(hit, offset)
        kind = ("suffix", pow2(len(r["prompt"]) - offset)) if offset else \
            ("whole", pow2(len(r["prompt"])))
        seen["set-up" if r["due"] < 0 else "window"].add(kind)
        accepted, _ = cache.donate(slot, r["prompt"], len(r["prompt"]))
        if not accepted:
            free.append(slot)
    warmed = {("whole", pow2(n)) for n in cell["warm_prompt_lengths"]}
    assert seen["window"] <= seen["set-up"] | warmed
    assert {("suffix", b) for b in (16, 32, 64, 128, 256, 512, 1024)} <= \
        seen["set-up"]
    assert {k for k in seen["window"] if k[0] == "whole"} <= warmed == \
        {("whole", 1024), ("whole", 2048)}
    assert cache.hits > 0.7 * cache.lookups


def laid_over(man):
    """``BENCHMARK.json`` as a ``benchmark`` issue would leave it: the cell at
    the end of ``workloads`` and of every list its control is in."""
    man["workloads"].append({"name": CELL, "config": "opt-1.3b",
                             "traffic": "serve-sessions", "chips": 1,
                             "why": load_json("workloads",
                                              CELL + ".json")["why"]})
    for m in man["end_to_end"] + man["per_layer"]:
        if "opt-1.3b.serve-chat" in m.get("workloads", []):
            m["workloads"].append(CELL)
    return man


def test_the_cell_waits_outside_the_manifest(cell):
    """PR 46 left the cell out of ``BENCHMARK.json`` (``itl_p95_ms`` spread
    too widely in the driver's two sets of six: ``PERF.md`` section 7); its
    files stay, with the readings, for a ``benchmark`` issue to enter."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    assert CELL not in raw
    man = laid_over(json.loads(raw))
    assert len(man["workloads"][-1]["why"]) <= 200
    assert CELL in next(m for m in man["end_to_end"]
                        if m["name"] == "itl_p95_ms")["workloads"]
    placed = load_json("traffic", "serve-sessions.json")["placement"]
    assert placed["gap_p93_ms"] <= placed["gap_p95_ms"] <= placed["gap_p97_ms"]
    assert "1.09%" in placed["driver"]
    assert cell["memory"]["memory_peak_bytes"] >= 0.25 * 16e9


def test_the_cell_rehearses_under_a_laid_over_manifest(tmp_path):
    """A copy of ``chipbench/`` under that manifest: the rehearsal sends the
    ladder and the history in set-up, compiles nothing in the window, is
    ``correct`` and reports what its control reports."""
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = laid_over(json.load(f))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "chipbench" / "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 46), "--seconds", "2",
         "--trace", "0", "--rehearse"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                    "setup_s"}
