"""The benchmark's own counts and generators (no JAX needed)."""

import collections
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import counts                                    # noqa: E402
from chipbench.generators import openloop_lognormal, zipf_batches  # noqa: E402
from chipbench.model import load_json                           # noqa: E402


@pytest.mark.parametrize("config,flops", [("gpt2-medium", 2_271_713_280),
                                          ("opt-1.3b", 8_167_489_536)])
def test_required_flops_per_token(config, flops):
    dims = load_json("configs", config + ".json")["dims"]
    assert counts.train_flops_per_token(dims, 1024) == flops


def test_decode_bytes_of_opt_1_3b():
    dims = load_json("configs", "opt-1.3b.json")["dims"]
    # 24 x (4 d^2 + 2 d ff) + 50272 d matmul parameters, bf16
    assert counts.weight_bytes(dims, 2) > 2 * counts.matmul_params(dims)
    assert counts.weight_bytes(dims, 2) < 2.01 * counts.matmul_params(dims)
    assert counts.kv_bytes_per_token(dims, 2) == 2 * 24 * 2048 * 2


def test_attention_is_compute_bound_at_1024():
    dims = load_json("configs", "gpt2-medium.json")["dims"]
    for backward in (False, True):
        f = counts.attention_flops(dims, 1024, backward) / 197e12
        b = counts.attention_bytes(dims, 1024, backward) / 819e9
        assert f > b


def _serve(seed, seconds=20.0, rate=None, running=False):
    reqs = openloop_lognormal.generate(load_json("traffic", "serve-chat.json"),
                                       seed, 50272, seconds, rate=rate)
    return [r for r in reqs if (r["due"] < 0) == running]


def test_openloop_same_seed_same_inputs():
    a, b = _serve(7, rate=2.0), _serve(7, rate=2.0)
    assert [r["due"] for r in a] == [r["due"] for r in b]
    assert all((x["prompt"] == y["prompt"]).all() and
               x["max_new"] == y["max_new"] for x, y in zip(a, b))


def test_openloop_seeds_share_the_multiset_of_lengths():
    a, b = _serve(1, rate=2.0), _serve(2**31 + 11, rate=2.0)
    assert len(a) == len(b) == 40
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_new"]):
        assert collections.Counter(map(key, a)) == \
            collections.Counter(map(key, b))
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert [r["due"] for r in a] == [r["due"] for r in b]   # one fixed schedule
    order = sorted(range(40), key=lambda i: a[i]["max_new"])
    # a seed moves a request only among the slots of its block of neighbours
    assert sorted(a[i]["due"] for i in order[:4]) == sorted(
        b[i]["due"] for i in sorted(range(40), key=lambda i: b[i]["max_new"])[:4])
    spec = load_json("traffic", "serve-chat.json")
    assert all(spec["prompt"]["min"] <= len(r["prompt"]) <= spec["prompt"]["max"]
               and spec["output"]["min"] <= r["max_new"] <= spec["output"]["max"]
               and 0 <= r["due"] < 20.0 for r in a)
    assert [r["due"] for r in a] == sorted(r["due"] for r in a)


def test_openloop_steady_start_is_the_same_pool_for_every_seed():
    """The requests already running as the window opens: first in the list,
    the same lengths for every seed (token ids differ), each within the
    traffic's own bounds once the streamed tokens are moved back."""
    a, b = _serve(1, running=True), _serve(2**31 + 11, running=True)
    spec = load_json("traffic", "serve-chat.json")
    full = openloop_lognormal.generate(spec, 1, 50272, 20.0)
    assert [r["due"] for r in full] == sorted(r["due"] for r in full)
    # Little's law: rate x mean request time (about 120 tokens x tick_ms)
    busy = spec["rate_per_s"] * 120 * spec["steady_start"]["tick_ms"] / 1e3
    assert 0.7 * busy <= len(a) <= 1.3 * busy
    assert [(r["due"], len(r["prompt"]), r["max_new"]) for r in a] == \
        [(r["due"], len(r["prompt"]), r["max_new"]) for r in b]
    assert not all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, b))
    for r in a:
        streamed = 1 + int(-r["due"] * 1e3 / spec["steady_start"]["tick_ms"])
        assert r["max_new"] >= 1
        assert spec["output"]["min"] <= r["max_new"] + streamed <= spec["output"]["max"]
        assert spec["prompt"]["min"] <= len(r["prompt"]) - streamed <= spec["prompt"]["max"]
    assert _serve(1, running=True) and not openloop_lognormal.generate(
        {k: v for k, v in spec.items() if k != "steady_start"}, 1, 50272,
        20.0)[0]["due"] < 0


def test_zipf_batches_are_seeded_and_of_one_shape():
    p = {**load_json("traffic", "train-z1.json"), "rows": 2, "seq": 64}
    a = zipf_batches.generate(p, 3, 512, 1.0)
    b = zipf_batches.generate(p, 3, 512, 1.0)
    c = zipf_batches.generate(p, 4, 512, 1.0)
    assert len(a) == len(c) == p["warm_batches"] + 3 and a[0]["input_ids"].shape == (2, 2, 64)
    assert all((x["input_ids"] == y["input_ids"]).all() for x, y in zip(a, b))
    assert not (a[0]["input_ids"] == c[0]["input_ids"]).all()
    ids = np.concatenate([x["input_ids"].ravel() for x in a])
    assert ids.min() >= 0 and ids.max() < 512
    assert (ids == 0).mean() > 5 * (ids == 100).mean()      # Zipf, not uniform
