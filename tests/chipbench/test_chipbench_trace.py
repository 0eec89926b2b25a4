"""The trace reduction: interval arithmetic on made-up events, and a slice
of a real trace (device 0 of ``gpt2-medium.train-z1`` on a TPU v5e, the
first second of the window, recorded by PR 26's first traced chip run) with
its union-of-intervals busy time and one known gap pinned."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import trace as T                         # noqa: E402

SLICE = os.path.join(os.path.dirname(__file__), "data",
                     "train_z1_slice.json.gz")


def test_union_subtract_and_self_time():
    assert T.union([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert T.subtract([(0, 20)], [(0, 10), (12, 13)]) == [(10, 12), (13, 20)]
    assert T.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]
    nested = [(0, 10, "while"), (1, 3, "a"), (3, 4, "b"), (6, 8, "a"),
              (12, 13, "c")]
    pieces = T.self_events(nested)
    assert T.total((s, e) for s, e, _ in pieces) == 11      # the union
    by_name = {}
    for s, e, n in pieces:
        by_name[n] = by_name.get(n, 0) + e - s
    assert by_name == {"while": 5, "a": 4, "b": 1, "c": 1}
    # adding durations up would say 16 busy seconds of 13
    assert sum(e - s for s, e, _ in nested) == 16


def test_op_names_are_cut_from_the_instruction_text():
    text = ('%attn.23 = (bf16[8,1024,1024]{2,1,0}) custom-call(bf16[8] %x), '
            'custom_call_target="tpu_custom_call"')
    assert T._op_name(text) == "attn.23 tpu_custom_call"
    assert T._op_name("%fusion.3 = bf16[2] fusion(bf16[2] %y)") == "fusion.3"
    assert T.COLLECTIVE.match(T._op_name("%all-gather-start.2 = f32[] x()"))
    assert T._module_name("jit_train_step(113910295899110017)") == \
        "jit_train_step"


@pytest.fixture(scope="module")
def recorded():
    return T.load(SLICE)


def test_recorded_trace_busy_time_is_the_union(recorded):
    dev = recorded.devices[0]
    assert dev.name == "/device:TPU:0" and len(dev.ops) == 27115
    lo = min(s for s, _, _ in dev.ops)
    hi = max(e for _, e, _ in dev.ops)
    busy = T.total(dev.busy(lo, hi))
    assert busy == pytest.approx(0.987515525, abs=1e-8)
    # the events nest (a while and its body): their durations add up to 2.65 s
    assert sum(e - s for s, e, _ in dev.ops) == pytest.approx(2.654154432,
                                                              abs=1e-6)
    assert busy < hi - lo < 1.0
    self_s = dev.op_self_seconds(lo, hi)
    assert sum(self_s.values()) == pytest.approx(busy, abs=1e-8)
    kernels = sum(v for k, v in self_s.items() if "tpu_custom_call" in k)
    assert kernels == pytest.approx(0.291245592, abs=1e-8)
    mods = dev.module_seconds(lo, hi)
    assert mods["jit_train_step"][0] == 2


def test_recorded_trace_known_gap_between_two_steps(recorded):
    dev = recorded.devices[0]
    lo = min(s for s, _, _ in dev.ops)
    hi = max(e for _, e, _ in dev.ops)
    idle = T.subtract([(lo, hi)], dev.busy(lo, hi))
    s, e = max(idle, key=lambda g: g[1] - g[0])
    assert (s, e) == (pytest.approx(0.904848558, abs=1e-8),
                      pytest.approx(0.907800598, abs=1e-8))
    recorded.lo, recorded.hi = lo, hi
    name, seconds = recorded.gaps(0, top=1)[0]
    assert seconds == pytest.approx(e - s) and name == "train_batch"
    by_span = recorded.idle_by_span(0)
    assert sum(by_span.values()) == pytest.approx(T.total(idle))
