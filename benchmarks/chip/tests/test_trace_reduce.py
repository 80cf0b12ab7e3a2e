"""The trace reduction on a small trace recorded on a TPU v5e
(``testdata/record.py``): three rounds, each a matmul and an elementwise
pass followed by 50 ms of host sleep."""

import json
from pathlib import Path

import pytest

import trace_reduce

DATA = Path(__file__).resolve().parents[1] / "testdata"


@pytest.fixture(scope="module")
def meta():
    return json.loads((DATA / "small.json").read_text())


def _reduce(meta, program_events=()):
    return trace_reduce.reduce(DATA / "small.xplane.pb", window="window",
                               step="round",
                               step_starts_ns=meta["round_starts_ns"],
                               program_events=program_events)


def test_busy_idle_and_window(meta):
    out = _reduce(meta)
    assert 3 * meta["sleep_s"] < out["window_s"] < 10.0
    assert 0.0 < out["busy_s"] < out["window_s"]
    assert out["idle_share"] == pytest.approx(
        1.0 - out["busy_s"] / out["window_s"])
    # the device sleeps with the host: at least the three sleeps are idle
    assert out["idle_share"] * out["window_s"] >= 3 * meta["sleep_s"] * 0.95


def test_device_ops_and_gaps(meta):
    out = _reduce(meta)
    ops = out["device_ops"]
    assert 0 < len(ops) <= 10
    assert all(t > 0 for _, t in ops)
    assert all(name.startswith("jit_") and "/" in name for name, _ in ops)
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    gaps = out["idle_gaps"]
    assert 3 <= len(gaps) <= 10
    # the three sleeps are the longest gaps, each inside a round span
    for name, seconds in gaps[:3]:
        assert name == "round"
        assert seconds == pytest.approx(meta["sleep_s"], rel=0.5)


def test_program_spans_are_put_on_the_trace_clock(meta):
    # a program span opened with each round, on perf_counter_ns, that
    # lasts as long as the round: the innermost span covering each sleep
    # is then the program's, if the clocks are aligned
    host = trace_reduce._host_events(
        __import__("jax").profiler.ProfileData.from_file(
            str(DATA / "small.xplane.pb")))
    rounds = sorted((s, e) for name, s, e in host if name == "round")
    events = [["X", "sleeping", "train", p + 1, (e - s) - 2, 0, {}]
              for p, (s, e) in zip(meta["round_starts_ns"], rounds)]
    out = _reduce(meta, events)
    assert [name for name, _ in out["idle_gaps"][:3]] == ["sleeping"] * 3
