"""The per-layer metrics that read the program's own spans
(``program_spans``): per-round sums, self time, and nothing where the
ring lost the window's records or the program records no such span."""

import pytest

from repro.obs import trace as obs_trace

TRAIN = {"host_layer_s.train": "host_layer", "encode_s.train": "encode",
         "decrypt_s.train": "decrypt", "decode_s.train": "decode",
         "split_s.train": "split"}


def _x(name, ts, dur, tid=1):
    return ("X", name, "train", ts, dur, tid, {})


@pytest.fixture
def installed():
    """Install a tracer as the process default, as a traced run does, and
    restore the default after the test."""
    def install(events, capacity=1 << 16):
        tr = obs_trace.Tracer("bench", capacity=capacity)
        for ph, name, cat, ts, dur, tid, attrs in events:
            tr.complete(name, ts, dur, cat=cat, tid=tid, **attrs)
        obs_trace.set_default(tr)
        return tr
    yield install
    obs_trace.set_default(None)


def _run(n_units, unit_rows=None):
    import harness
    units = [{"seconds": 1.0} if unit_rows is None
             else {"seconds": 1.0, "rows": unit_rows}] * n_units
    return harness.Run(cell={}, config={}, traffic={}, seed=0, seconds=0.0,
                       units=units, trace_summary={"busy_s": 0.0})


def _rounds():
    """A set-up round, then two window rounds (ns): children inside and
    outside them, on the round's thread and on another."""
    ev = [_x("round", 0, 1_000), _x("host_layer", 100, 500)]   # set-up
    for r0 in (10_000, 20_000):
        ev += [_x("round", r0, 1_000),
               _x("host_layer", r0 + 100, 300),
               _x("encode", r0 + 50, 20),
               _x("decrypt", r0 + 400, 100),
               _x("decode", r0 + 500, 60),
               _x("decode", r0 + 600, 40),
               _x("split", r0 + 700, 200),
               _x("gc", r0 + 750, 10),            # nested in split
               _x("decrypt", r0 + 400, 100, tid=2),  # another thread
               _x("split", r0 + 950, 100)]        # ends after the round
    return ev


@pytest.mark.parametrize("metric,per_round", [
    ("host_layer_s.train", 300e-9), ("encode_s.train", 20e-9),
    ("decrypt_s.train", 100e-9), ("decode_s.train", 100e-9),
    ("split_s.train", 200e-9)])
def test_train_readers_sum_their_span_per_round(tiny_bench, installed,
                                                metric, per_round):
    installed(_rounds())
    got = tiny_bench.reader(metric).read(_run(2))
    assert got == pytest.approx(per_round, rel=1e-12)


def test_untraced_is_round_less_union_of_children(tiny_bench, installed):
    installed(_rounds())
    # inside each round on its thread: [50,70) [100,400) [400,500)
    # [500,560) [600,640) [700,900) (gc nested): union 720 of 1,000
    got = tiny_bench.reader("untraced_s.train").read(_run(2))
    assert got == pytest.approx(280e-9, rel=1e-12)


def test_fewer_unit_records_than_units_read_nothing(tiny_bench, installed):
    installed(_rounds())
    for metric in (*TRAIN, "untraced_s.train"):
        assert tiny_bench.reader(metric).read(_run(4)) is None, metric


def test_overflowed_ring_reads_nothing(tiny_bench, installed):
    ev = _rounds()
    installed(ev, capacity=len(ev) - 12)     # the first window round lost
    assert tiny_bench.reader("host_layer_s.train").read(_run(2)) is None


def test_a_span_the_program_does_not_record_reads_nothing(tiny_bench,
                                                          installed):
    installed([e for e in _rounds() if e[1] != "encode"])
    assert tiny_bench.reader("encode_s.train").read(_run(2)) is None
    assert tiny_bench.reader("decode_s.train").read(_run(2)) is not None


def test_untraced_run_reads_nothing(tiny_bench, installed):
    installed(_rounds())
    run = _run(2)
    run.trace_summary = None
    for metric in (*TRAIN, "untraced_s.train", "leaf_sum_ms.serve"):
        assert tiny_bench.reader(metric).read(run) is None, metric


def test_leaf_sum_is_milliseconds_per_batch(tiny_bench, installed):
    ev = [("X", "serve_batch", "serve", 0, 5_000_000, 1, {}),
          ("X", "serve_leaf_sum", "serve", 1_000_000, 3_000_000, 1, {})]
    for b0 in (10_000_000, 20_000_000):
        ev += [("X", "serve_batch", "serve", b0, 5_000_000, 1, {}),
               ("X", "serve_leaf_sum", "serve", b0 + 4_000_000, 500_000, 1,
                {})]
    installed(ev)
    got = tiny_bench.reader("leaf_sum_ms.serve").read(_run(2, unit_rows=8))
    assert got == pytest.approx(0.5, rel=1e-12)
