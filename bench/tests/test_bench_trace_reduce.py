"""bench/trace_reduce.py on a small trace recorded on one v5e chip, and on
hand-made events for nesting, unions and gaps.

data/small_1chip.xplane.pb: a jitted ``tanh(x @ y) + 1`` on (1024, 1024)
bfloat16, three times, each as ``bench.dispatch`` then ``bench.wait``
then a 10 ms ``bench.sleep``, traced with the JAX profiler.

data/allreduce_4chip.xplane.pb: on four v5e chips, ``psum(2 x)`` of a
float32 vector of 4M elements sharded over the chips, three times, each as
``bench.dispatch``, ``bench.wait`` and a 5 ms ``bench.sleep``.
"""
import types

import pytest

from benchkit import ROOT

from bench import trace_reduce

DATA = ROOT / "bench/tests/data"


def _reduce(name):
    from jax.profiler import ProfileData

    return trace_reduce.reduce_xspace(ProfileData.from_file(str(DATA / name)))


@pytest.fixture(scope="module")
def small():
    return _reduce("small_1chip.xplane.pb")


def test_all_reduce_time_on_four_chips():
    s = _reduce("allreduce_4chip.xplane.pb")
    assert s["chips"] == 4
    labels = {k.split("[")[-1] for k in s["ops"]}
    assert labels == {"all-reduce]", "fusion]"}
    seconds, calls = trace_reduce.op_seconds(
        s, lambda t: trace_reduce.op_label(t).endswith("[all-reduce]"))
    # two of the three calls fall in the window (the clocks differ by ~1 ms,
    # as in the one-chip trace); each moves 4 MiB in some tens of us
    assert calls == 2
    assert 20e-6 < seconds / calls < 500e-6
    assert s["breakdown"]["device_ops"][0][0].endswith("[all-reduce]")
    assert [g[0] for g in s["breakdown"]["idle_gaps"][:3]] == ["bench.sleep"] * 3


def test_window_busy_and_idle_share(small):
    assert small["chips"] == 1
    # the window spans the three dispatch/wait/sleep rounds: > 30 ms of sleep
    assert 0.030 < small["window_s"] < 0.060
    # ~16 us matrix products: the chip is idle almost all the window.  The
    # trace puts the first product 1.2 ms before the first annotation (the
    # host and device clocks differ by about a millisecond), so two of the
    # three fall inside the window
    assert 30e-6 < small["busy_s"] < 40e-6
    idle = 1 - small["busy_s"] / small["window_s"]
    assert 0.99 < idle < 1.0


def test_named_op_time(small):
    fusion = trace_reduce.op_seconds(small, lambda t: t.startswith("%fusion "))
    assert fusion is not None
    seconds, calls = fusion
    assert calls == 2
    assert 30e-6 < seconds < 40e-6
    assert seconds <= small["busy_s"] + 1e-12
    assert trace_reduce.op_seconds(small, lambda t: "all-reduce" in t) is None


def test_breakdown(small):
    ops = small["breakdown"]["device_ops"]
    assert ops[0][0] == "%fusion [fusion]"
    assert all(isinstance(s, float) for _, s in ops)
    gaps = small["breakdown"]["idle_gaps"]
    assert len(gaps) <= 10
    # the three longest gaps are the host's sleeps
    assert [g[0] for g in gaps[:3]] == ["bench.sleep"] * 3
    assert all(0.009 < g[1] < 0.02 for g in gaps[:3])
    assert gaps == sorted(gaps, key=lambda g: -g[1])


def _pd(device_lines, host):
    def ev(s, d, name):
        return types.SimpleNamespace(start_ns=s, duration_ns=d, name=name)

    def plane(name, lines):
        return types.SimpleNamespace(name=name, lines=[
            types.SimpleNamespace(name=ln, events=[ev(*e) for e in evs])
            for ln, evs in lines])

    planes = [plane(f"/device:TPU:{i}", [("XLA Ops", evs)])
              for i, evs in enumerate(device_lines)]
    planes.append(plane("/host:CPU", [("python3", host)]))
    return types.SimpleNamespace(planes=planes)


def test_nested_self_time_all_reduce_and_chip_average():
    loop = "%while.1 = (f32[8]{0}) while(f32[8]{0} %x), body=%b"
    inner = "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %y), kind=kLoop"
    ar = "%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %g), to_apply=%add"
    ars = "%all-reduce-start.4 = f32[8]{0} all-reduce-start(f32[8]{0} %g)"
    chip0 = [(100, 50, loop), (110, 20, inner), (200, 30, ar)]
    chip1 = [(100, 40, loop), (300, 10, ars)]
    host = [(90, 300, "bench.window"), (150, 50, "bench.feed")]
    s = trace_reduce.reduce_xspace(_pd([chip0, chip1], host))
    assert s["chips"] == 2
    assert s["window_s"] == pytest.approx(300e-9)
    # busy unions: chip0 80 ns (nested loop counted once), chip1 50 ns
    assert s["busy_s"] == pytest.approx(65e-9)
    # the loop's self time leaves out what its body covers
    assert s["ops"]["%while.1 [while]"]["s"] == pytest.approx((30 + 40) / 2 * 1e-9)
    assert s["ops"]["%fusion.2 [fusion]"]["s"] == pytest.approx(20 / 2 * 1e-9)
    got = trace_reduce.op_seconds(s, lambda t: trace_reduce.op_label(t).split("[")[-1]
                                  .startswith("all-reduce"))
    assert got == (pytest.approx((30 + 10) / 2 * 1e-9), 1.0)
    # chip 0 idles 90..100, 150..200 and 230..390; each gap is labelled by
    # the innermost annotation open at its middle
    assert s["breakdown"]["idle_gaps"] == [
        ["bench.window", pytest.approx(160e-9)],
        ["bench.feed", pytest.approx(50e-9)],
        ["bench.window", pytest.approx(10e-9)]]


def test_window_clips_operations():
    chip = [(0, 100, "%fusion.1 = f32[2]{0} fusion()"),
            (150, 100, "%fusion.2 = f32[2]{0} fusion()")]
    s = trace_reduce.reduce_xspace(_pd([chip], [(50, 150, "bench.step")]))
    assert s["window_s"] == pytest.approx(150e-9)
    assert s["busy_s"] == pytest.approx((50 + 50) * 1e-9)


def test_no_annotation_or_device_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce_xspace(_pd([[(0, 1, "%a = f32[] add()")]], []))
    with pytest.raises(ValueError):
        trace_reduce.reduce_xspace(_pd([], [(0, 10, "bench.x")]))


def test_host_spans_inside_window():
    ev = [{"ph": "B", "name": "decode", "sid": 1, "ts": 1.0},
          {"ph": "E", "name": "decode", "sid": 1, "ts": 1.1},
          {"ph": "B", "name": "prefill_chunk", "sid": 2, "ts": 1.2},
          {"ph": "E", "name": "prefill_chunk", "sid": 2, "ts": 1.25},
          {"ph": "B", "name": "decode", "sid": 3, "ts": 5.0},
          {"ph": "E", "name": "decode", "sid": 3, "ts": 5.1},
          {"ph": "i", "name": "submit", "ts": 1.0}]
    spans = trace_reduce.host_spans(ev, 0.5, 2.0)
    assert spans["decode"] == [pytest.approx(0.1)]
    assert spans["prefill_chunk"] == [pytest.approx(0.05)]
