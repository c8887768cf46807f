"""The trace reduction on hand-made planes, and on a small trace
recorded on a TPU v5e (``small.xplane.pb``: after a ``bench.window``
annotation, five calls of a jitted ``f`` of which the device plane kept
the last three, three of ``staged_gather`` and two of
``pooled_lookup_staged``)."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import xplane

FIXTURE = Path(__file__).with_name("small.xplane.pb")


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur, end_ns=start + dur,
              stats=list(stats.items()))


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def planes():
    host = plane("/host:CPU", python=[ev(xplane.MARK, 0, 1)])
    dev0 = plane("/device:TPU:0",
                 XLA_Modules=[ev("jit_a(1)", 100, 300), ev("jit_b(2)", 500, 100),
                              ev("jit_a(1)", 900, 200)],
                 XLA_Ops=[ev("fusion.1", 100, 200), ev("%k.2 = f32[8] custom-call(%a)", 250, 150),
                          ev("fusion.3", 500, 100), ev("fusion.1", 900, 200)])
    dev1 = plane("/device:TPU:1",
                 XLA_Modules=[ev("jit_a(1)", 100, 100)],
                 XLA_Ops=[ev("fusion.1", 100, 100)])
    return [host, dev0, dev1, plane("/device:TPU:0 SparseCore 0")]


def test_reduce_by_hand():
    red = xplane.reduce(planes(), [("decide", 400e-9, 500e-9),
                                   ("advance", 600e-9, 880e-9),
                                   ("load", 0.0, 1e-6, 1)],
                        0.0, window_ns=(0, 1000))
    assert red.n_devices == 2
    assert red.window_s == pytest.approx(1000e-9)
    # device 0 busy 100-400, 500-600, 900-1000 (clipped): 500 ns; device 1: 100
    assert red.busy_s == pytest.approx(300e-9)
    assert red.idle_share() == pytest.approx(0.7)
    assert red.module_calls == {"jit_a": 2, "jit_b": 1}
    assert red.seconds(["jit_a"]) == pytest.approx((300 + 100 + 100) * 1e-9 / 2)
    assert red.kernel_seconds("k") == pytest.approx(75e-9)
    assert red.top_ops(2) == [("fusion", pytest.approx(250e-9)),
                              ("k", pytest.approx(75e-9))]
    # gaps of device 0: 0-100, 400-500, 600-900; a rank-0 span names a
    # gap before the rank-1 "load" that overlaps them all
    assert red.gaps == [("advance", pytest.approx(300e-9)),
                        ("load", pytest.approx(100e-9)),
                        ("decide", pytest.approx(100e-9))]


def test_window_from_mark_and_host_offset():
    ps = planes()
    assert xplane.host_offset(ps, 2.0) == pytest.approx(-2e9)
    red = xplane.reduce(ps[:1] + ps[1:2])
    assert red.window_s == pytest.approx(1e-9)
    with pytest.raises(ValueError):
        xplane.reduce(ps[:1], window_ns=(0, 10))


def test_recorded_tpu_trace():
    ps = xplane.read_planes(FIXTURE)
    start = xplane.host_offset(ps, 0.0)
    red = xplane.reduce(ps, window_ns=(start, start + 1e9))
    assert red.n_devices == 1
    assert red.module_calls["jit_f"] == 3
    assert red.module_calls["jit_staged_gather"] == 3
    assert red.module_calls["jit_pooled_lookup_staged"] == 2
    assert 0 < red.busy_s < red.window_s
    assert red.kernel_seconds("staged_gather") > 0
    assert red.kernel_seconds("pooled_lookup_staged") > 0
