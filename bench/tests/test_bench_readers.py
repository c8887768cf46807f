"""The reader context of a traced run carries the exclusive device
seconds of each scope (``scope_s``) and the main thread's host seconds
of each of the tracer's spans (``host_phase_s``), read from the capture
before it is removed; the readers of scopes and spans give milliseconds
per train step, and nothing where their scope or span is absent."""
import shutil
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import run, scopes, train_cell, xplane
from bench.tests.tiny import ROOT, TINY

FIXTURE = Path(__file__).with_name("small.xplane.pb")
READERS = {"cache_update_ms.train": ("scope_s", "esd.cache_update"),
           "optimizer_ms.train": ("scope_s", "optim.update"),
           "batch_ms.train": ("host_phase_s", "batch.next"),
           "record_ms.train": ("host_phase_s", "record")}


def _reader(name):
    return ROOT / "bench" / "metrics" / f"{name}.py"


class Tracer:
    """The spans a tracer kept, on the host clock of the capture's mark."""

    t0 = 0.0

    def events(self):
        return [{"name": "PjitFunction(f)", "ts": 0.0, "dur": 1e-3,
                 "thread": "MainThread"},
                {"name": "record", "ts": 2e-3, "dur": 1e-3,
                 "thread": "MainThread"}]


def test_traced_context_carries_scope_and_span_seconds(tmp_path):
    capture = tmp_path / "trace"
    (capture / "plugins" / "profile" / "run").mkdir(parents=True)
    shutil.copy(FIXTURE, capture / "plugins" / "profile" / "run" / FIXTURE.name)
    feed = NS(profiling=str(capture), mark_t=0.0, warmup_steps=0,
              distinct=[])
    ctx = train_cell._reduce(feed, Tracer(), 1.0, TINY, 8, [], [],
                             {"kind": "TPU v5 lite"})
    assert not capture.exists()                     # removed after reading

    start = xplane.host_offset(xplane.read_planes(FIXTURE), 0.0)
    want = scopes.reduce(scopes.read_space(FIXTURE), (start, start + 1e9),
                         names={"PjitFunction(f)", "record"})
    assert ctx["scope_s"] == want.scope_s
    assert ctx["scope_s"]["jit(f)"] > 0
    # only the tracer's own span names, and only those the capture holds
    assert set(ctx["host_phase_s"]) == {"PjitFunction(f)"}
    assert ctx["host_phase_s"] == want.host_s
    assert ctx["reduced"].module_calls["jit_f"] == 3
    # the recording has no train step: every reader finds nothing
    for name in READERS:
        assert run.read_metric(_reader(name), ctx) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_ms_per_train_step(name):
    where, key = READERS[name]
    ctx = {"reduced": NS(module_calls={"jit_train_jit": 4}),
           "scope_s": {}, "host_phase_s": {}}
    assert run.read_metric(_reader(name), ctx) is None
    ctx[where] = {key: 0.068, "other": 1.0}
    assert run.read_metric(_reader(name), ctx) == pytest.approx(17.0)
    ctx["reduced"] = NS(module_calls={})
    assert run.read_metric(_reader(name), ctx) is None
