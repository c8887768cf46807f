"""Device time by scope and host time by span, read from the raw trace:
on the v5e recording (``small.xplane.pb``: five calls of a jitted ``f``,
three of ``staged_gather`` and two of ``pooled_lookup_staged``), on a
hand-built trace with a ``while`` that encloses its body, on one chip
and on several."""
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import scopes, xplane

FIXTURE = Path(__file__).with_name("small.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    planes = xplane.read_planes(FIXTURE)
    start = xplane.host_offset(planes, 0.0)
    return scopes.reduce(scopes.read_space(FIXTURE), (start, start + 1e9))


def _ops(named, hlo):
    return {k: op for k, op in named.ops.items() if k[1] == hlo}


def test_jit_f_ops_fall_under_their_scope(recorded):
    (fusion,) = _ops(recorded, "fusion").values()
    assert fusion.tf_op == "jit(f)/dot_general:"
    assert recorded.scope_s["jit(f)"] == pytest.approx(fusion.seconds)
    assert fusion.seconds > 0
    # the copies around it carry no name stack and belong to no scope
    unowned = sum(op.seconds for op in recorded.ops.values() if not op.tf_op)
    owned = sum(recorded.scope_s[s] for s in (
        "jit(f)", "jit(staged_gather)", "jit(pooled_lookup_staged)"))
    assert unowned > 0
    assert owned + unowned == pytest.approx(recorded.exclusive_s)


def test_pallas_call_falls_under_its_jit(recorded):
    (kernel,) = _ops(recorded, "staged_gather.1").values()
    assert kernel.tf_op == "jit(staged_gather)/pallas_call:"
    assert scopes.scopes_of(kernel.tf_op) == ("jit(staged_gather)",)
    (pad,) = [op for (pid, hlo), op in recorded.ops.items()
              if hlo == "pad.0" and "staged_gather" in op.tf_op
              and "pooled" not in op.tf_op]
    assert recorded.scope_s["jit(staged_gather)"] == pytest.approx(
        kernel.seconds + pad.seconds)


def test_pads_of_two_programs_stay_apart(recorded):
    pads = _ops(recorded, "pad.0")
    assert len(pads) == 2                       # one per program
    assert len({pid for pid, _ in pads}) == 2
    assert {op.tf_op for op in pads.values()} == {
        "jit(staged_gather)/jit(_pad)/pad:",
        "jit(pooled_lookup_staged)/jit(_pad)/pad:"}
    assert recorded.scope_s["jit(_pad)"] == pytest.approx(
        sum(op.seconds for op in pads.values()))


def test_scopes_of_name_stack():
    assert scopes.scopes_of(
        "jit(train_jit)/dlrm.train_step/transpose(jvp(dlrm.forward))/"
        "jit(_where)/select_n:") == (
        "jit(train_jit)", "dlrm.train_step", "dlrm.forward", "jit(_where)")
    assert scopes.scopes_of("fusion") == ()
    assert scopes.scopes_of("") == ()


MAIN = "python"


def hand_built():
    """One chip: a ``while`` (0-100 ns) enclosing two body operations
    (10-40, 50-90), then a backward ``dot_general`` (120-150); the host's
    main line holds the window mark, a ``batch.next`` and a ``record``
    span, and another thread a ``record`` of its own."""
    space = scopes._xspace_class()()
    dev = space.planes.add(name="/device:TPU:0")
    for sid, name in ((1, "tf_op"), (2, "program_id")):
        dev.stat_metadata[sid].id = sid
        dev.stat_metadata[sid].name = name
    cut = "jit(advance)/esd.advance/esd.cache_update/capacity_cut"
    ops = [("while.3", f"{cut}/jit(searchsorted)/while:", 0, 100),
           ("fusion.1", f"{cut}/jit(searchsorted)/while/body/add:", 10, 30),
           ("sort.2", f"{cut}/sort:", 50, 40),
           ("fusion.9", "jit(train_jit)/dlrm.train_step/"
                        "transpose(jvp(dlrm.forward))/dot_general:", 120, 30)]
    line = dev.lines.add(id=1, name=xplane.OPS_LINE, timestamp_ns=1000)
    for mid, (name, tf_op, start, dur) in enumerate(ops, 1):
        md = dev.event_metadata[mid]
        md.id, md.name = mid, f"%{name} = f32[8] op()"
        md.stats.add(metadata_id=1, str_value=tf_op)
        md.stats.add(metadata_id=2, uint64_value=40 + (mid == 4))
        line.events.add(metadata_id=mid, offset_ps=start * 1000,
                        duration_ps=dur * 1000)
    host = space.planes.add(name="/host:CPU")
    for mid, name in enumerate((xplane.MARK, "batch.next", "record",
                                "PjitFunction(advance)"), 1):
        host.event_metadata[mid].id = mid
        host.event_metadata[mid].name = name
    main = host.lines.add(id=1, name=MAIN, timestamp_ns=1000)
    for mid, start, dur in ((1, 0, 1), (2, 5, 20), (4, 10, 5), (3, 30, 40),
                            (2, 140, 40)):
        main.events.add(metadata_id=mid, offset_ps=start * 1000,
                        duration_ps=dur * 1000)
    other = host.lines.add(id=2, name="loader", timestamp_ns=1000)
    other.events.add(metadata_id=3, offset_ps=0, duration_ps=500_000)
    return space


def test_exclusive_time_of_a_nested_while():
    space = hand_built()
    named = scopes.reduce(space, (1000, 1160))
    ns = pytest.approx
    assert named.ops[("40", "while.3")].seconds == ns(30e-9)   # 100 - 30 - 40
    assert named.scope_s["capacity_cut"] == ns(100e-9)
    assert named.scope_s["esd.cache_update"] == ns(100e-9)
    assert named.scope_s["dlrm.forward"] == ns(30e-9)
    # each device nanosecond once: the exclusive sum is the busy time,
    # which the operations' inclusive sum overstates
    red = xplane.reduce(ProfileData.from_serialized_xspace(
        space.SerializeToString()).planes, window_ns=(1000, 1160))
    assert named.exclusive_s == ns(red.busy_s) == ns(130e-9)
    assert sum(red.op_s.values()) == ns(200e-9)
    top = named.scope_s["jit(advance)"] + named.scope_s["jit(train_jit)"]
    assert top <= red.busy_s + 1e-15
    # the window clips: up to 60 ns the while keeps 60 - 30 - 10
    clipped = scopes.reduce(space, (1000, 1060))
    assert clipped.ops[("40", "while.3")].seconds == ns(20e-9)
    assert clipped.exclusive_s == ns(60e-9)


def test_host_seconds_per_span_on_the_main_line():
    space = hand_built()
    named = scopes.reduce(space, (1000, 1160),
                          names={"batch.next", "record"})
    # the loader's record is on another line; the second batch.next is
    # clipped by the window; the jit call inside the first is not ours
    assert named.host_s == {"batch.next": pytest.approx(40e-9),
                            "record": pytest.approx(40e-9)}
    everything = scopes.reduce(space, (1000, 1160)).host_s
    assert {"PjitFunction(advance)", xplane.MARK} <= set(everything)


@pytest.mark.parametrize("chips", [1, 2, 4])
def test_device_seconds_are_the_mean_over_chips(chips):
    """Chip ``i`` runs the same operations ``i + 1`` times slower, so
    every scope reads the one-chip time times the mean of 1..chips."""
    space = hand_built()
    dev = space.planes[0]
    for i in range(1, chips):
        plane = space.planes.add()
        plane.CopyFrom(dev)
        plane.name = f"/device:TPU:{i}"
        for ev in plane.lines[0].events:
            ev.offset_ps *= i + 1
            ev.duration_ps *= i + 1
    window = (1000, 1000 + 160 * chips)
    one = scopes.reduce(hand_built(), window)
    named = scopes.reduce(space, window)
    scale = (chips + 1) / 2
    assert set(named.scope_s) == set(one.scope_s)
    for scope, seconds in one.scope_s.items():
        assert named.scope_s[scope] == pytest.approx(seconds * scale)
    assert named.exclusive_s == pytest.approx(130e-9 * scale)


def test_no_host_seconds_without_the_window_mark():
    space = hand_built()
    host = space.planes[1]
    del host.lines[0].events[0]                 # the bench.window mark
    assert scopes.reduce(space, (1000, 1160)).host_s == {}
