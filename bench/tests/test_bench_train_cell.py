"""A training cell run on the CPU at a tiny size: the whole run but the
look for a chip.  Sound, it is correct; with the timed path broken
underneath (a step that returns its state unchanged, half of each batch
left out with the mean over the rest) it is not."""
import pytest

from bench import program, run, train_cell
from bench.tests import tiny


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    return run.Cell(tiny.tiny_root(tmp_path_factory.mktemp("b")), "tiny.train")


def test_sound_run_is_correct(cell):
    res = run.run_cell(cell, 2 ** 31 + 3, 1.0, False, tiny.CPU)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert res["metrics"]["train_samples_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"


def test_a_new_seed_compiles_no_new_set_up_program(cell):
    run.run_cell(cell, 2 ** 31 + 11, 1.0, False, tiny.CPU)
    programs = train_cell._state_norms._cache_size()
    run.run_cell(cell, 2 ** 31 + 12, 1.0, False, tiny.CPU)
    assert train_cell._state_norms._cache_size() == programs


def _unchanged(name, lr):
    from repro.optim import Optimizer, get_optimizer

    opt = get_optimizer(name, lr)
    return Optimizer(opt.init, lambda g, s, p: (p, s))


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_broken_step_is_not_correct(cell, fault, monkeypatch):
    from repro.launch import train
    from repro.models import dlrm

    if fault == "state_unchanged":
        monkeypatch.setattr(train, "get_optimizer", _unchanged)
    else:
        bce = dlrm.bce_loss

        def half_batch(params, cfg, sparse, dense, labels):
            h = labels.shape[0] // 2
            return bce(params, cfg, sparse[:h], dense[:h], labels[:h])
        monkeypatch.setattr(dlrm, "bce_loss", half_batch)
    res = run.run_cell(cell, 5, 1.0, False, tiny.CPU)
    assert not res["correct"], res["checks"]


def test_program_train_closure_holds_its_state(cell, monkeypatch):
    """``run_dlrm`` hands back no state: the check reads ``params`` and
    ``opt_state`` from its train closure.  A program change that moves
    them fails here, by name, before it reaches a chip."""
    read = []
    orig = program.train_state

    def spy(train_fn):
        read.append(orig(train_fn))
        return read[-1]
    monkeypatch.setattr(program, "train_state", spy)
    res = run.run_cell(cell, 11, 0.5, False, tiny.CPU)
    assert "run_raised" not in res["checks"], res["checks"]
    assert len(read) == 2
    assert {"embed", "wide", "bottom", "top"} <= set(read[0]["params"])
    assert set(read[0]["opt_state"]) == set(read[0]["params"])


def test_state_missing_from_closure_is_named():
    def build():
        params = {}

        def train_fn(x):
            return params, x
        return train_fn
    with pytest.raises(RuntimeError, match="opt_state"):
        program.train_state(build())
