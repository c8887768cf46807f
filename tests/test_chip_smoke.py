"""chip_smoke.py's control flow on the CPU: its phase functions at
wdl-tiny (Pallas kernels in interpret mode), and its refusal to run
without a TPU — so the script keeps working between chip runs."""
import importlib.util
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _readings(capsys):
    return [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith('{"phase"')]


def test_train_phases_agree(smoke, capsys):
    esd = smoke.phase_train(arch="wdl-tiny", steps=4, batch_per_worker=8,
                            esd=True)
    plain = smoke.phase_train(arch="wdl-tiny", steps=4, batch_per_worker=8,
                              esd=False)
    smoke.phase_parity(esd, plain)
    readings = _readings(capsys)
    assert [r["phase"] for r in readings] == ["train", "train", "parity"]
    assert all(m is not None for m in readings[0]["miss_pull"])


@pytest.mark.parametrize("esd,plain,ok", [
    ([0.69, 0.61, 3.0], [0.69, 0.61, 3.1], True),    # past the first steps
    ([0.69, 0.61], [0.69, 0.62], False),             # a wrong update
    ([0.70, 0.61], [0.69, 0.61], False),             # a wrongly routed sample
])
def test_parity_holds_the_first_steps(smoke, esd, plain, ok):
    if ok:
        smoke.phase_parity(esd, plain)
    else:
        with pytest.raises(AssertionError, match="differ"):
            smoke.phase_parity(esd, plain)


def test_serve_phase(smoke, capsys):
    out = smoke.phase_serve(arch="wdl-tiny", qps=20.0, duration=0.5)
    assert out["n_requests"] == out["n_arrivals"] > 0
    assert _readings(capsys)[-1]["phase"] == "serve"


def test_kernel_phase(smoke, capsys):
    smoke.phase_kernels(E=128, V=203, B=12, F=5, interpret=True)
    names = [r["name"] for r in _readings(capsys) if r["phase"] == "kernel"]
    assert names == ["pooled_lookup", "pooled_lookup_block_f",
                     "staged_gather", "pooled_lookup_staged",
                     "pooled_lookup_quant", "gather_rows_pallas",
                     "gather_rows_pallas_ids", "gather_rows_quant_pallas"]


def test_main_refuses_without_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""
