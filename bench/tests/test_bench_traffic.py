"""The benchmark's traffic copy gives the program's arrays, seed for
seed, and its configurations state the program's shapes, with the big
tables scaled to one chip's share."""
import json

import numpy as np
import pytest

from bench import traffic
from bench.tests.tiny import ROOT
from repro.configs import DLRM_CONFIGS
from repro.data.synthetic import WORKLOADS

SEED = 2 ** 31 + 7


def spec(wl):
    return {"sizes": wl.table_sizes, "zipf_a": wl.zipf_a,
            "n_dense": wl.n_dense, "n_groups": wl.n_groups,
            "group_frac": wl.group_frac, "hist_max": wl.hist_max,
            "hist_mean": wl.hist_mean}


@pytest.mark.parametrize("name", ["S1", "S3", "tiny"])
def test_batches_match_program(name):
    wl = WORKLOADS[name]
    ours = traffic.CTRSampler(spec(wl)).batches(SEED, 32)
    prog = wl.stream(SEED, 32)
    for _ in range(3):
        for a, b in zip(next(prog), next(ours), strict=True):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("config,arch", [("wdl-s1", "wdl-s1"),
                                         ("dcn-s3", "dcn-s3")])
def test_configs_state_the_program_jobs(config, arch):
    cfg = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                     .read_text())
    prog = DLRM_CONFIGS[arch]
    assert cfg["kind"] == prog.kind
    assert cfg["embedding_dim"] == prog.embedding_dim
    assert tuple(cfg["mlp_dims"]) == prog.mlp_dims
    if prog.kind == "dcn":
        assert cfg["cross_layers"] == prog.cross_layers
    got = spec(WORKLOADS[prog.workload])
    ours = cfg["tables"]
    assert set(ours) == set(got)
    for k, v in ours.items():
        if k != "sizes":
            assert tuple(v) == tuple(got[k]) if isinstance(v, list) else v == got[k]
    # every field kept, the big ones scaled alike and the rest as they are
    big = max(got["sizes"])
    assert len(ours["sizes"]) == len(got["sizes"])
    for a, b in zip(ours["sizes"], got["sizes"]):
        assert a == b if b < big else a == max(ours["sizes"]) > b
