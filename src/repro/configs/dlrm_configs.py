"""DLRM configs for the paper's own workloads (Table 3).

S1: WDL [12] on Criteo-Kaggle-shaped data, S2: DFM [24] on Avazu-shaped,
S3: DCN [66] on Criteo-Sponsored-shaped.  Embedding size defaults to the
paper's 512.  ``dlrm-dcnv2`` is DLRM-DCNv2 as MLPerf Training runs it
(mlcommons/training ``recommendation_v2/torchrec_dlrm``; DCN-V2,
arXiv:2008.13535) at its published widths, on one chip's share of the
tables.  These are `family="dlrm"`: the model is embedding tables +
feature interaction + MLP, and ESD drives their sparse input path.
"""
from __future__ import annotations

import dataclasses

__all__ = ["DLRMConfig", "DLRM_CONFIGS"]


@dataclasses.dataclass(frozen=True)
class DLRMConfig:
    name: str
    kind: str                     # wdl | dfm | dcn | dcnv2
    workload: str                 # synthetic workload key (data/synthetic.py)
    embedding_dim: int = 512      # paper default
    n_dense: int = 13
    mlp_dims: tuple[int, ...] = (1024, 512, 256)
    cross_layers: int = 3         # dcn, dcnv2
    family: str = "dlrm"
    # widths of the bottom MLP (dense features -> E) and the top MLP
    # (interaction -> 1), each ending at its output; () is mlp_dims then
    # E, and mlp_dims then 1
    bottom_dims: tuple[int, ...] = ()
    top_dims: tuple[int, ...] = ()
    cross_rank: int = 0           # dcnv2: the low-rank cross's inner width

    def __post_init__(self):
        if self.kind == "dcnv2" and self.cross_rank <= 0:
            raise ValueError(f"{self.name}: dcnv2 needs cross_rank > 0")

    @property
    def bottom_widths(self) -> tuple[int, ...]:
        return self.bottom_dims or (*self.mlp_dims, self.embedding_dim)

    @property
    def top_widths(self) -> tuple[int, ...]:
        return self.top_dims or (*self.mlp_dims, 1)

    @property
    def source(self) -> str:
        return {"wdl": "WDL [12] / Criteo Kaggle [1]",
                "dfm": "DeepFM [24] / Avazu [2]",
                "dcn": "DCN [66] / Criteo Sponsored Search [61]",
                "dcnv2": "DLRM-DCNv2 / MLPerf Training, Criteo 1TB "
                         "multi-hot"}[self.kind]


DLRM_CONFIGS = {
    "wdl-s1": DLRMConfig("wdl-s1", "wdl", "S1"),
    "dfm-s2": DLRMConfig("dfm-s2", "dfm", "S2"),
    "dcn-s3": DLRMConfig("dcn-s3", "dcn", "S3"),
    "wdl-tiny": DLRMConfig("wdl-tiny", "wdl", "tiny", embedding_dim=16,
                           mlp_dims=(64, 32)),
    "dfm-tiny": DLRMConfig("dfm-tiny", "dfm", "tiny", embedding_dim=16,
                           mlp_dims=(64, 32)),
    "dcn-tiny": DLRMConfig("dcn-tiny", "dcn", "tiny", embedding_dim=16,
                           mlp_dims=(64, 32), cross_layers=2),
    # --embedding_dim 128 --dense_arch_layer_sizes 512,256,128
    # --over_arch_layer_sizes 1024,1024,512,256,1 --dcn_num_layers 3
    # --dcn_low_rank_dim 512
    "dlrm-dcnv2": DLRMConfig("dlrm-dcnv2", "dcnv2", "dlrm-dcnv2",
                             embedding_dim=128, bottom_dims=(512, 256, 128),
                             top_dims=(1024, 1024, 512, 256, 1),
                             cross_layers=3, cross_rank=512),
    "dcnv2-tiny": DLRMConfig("dcnv2-tiny", "dcnv2", "dcnv2-tiny",
                             embedding_dim=16, bottom_dims=(32, 16),
                             top_dims=(64, 32, 1), cross_layers=2,
                             cross_rank=8),
}
