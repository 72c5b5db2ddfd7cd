"""GLM-5-family decoder LM (``model_type`` ``glm_moe_dsa``): DeepSeek-V3's
block (``models/deepseek_v3.py``: latent attention, leading dense layers,
sigmoid-routed experts beside a shared expert over a held range, the MTP module
as the drafter of a serving step) with a LEARNED SPARSE SELECTION inside every
attention, the MTP module's too (DeepSeek Sparse Attention, arXiv 2512.02556
section 2.1). Beside the latent row, each layer caches the indexer's key of a
token; a query scores every earlier position of its sequence and the softmax
runs over the ``index_topk`` best alone:

    q_I[t, j] = c_Q[t] W_Iq          Hi heads j of di, the first dr numbers of each rotated at t
    k_I[t]    = layer_norm(h[t] W_Ik)            [di], its first dr numbers rotated at t: the cache's ``index`` row
    w[t, j]   = (h[t] W_Iw)[j] Hi^-1/2 di^-1/2
    I[t, s]   = sum_j w[t, j] relu(q_I[t, j] . k_I[s])        s <= t, float32
    S_t       = the index_topk positions of largest I[t, .] (all of them while t < index_topk; of equal scores the lower)
    attention = the latent attention of models/latent.py with the softmax over s in S_t only

Nothing of the body, the expert layer or the MTP programs is copied: the
parameters are ``xing4._group_shapes``' with the indexer's five arrays a layer
(``idx_*``, there because this configuration's ``index_topk`` is not 0), the
projections ``xing4._indexer``, the scores and the exact choice
``ops/sparse_index.py``, the two attention paths and the second cached row
``models/latent.py::_sparse_attention`` (on a TPU a chunk's attention is
``ops/latent_flash.py::attend_selected`` and a decode or verify window reads
its slots' live blocks through ``ops/index_paged.py`` and
``ops/latent_paged.py``, the selection a mask in both); the entry points are
``deepseek_v3``'s, run with this configuration. No YaRN (``rope_factor`` 1: the
plain table at ``rope_theta``), no group stage in the router (``n_group`` 1).

A token leaves TWO rows of different widths in every layer (``CacheLayout``
with two arrays under one block table): what ships a block as one stacked
payload (export, import, the tier) is refused where the engine is made.
"""

from __future__ import annotations

from dataclasses import dataclass, replace

from ray_tpu.models import deepseek_v3, latent
from ray_tpu.models.deepseek_v3 import DeepseekV3Config
from ray_tpu.models.interface import AttentionPath


@dataclass(frozen=True)
class GlmDsaConfig(DeepseekV3Config):
    """``DeepseekV3Config`` with the indexer; the defaults are GLM-5's
    published widths with every expert held."""

    vocab_size: int = 154880
    dim: int = 6144
    n_layers: int = 78
    n_dense_layers: int = 3
    n_heads: int = 64
    q_lora_rank: int = 2048
    qk_nope_head_dim: int = 192
    v_head_dim: int = 256
    mlp_hidden: int = 12288
    n_group: int = 1
    topk_group: int = 1
    rope_theta: float = 1000000.0
    rope_factor: float = 1.0
    norm_eps: float = 1e-5
    #: the indexer: heads, their width, and the positions a query attends over
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048

    @staticmethod
    def tiny(**overrides) -> "GlmDsaConfig":
        """CI-sized: 1 dense + 2 expert layers and the MTP module, 8 experts, 2
        a token, an indexer of 3 heads of 16 that keeps 8 positions a query."""
        base = dict(
            vocab_size=256, dim=64, n_layers=3, n_dense_layers=1, n_heads=4,
            q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=24, mlp_hidden=96, moe_hidden=32, n_routed_experts=8,
            held_experts=(0, 8), moe_top_k=2, max_seq_len=64,
            index_n_heads=3, index_head_dim=16, index_topk=8,
        )
        base.update(overrides)
        return GlmDsaConfig(**base)


def _attention_path(cfg: GlmDsaConfig, window: int, cache, backend=None) -> AttentionPath:
    """Every window selects. A decode or verify window, by what will run:
    ``latent.sparse_paged`` where the paged kernels serve
    (``latent.sparse_paged_serves``: a TPU, bf16, both arrays stored in whole
    tiles, window x heads within the kernel's query rows): each real slot's
    LIVE blocks of both arrays and no other, the index keys scored a wave at a
    time, the latent rows attended under the selection as a mask;
    ``latent.sparse`` elsewhere: the index keys at the table's width for every
    slot of the bucket, the chosen latent rows gathered by token. A chunk
    reads both arrays up to its rung (``latent.index_rungs``), scores and
    selects there, and attends under the selection as a mask over all of it, by what will run:
    ``latent.sparse_flash`` where the kernel serves
    (``latent.selected_serves``: a TPU, bf16, whole tiles: the EXPANDED form,
    K and V expanded in VMEM from the key tiles up to the chunk's end alone,
    the scores never in HBM), ``latent.sparse_masked`` elsewhere (the
    absorbed form's materialised softmax over the rung, XLA's)."""
    if latent.sparse_paged_serves(cfg, window, cache, backend=backend):
        return AttentionPath("latent.sparse_paged", "blocks")
    if latent.absorbs(cfg, window):
        return AttentionPath("latent.sparse", "table")
    if latent.selected_serves(cfg, window, cache, backend=backend):
        return AttentionPath("latent.sparse_flash", "live")
    return AttentionPath("latent.sparse_masked", "table")


MODEL = replace(
    deepseek_v3.MODEL,
    name="glm_moe_dsa",
    attention_path=_attention_path,
    selection=lambda cfg: cfg.index_topk,
)
