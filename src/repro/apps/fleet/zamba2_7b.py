"""Zamba2-7B's published numbers, the one place the program keeps them.

Source: https://huggingface.co/Zyphra/Zamba2-7B-Instruct/blob/main/config.json
(Zyphra, 2024-10).  Zamba2 interleaves Mamba2 layers with a shared
attention block: 81 layers, the shared block at ``hybrid_layer_ids``
(every 6th after the first), which attends over the concatenation of
the hidden stream and the original embeddings, ``attention_hidden_size``
= 2 x ``hidden_size`` wide, in 32 heads of ``attention_head_dim`` 224.
The Mamba2 layers run 112 heads of ``mamba_headdim`` 64 with a state of
``mamba_d_state`` 64 and 2 groups of B/C, scanned in chunks of
``chunk_size`` 256.

``fleet-zamba2-7b`` (:mod:`.pipeline`) is one hybrid layer of this
model on one chip of a 4-way tensor-parallel deployment, which splits
every head count (and the B/C groups) four ways and keeps every width.
"""

from __future__ import annotations

from typing import Dict, Tuple

from ...configs.base import ModelConfig

__all__ = ["SOURCE", "PUBLISHED", "TENSOR_PARALLEL", "CHIP_SHARE",
           "analytical_stages"]

SOURCE = ("https://huggingface.co/Zyphra/Zamba2-7B-Instruct/blob/main/"
          "config.json")

# the keys of the published config the app reads, as published
PUBLISHED: Dict[str, object] = {
    "hidden_size": 3584,
    "num_hidden_layers": 81,
    "hybrid_layer_ids": (6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    "num_attention_heads": 32,
    "num_key_value_heads": 32,
    "attention_head_dim": 224,
    "attention_hidden_size": 7168,
    "ffn_hidden_size": 14336,
    "num_mem_blocks": 2,
    "n_mamba_heads": 112,
    "mamba_headdim": 64,
    "mamba_d_state": 64,
    "mamba_ngroups": 2,
    "mamba_expand": 2,
    "mamba_d_conv": 4,
    "chunk_size": 256,
    "max_position_embeddings": 4096,
}

# one four-chip v5e host: attention heads, KV heads, Mamba heads and the
# B/C groups split four ways, one share per chip
TENSOR_PARALLEL = 4

# the head counts one chip of that deployment holds
CHIP_SHARE: Dict[str, int] = {
    "q_heads": PUBLISHED["num_attention_heads"] // TENSOR_PARALLEL,
    "kv_heads": PUBLISHED["num_key_value_heads"] // TENSOR_PARALLEL,
    "ssd_heads": PUBLISHED["n_mamba_heads"] // TENSOR_PARALLEL,
    "bc_groups": max(1, PUBLISHED["mamba_ngroups"] // TENSOR_PARALLEL),
}
assert all(PUBLISHED[k] % TENSOR_PARALLEL == 0 for k in (
    "num_attention_heads", "num_key_value_heads", "n_mamba_heads"))


def analytical_stages() -> Tuple[ModelConfig, ModelConfig]:
    """The two stages as the analytical (XLA roofline) tool prices them,
    one layer each at the published widths: the shared attention block
    (a dense block ``attention_hidden_size`` wide, its heads and its
    MLP) and one Mamba2 layer.  No embedding: the stages are layers."""
    p = PUBLISHED
    attn = ModelConfig(
        name="zamba2-7b-shared-attn", family="dense", n_layers=1,
        d_model=p["attention_hidden_size"],
        n_heads=p["num_attention_heads"],
        n_kv_heads=p["num_key_value_heads"],
        head_dim=p["attention_head_dim"], d_ff=p["ffn_hidden_size"],
        vocab=0, source=SOURCE)
    mamba = ModelConfig(
        name="zamba2-7b-mamba2", family="ssm", n_layers=1,
        d_model=p["hidden_size"], vocab=0,
        ssm_state=p["mamba_d_state"], ssm_head_dim=p["mamba_headdim"],
        ssm_expand=p["mamba_expand"], ssm_chunk=p["chunk_size"],
        conv_kernel=p["mamba_d_conv"], source=SOURCE)
    assert mamba.ssm_heads() == p["n_mamba_heads"]
    return attn, mamba
