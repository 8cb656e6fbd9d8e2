"""Moonlight-16B-A3B: the checkpoint's shape, for restoring it onto chips.

Source: https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json
(``model_type`` ``deepseek_v3``, the block of DeepSeek-V3 and Kimi K2).
27 layers of hidden size 2048; the first is dense (MLP width 11264), the
other 26 hold 64 routed experts of width 1408, 6 per token, and 2 shared
experts.  Attention is multi-head latent attention (MLA) with 16 heads, no
query compression (``q_lora_rank`` null), a 512-wide key/value latent and
head sizes 128 (no rope) + 64 (rope) for queries and keys, 128 for values.
Vocabulary 163840, input and output embeddings untied.  15.96e9 parameters.

This module gives the checkpoint's *shape*, not a forward pass: MDTP runs
no model, it lands weights.  :func:`checkpoint_specs` returns the
parameter tree in the repository's layer-stacked convention, every leaf a
:class:`~repro.models.common.ParamSpec` whose logical axes the sharding
context resolves (``DEFAULT_RULES``: ``expert``, ``qheads``, ``mlp`` and
``vocab`` on the ``model`` axis, the rest replicated).  It is deliberately
not in the architecture registry, whose models all run forward.

The equations each shape stands for (x is a token's [d] activation,
RMSNorm scales are ``ln1``, ``ln2``, ``kv_norm``, ``final_norm``):

* MLA, per layer.  ``q = x @ wq``, [H, 128 + 64], split into ``q_nope`` and
  ``q_rope = RoPE(q[..., 128:])``.  ``[c, k_r] = x @ wkv_a``, [512 + 64]:
  the latent ``c = RMSNorm(c) * kv_norm`` and one rope key
  ``k_rope = RoPE(k_r)`` that all heads share (the decoupled rope key).
  ``[k_nope, v] = c @ wkv_b``, [H, 128 + 128].  Per head
  ``k = [k_nope, k_rope]``, ``o_h = softmax(q_h . k_h / sqrt(192)) v_h``,
  and ``out = sum_h o_h @ wo[h]``.
* Router (MoE layers).  ``s = sigmoid(x @ router)``, [64].  The 6 experts
  are chosen by ``s + router_bias`` (the correction bias steers the choice
  only); their gates are ``g = 2.446 * s_i / sum_chosen s``
  (``norm_topk_prob``, ``routed_scaling_factor``).  ``n_group`` and
  ``topk_group`` are 1: no group-limited routing.
* Experts.  ``FFN(x) = (silu(x @ wg) * (x @ wi)) @ wo``.  The 2 shared
  experts are one FFN of width 2 * 1408 that every token takes; a MoE
  layer's output is ``shared(x) + sum_chosen g_i FFN_i(x)``.
* A layer: ``h = x + MLA(RMSNorm(x))``, ``y = h + F(RMSNorm(h))``, F the
  dense FFN of width 11264 in layer 0 and the MoE above after it; the
  logits are ``RMSNorm(y) @ unembed`` and the input is ``embed[token]``.
"""

from __future__ import annotations

import math
from typing import Optional

import jax

from repro.distributed.context import ShardingCtx, ShardingRules
from repro.models.common import ModelConfig, ParamSpec
from repro.models.layers import mlp_specs, norm_spec
from repro.models.moe import moe_specs
from repro.models.transformer import _stack

SOURCE = ("https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/"
          "config.json")

#: the published ``config.json``, shape keys as the source names them
CONFIG = {
    "attention_bias": False,
    "ep_size": 1,
    "first_k_dense_replace": 1,
    "hidden_act": "silu",
    "hidden_size": 2048,
    "intermediate_size": 11264,
    "kv_lora_rank": 512,
    "max_position_embeddings": 8192,
    "model_type": "deepseek_v3",
    "moe_intermediate_size": 1408,
    "moe_layer_freq": 1,
    "n_group": 1,
    "n_routed_experts": 64,
    "n_shared_experts": 2,
    "norm_topk_prob": True,
    "num_attention_heads": 16,
    "num_experts_per_tok": 6,
    "num_hidden_layers": 27,
    "num_key_value_heads": 16,
    "num_nextn_predict_layers": 0,
    "q_lora_rank": None,
    "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05,
    "rope_theta": 50000,
    "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid",
    "seq_aux": True,
    "tie_word_embeddings": False,
    "topk_group": 1,
    "topk_method": "noaux_tc",
    "v_head_dim": 128,
    "vocab_size": 163840,
}

def reduced() -> dict:
    """The same leaf kinds and placements at CPU-test widths: a dense layer
    and two MoE layers, 8 routed experts (2 on each of 4 devices), heads,
    MLP widths and vocabulary divisible by 4."""
    return dict(CONFIG, hidden_size=128, intermediate_size=384,
                kv_lora_rank=32, moe_intermediate_size=64,
                n_routed_experts=8, num_experts_per_tok=2,
                num_attention_heads=4, num_key_value_heads=4,
                num_hidden_layers=3, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, vocab_size=4096)


def _model_config(cfg: dict) -> ModelConfig:
    """The repository's config for the spec builders it shares (norms,
    SwiGLU MLPs, routed experts); MLA's sizes stay in ``cfg``."""
    return ModelConfig(
        name="moonlight-16b-a3b", family="moe",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["moe_intermediate_size"], vocab_size=cfg["vocab_size"],
        n_experts=cfg["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"],
        tie_embeddings=cfg["tie_word_embeddings"],
        norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"])


def mla_specs(cfg: dict) -> dict:
    """Multi-head latent attention without query compression."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    qk = cfg["qk_nope_head_dim"] + rope
    v = cfg["v_head_dim"]
    return {
        "wq": ParamSpec((d, H, qk), ("attn_in", "qheads", "head_dim"),
                        "normal", 1.0 / math.sqrt(d)),
        "wkv_a": ParamSpec((d, rank + rope), ("attn_in", None), "normal",
                           1.0 / math.sqrt(d)),
        "kv_norm": ParamSpec((rank,), (None,), "ones"),
        "wkv_b": ParamSpec((rank, H, cfg["qk_nope_head_dim"] + v),
                           (None, "qheads", "head_dim"), "normal",
                           1.0 / math.sqrt(rank)),
        "wo": ParamSpec((H, v, d), ("qheads", "head_dim", "attn_out_d"),
                        "normal", 1.0 / math.sqrt(H * v)),
    }


def checkpoint_specs(depth: Optional[int] = None,
                     cfg: Optional[dict] = None) -> dict:
    """The checkpoint's ``ParamSpec`` tree: ``dense`` (the leading dense
    layer, unstacked), ``blocks`` (the MoE layers, stacked on a leading
    ``layers`` axis), ``embed`` [V, d], ``unembed`` [d, V], ``final_norm``.
    ``depth`` counts layers, the dense one included (default: all)."""
    cfg = CONFIG if cfg is None else cfg
    depth = cfg["num_hidden_layers"] if depth is None else depth
    if cfg["first_k_dense_replace"] != 1 or depth < 2:
        raise ValueError("one leading dense layer and at least one MoE "
                         f"layer; depth {depth}")
    m = _model_config(cfg)
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    shared = mlp_specs(m, cfg["n_shared_experts"]
                       * cfg["moe_intermediate_size"])
    moe = {**moe_specs(m), "shared": shared,
           "router_bias": ParamSpec((cfg["n_routed_experts"],), (None,),
                                    "normal", 0.02)}
    dense = {"ln1": norm_spec(m), "attn": mla_specs(cfg), "ln2": norm_spec(m),
             "mlp": mlp_specs(m, cfg["intermediate_size"])}
    block = {"ln1": norm_spec(m), "attn": mla_specs(cfg), "ln2": norm_spec(m),
             "moe": moe}
    return {
        "dense": dense,
        "blocks": _stack(block, depth - 1),
        "embed": ParamSpec((V, d), ("vocab", "embed"), "normal",
                           1.0 / math.sqrt(d)),
        "unembed": ParamSpec((d, V), ("embed", "vocab"), "normal",
                             1.0 / math.sqrt(d)),
        "final_norm": norm_spec(m),
    }


def leaf_table(specs: dict, mesh) -> list[dict]:
    """Each leaf as a checkpoint's leaf table holds it, in the tree's
    order: ``key``, ``shape``, ``dtype`` (float32 for the router's
    correction bias, bfloat16 for the rest), the value distribution
    (``mean``, ``std``: norm scales 1 + 0.02 N(0, 1), weights their init's
    N(0, std)) and ``spec``, the ``PartitionSpec`` entries that ``DEFAULT_RULES`` give
    it on ``mesh`` (a ``Mesh`` or ``AbstractMesh``)."""
    ctx = ShardingCtx(mesh, ShardingRules())
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))[0]
    table = []
    for path, s in flat:
        key = "/".join(str(p.key) for p in path)
        ones = s.init == "ones"
        table.append({"key": key, "shape": list(s.shape),
                      "dtype": ("float32" if key.endswith("router_bias")
                                else "bfloat16"),
                      "mean": 1.0 if ones else 0.0,
                      "std": 0.02 if ones else s.scale,
                      "spec": list(ctx.spec(s.logical, s.shape))})
    return table
