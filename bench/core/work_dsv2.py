"""Work of one served decode step of a DeepSeek-V2 configuration (latent
attention, leading dense layers, held experts), counted from the
configuration's shapes alone, as ``work.py`` counts a dense decoder's.

* FLOPs: 2 per matmul parameter per token, with the attention computed
  through the latent cache (``kv_b``'s key half applied to the query and
  its value half to the output: as many parameters as ``kv_b``), plus the
  attention itself, 2 * heads * (2 * kv_lora_rank + rope_dim) per token per
  layer for each position of the filled context.  The expert layer counts
  the shared experts, the router, and the held experts at the share of
  the token's ``num_experts_per_tok`` picks that a router spreading evenly
  sends here: k * held / published experts.
* Bytes: every matmul weight once a step at 2 bytes (bfloat16, the dtype
  the step computes in), every held expert included, plus the latent
  cache positions filled so far at 2 bytes.  Norms and activations are
  left out: they are small beside these.
"""
from __future__ import annotations

BF16 = 2


def _held(cfg: dict) -> int:
    return cfg["held_experts"][1] - cfg["held_experts"][0]


def _moe_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def mla_params(cfg: dict) -> int:
    """Matmul parameters of one latent-attention layer."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (d * ql + ql * h * (nope + rope) + d * (kvl + rope)
            + kvl * h * (nope + v) + h * v * d)


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _shared_and_router(cfg: dict) -> int:
    return (cfg["n_shared_experts"] * expert_params(cfg)
            + cfg["hidden_size"] * cfg["source_values"]["n_routed_experts"])


def _dense_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def _head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def step_weight_params(cfg: dict) -> int:
    """Matmul parameters one decode step reads: every layer's attention,
    the dense layers' MLP, each expert layer's shared experts, router and
    held experts, and the head."""
    moe = _shared_and_router(cfg) + _held(cfg) * expert_params(cfg)
    return (cfg["num_hidden_layers"] * mla_params(cfg)
            + cfg["first_k_dense_replace"] * _dense_params(cfg)
            + _moe_layers(cfg) * moe + _head_params(cfg))


def token_matmul_params(cfg: dict) -> float:
    """Matmul parameters one token's forward uses on this chip."""
    routed = (cfg["num_experts_per_tok"] * _held(cfg)
              / cfg["source_values"]["n_routed_experts"])
    moe = _shared_and_router(cfg) + routed * expert_params(cfg)
    return (cfg["num_hidden_layers"] * mla_params(cfg)
            + cfg["first_k_dense_replace"] * _dense_params(cfg)
            + _moe_layers(cfg) * moe + _head_params(cfg))


def decode_flops(cfg: dict, rows: int, context: int) -> float:
    """FLOPs of one decode step of ``rows`` sequences whose cache holds
    ``context`` positions (the new token's included)."""
    attn = (2 * cfg["num_attention_heads"]
            * (2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * context)
    per_token = (2 * token_matmul_params(cfg)
                 + cfg["num_hidden_layers"] * attn)
    return float(rows * per_token)


def decode_bytes(cfg: dict, rows: int, context: int) -> float:
    """Least bytes one decode step moves: the weights once, and the filled
    latent cache of every row."""
    cache = (cfg["num_hidden_layers"] * rows * context
             * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * BF16)
    return float(step_weight_params(cfg) * BF16 + cache)


def decode_least_seconds(cfg: dict, rows: int, context: int,
                         peaks: dict) -> float:
    """The larger of the step's FLOPs over peak FLOP/s and its bytes over
    peak HBM bandwidth."""
    return max(decode_flops(cfg, rows, context) / peaks["bf16_flops"],
               decode_bytes(cfg, rows, context) / peaks["hbm_bytes_per_s"])
