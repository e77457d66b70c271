"""deepseek-v2-236b-a21b [moe] — 60L d_model=5120, multi-head latent
attention (128 heads, q_lora 1536, kv_lora 512, nope 128 + rope 64, v 128,
YaRN x40 over 4096 positions); layer 0 dense (d_ff=12288), layers 1-59 MoE:
160 routed experts of width 1536, top-6 by group-limited greedy routing (8
groups, best 3), weights x16 unnormalized, 2 shared experts; vocab=102400,
untied.  [hf:deepseek-ai/DeepSeek-V2]"""
from .base import ArchConfig, LayerKind, Yarn

YARN = Yarn(factor=40.0, original_max_position=4096, beta_fast=32.0,
            beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)


def config() -> ArchConfig:
    return ArchConfig(
        name="deepseek-v2-236b-a21b", family="moe",
        n_layers=60, d_model=5120, n_heads=128, n_kv=128, d_ff=12288,
        vocab=102_400, pattern=(LayerKind("mla", ffn="moe"),),
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, rope_theta=10_000.0,
        yarn=YARN, first_k_dense=1,
        n_experts=160, top_k=6, moe_dff=1536, norm_topk=False,
        moe_dispatch="held", n_shared_experts=2,
        n_group=8, topk_group=3,
        routed_scaling=16.0, fsdp=True, tie_embeddings=False,
        max_seq=163_840, sub_quadratic=False)


def smoke_config() -> ArchConfig:
    return ArchConfig(
        name="deepseek-v2-smoke", family="moe",
        n_layers=3, d_model=64, n_heads=4, n_kv=4, d_ff=128,
        vocab=256, pattern=(LayerKind("mla", ffn="moe"),),
        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_theta=10_000.0,
        yarn=Yarn(factor=4.0, original_max_position=32, beta_fast=32.0,
                  beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
        first_k_dense=1, n_experts=16, top_k=3, moe_dff=32,
        norm_topk=False, moe_dispatch="held", n_shared_experts=2,
        n_group=4, topk_group=2,
        routed_scaling=16.0, tie_embeddings=False, max_seq=128,
        sub_quadratic=False)
