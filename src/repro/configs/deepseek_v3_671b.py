"""deepseek-v3-671b [moe]: MLA (q-LoRA 1536 / kv-LoRA 512 / rope 64, YaRN
factor 40 over 4096 positions), 1 shared + 256 routed experts top-8
(sigmoid ``noaux_tc`` router: 8 groups, best 4, gates normalised and
scaled by 2.5), 3 leading dense layers (dense d_ff 18432; per-expert d_ff
2048).  MTP head omitted (DESIGN.md §5).  [arXiv:2412.19437]

``ep32_config`` is one chip's share of it served expert-parallel over 32
chips (DeepSeek's EP32 prefill layout): every width as published, 8 of
the 256 routed experts held, the vocabulary split 8 ways, and 7 layers (the
3 dense ones and 4 MoE ones; the rest lie on further pipeline stages)."""
from repro.models.config import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v3-671b", family="moe",
        n_layers=61, d_model=7168, n_heads=128, n_kv_heads=128,
        head_dim=128, d_ff=18432, vocab_size=129280,
        attn_type="mla", q_lora_rank=1536, kv_lora_rank=512,
        rope_head_dim=64, v_head_dim=128,
        n_experts=256, n_shared_experts=1, top_k=8, moe_d_ff=2048,
        first_dense_layers=3, router_type="sigmoid",
        n_group=8, topk_group=4, routed_scaling=2.5,
        rope_theta=10000.0, rope_factor=40.0,
    )


def ep32_config() -> ModelConfig:
    return config().with_(name="deepseek-v3-ep32", n_layers=7,
                          n_experts_held=8, vocab_size=129280 // 8)


def smoke_config() -> ModelConfig:
    return config().with_(
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
        d_ff=128, vocab_size=512, q_lora_rank=32, kv_lora_rank=16,
        rope_head_dim=8, v_head_dim=16, n_experts=8, top_k=2,
        n_group=4, topk_group=2,
        moe_d_ff=32, first_dense_layers=1, name="deepseek-smoke")
