"""nemotron3-nano-30b-a3b — hybrid Mamba-2 / GQA / 128-expert MoE.

52 blocks following ``hybrid_override_pattern``: 23 Mamba-2 mixers (M),
23 expert layers (E) and 6 attention mixers (*), each block
``x + mixer(RMSNorm(x))``.  Mamba-2: 64 heads of 64 (an inner width of
4096, not expand x d_model), state 128, 8 groups with the gated norm per
group, conv 4, chunk 128.  Attention: 32 query and 2 KV heads of 128, no
rotary embedding (HF ``modeling_nemotron_h`` applies none), no MLP.
Experts: 128 routed, top-6, relu² and not gated, width 1856; sigmoid
router with a correction bias, normalised top-k weights times 2.5; one
shared relu² expert of width 3712.  Untied embeddings.

[hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 config.json]
"""
from repro.configs.base import ModelConfig, hybrid_pattern

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

CONFIG = ModelConfig(
    name="nemotron3-nano-30b-a3b",
    family="hybrid",
    n_layers=52,
    d_model=2688,
    n_heads=32,
    n_kv_heads=2,
    head_dim=128,
    d_ff=1856,
    vocab_size=131072,
    act="relu2",
    gated_mlp=False,
    norm_eps=1e-5,
    pos_embedding="none",
    attn_pattern=hybrid_pattern(PATTERN),
    n_experts=128,
    top_k=6,
    d_expert=1856,
    n_shared_experts=1,
    d_shared_expert=3712,
    router_type="sigmoid",
    router_aux_free_bias=True,
    routed_scaling_factor=2.5,
    router_aux_coef=0.0,       # balanced by the correction bias alone
    ssm_state=128,
    ssm_nheads=64,
    ssm_headdim=64,
    ssm_ngroups=8,
    ssm_conv=4,
    ssm_chunk=128,
    tie_embeddings=False,
)

SMOKE = CONFIG.scaled(
    n_layers=7, attn_pattern=hybrid_pattern(PATTERN[:7]), d_model=64,
    n_heads=4, n_kv_heads=2, head_dim=16, d_ff=32, d_expert=32,
    d_shared_expert=64, n_experts=16, vocab_size=256, ssm_state=16,
    ssm_nheads=8, ssm_headdim=16, ssm_ngroups=2, ssm_chunk=16,
)
