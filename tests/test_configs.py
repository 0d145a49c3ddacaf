"""Guard the assigned architecture numbers (as transcribed from the task)
and config-system invariants."""
import pytest

from repro.configs import ARCH_IDS, SHAPES, get_config, get_smoke_config
from repro.configs.base import apply_tp_padding, shape_applicable

# (layers, d_model, heads, kv, d_ff, vocab) per the assignment
ASSIGNED = {
    "qwen2.5-32b": (64, 5120, 40, 8, 27648, 152064),
    "internlm2-20b": (48, 6144, 48, 8, 16384, 92544),
    "gemma2-27b": (46, 4608, 32, 16, 36864, 256000),
    "chatglm3-6b": (28, 4096, 32, 2, 13696, 65024),
    "qwen2-moe-a2.7b": (24, 2048, 16, 16, 1408, 151936),
    "deepseek-v3-671b": (61, 7168, 128, 128, 2048, 129280),
    "whisper-large-v3": (32, 1280, 20, 20, 5120, 51866),
    "llama-3.2-vision-90b": (100, 8192, 64, 8, 28672, 128256),
    "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
    "mamba2-130m": (24, 768, 0, 0, 0, 50280),
    "nemotron3-nano-30b-a3b": (52, 2688, 32, 2, 1856, 131072),
}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_assigned_numbers(arch):
    cfg = get_config(arch)
    L, d, h, kv, ff, v = ASSIGNED[arch]
    assert cfg.n_layers == L
    assert cfg.d_model == d
    assert cfg.n_heads == h
    assert cfg.n_kv_heads == kv
    assert (cfg.d_ff == ff or cfg.d_expert == ff)
    assert cfg.vocab_size == v


def test_arch_specials():
    assert get_config("qwen2.5-32b").qkv_bias
    g = get_config("gemma2-27b")
    assert g.attn_softcap == 50.0 and g.final_softcap == 30.0
    assert g.attn_pattern == ("local", "global")
    assert get_config("chatglm3-6b").rope_fraction == 0.5
    q = get_config("qwen2-moe-a2.7b")
    assert q.n_experts == 60 and q.top_k == 4 and q.n_shared_experts == 4
    ds = get_config("deepseek-v3-671b")
    assert ds.use_mla and ds.n_experts == 256 and ds.top_k == 8
    assert ds.kv_lora_rank == 512 and ds.mtp_depth == 1
    w = get_config("whisper-large-v3")
    assert w.is_encoder_decoder and w.encoder_seq == 1500
    lv = get_config("llama-3.2-vision-90b")
    assert lv.cross_attn_period == 5 and lv.n_layers % 5 == 0
    rg = get_config("recurrentgemma-9b")
    assert rg.attn_pattern == ("rglru", "rglru", "local")
    m = get_config("mamba2-130m")
    assert m.ssm_state == 128 and m.attn_pattern == ("ssd",)


def test_param_counts_in_expected_range():
    """Sanity: analytic param counts land near the advertised sizes."""
    from repro.models.model import count_params_analytic

    expect = {"qwen2.5-32b": (28e9, 38e9),
              "internlm2-20b": (17e9, 24e9),
              "gemma2-27b": (22e9, 32e9),
              "chatglm3-6b": (5e9, 8e9),
              "deepseek-v3-671b": (600e9, 720e9),
              "mamba2-130m": (0.10e9, 0.16e9),
              "recurrentgemma-9b": (7e9, 12e9),
              "nemotron3-nano-30b-a3b": (30e9, 33e9)}
    for arch, (lo, hi) in expect.items():
        n = count_params_analytic(get_config(arch))
        assert lo < n < hi, f"{arch}: {n/1e9:.2f}B outside [{lo/1e9},{hi/1e9}]"


def test_moe_active_params():
    from repro.models.model import count_params_analytic

    ds = get_config("deepseek-v3-671b")
    active = count_params_analytic(ds, active_only=True)
    total = count_params_analytic(ds)
    assert active < 0.1 * total          # 37B active of 671B
    assert 25e9 < active < 50e9


@pytest.mark.parametrize("tp", [4, 8, 16])
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_tp_padding_divisibility(arch, tp):
    cfg = apply_tp_padding(get_config(arch), tp)
    if cfg.n_heads:
        assert cfg.n_heads % tp == 0
        assert cfg.n_kv_heads % tp == 0 or cfg.use_mla
    assert cfg.vocab_size % tp == 0
    # padding preserves real dims
    assert cfg.n_heads_real == get_config(arch).n_heads or cfg.n_heads == get_config(arch).n_heads
    assert cfg.vocab_real == get_config(arch).vocab_size


def test_shape_skips_match_design():
    skips = []
    for arch in ARCH_IDS:
        ok, _ = shape_applicable(arch, SHAPES["long_500k"], get_config(arch))
        if not ok:
            skips.append(arch)
    assert "mamba2-130m" not in skips
    assert "recurrentgemma-9b" not in skips
    # every other arch has global attention layers (the hybrid
    # nemotron3-nano among them), so all of them skip the 500k decode
    assert sorted(skips) == sorted(set(ARCH_IDS) - {"mamba2-130m",
                                                   "recurrentgemma-9b"})
