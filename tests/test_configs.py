"""Assigned-architecture configs must match the pool table EXACTLY."""
import pytest

from repro.configs import all_arch_names, get_config, get_smoke_config

# (L, d_model, H, kv, d_ff, vocab) per the assignment
ASSIGNED = {
    "granite-moe-3b-a800m": (32, 1536, 24, 8, 512, 49155),
    "internvl2-2b": (24, 2048, 16, 8, 8192, 92553),
    "mamba2-2.7b": (64, 2560, 0, 0, 0, 50280),
    "seamless-m4t-large-v2": (24, 1024, 16, 16, 8192, 256206),
    "minicpm3-4b": (62, 2560, 40, 40, 6400, 73448),
    "mixtral-8x22b": (56, 6144, 48, 8, 16384, 32768),
    "zamba2-7b": (81, 3584, 32, 32, 14336, 32000),
    "granite-3-8b": (40, 4096, 32, 8, 12800, 49155),
    "llama3-8b": (32, 4096, 32, 8, 14336, 128256),
    "phi3-medium-14b": (40, 5120, 40, 10, 17920, 100352),
    "deepseek-v2-lite": (27, 2048, 16, 16, 10944, 102400),
}


@pytest.mark.parametrize("arch", list(ASSIGNED))
def test_exact_assigned_numbers(arch):
    cfg = get_config(arch)
    l, d, h, kv, ff, v = ASSIGNED[arch]
    assert cfg.num_layers == l
    assert cfg.d_model == d
    assert cfg.num_heads == h
    assert cfg.num_kv_heads == kv
    assert cfg.d_ff == ff
    assert cfg.vocab_size == v
    assert cfg.citation


def test_all_ten_present():
    # the ten assigned architectures and DeepSeek-V2-Lite
    assert len(all_arch_names()) == 11
    assert set(all_arch_names()) == set(ASSIGNED)


def test_family_specifics():
    assert get_config("granite-moe-3b-a800m").num_experts == 40
    assert get_config("granite-moe-3b-a800m").experts_per_token == 8
    assert get_config("mixtral-8x22b").num_experts == 8
    assert get_config("mixtral-8x22b").experts_per_token == 2
    assert get_config("mixtral-8x22b").sliding_window == 4096
    assert get_config("mamba2-2.7b").ssm_state == 128
    assert get_config("mamba2-2.7b").attn_type == "none"
    assert get_config("zamba2-7b").ssm_state == 64
    assert get_config("zamba2-7b").hybrid_period == 6
    assert get_config("minicpm3-4b").attn_type == "mla"
    assert get_config("seamless-m4t-large-v2").encoder_layers == 24
    assert get_config("internvl2-2b").frontend == "vision"
    assert get_config("seamless-m4t-large-v2").frontend == "audio"
    ds = get_config("deepseek-v2-lite")
    assert (ds.router_experts, ds.num_experts, ds.expert_offset) == (64, 16, 0)
    assert (ds.experts_per_token, ds.expert_d_ff, ds.shared_experts) == \
        (6, 1408, 2)
    assert ds.first_dense_layers == 1 and not ds.moe_renormalize
    assert ds.moe_gate_scale == 1.0
    assert (ds.q_lora_rank, ds.kv_lora_rank, ds.qk_nope_dim, ds.qk_rope_dim,
            ds.v_head_dim) == (0, 512, 128, 64, 128)
    assert (ds.yarn_factor, ds.yarn_original_max_pos, ds.yarn_beta_fast,
            ds.yarn_beta_slow, ds.yarn_mscale, ds.yarn_mscale_all_dim) == \
        (40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    assert ds.norm_eps == 1e-6 and ds.mla_absorb


@pytest.mark.parametrize("arch", list(ASSIGNED))
def test_smoke_configs_reduced(arch):
    """Smoke variants must honor the reduction limits."""
    cfg = get_smoke_config(arch)
    assert cfg.num_layers <= 5
    assert cfg.d_model <= 512
    assert cfg.num_experts <= 4
    assert cfg.family == get_config(arch).family


@pytest.mark.parametrize("arch", list(ASSIGNED))
def test_param_counts_sane(arch):
    """Analytic parameter counts are within 2x of the model-card scale."""
    expected_b = {
        "granite-moe-3b-a800m": 3.3e9, "internvl2-2b": 1.9e9,
        "mamba2-2.7b": 2.7e9, "seamless-m4t-large-v2": 2.3e9,
        "minicpm3-4b": 4.0e9, "mixtral-8x22b": 141e9, "zamba2-7b": 7.5e9,
        "granite-3-8b": 8.1e9, "llama3-8b": 8.0e9,
        "phi3-medium-14b": 14e9, "deepseek-v2-lite": 15.7e9,
    }[arch]
    got = get_config(arch).param_count()
    assert 0.5 * expected_b < got < 2.0 * expected_b, got
