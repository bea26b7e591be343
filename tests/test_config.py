"""Config registry, parameter accounting, smoke-variant bounds."""

import pytest

from repro.config import get_config, get_shape, list_configs, smoke_variant
from repro.config.model import AttentionKind, BlockKind
from repro.configs import ASSIGNED_ARCHS

# (arch, expected total params +-15%, expected active +-15%)
EXPECTED_PARAMS = {
    "granite-moe-1b-a400m": (1.3e9, 0.4e9),
    "granite-3-8b": (8.2e9, 8.2e9),
    "qwen2-7b": (7.6e9, 7.6e9),
    "stablelm-1.6b": (1.6e9, 1.6e9),
    "gemma3-27b": (27e9, 27e9),
    "rwkv6-1.6b": (1.6e9, 1.6e9),
    "llama4-maverick-400b-a17b": (400e9, 17e9),
    "musicgen-large": (2.4e9, 2.4e9),
    "paligemma-3b": (2.5e9, 2.5e9),
    "zamba2-7b": (9.2e9, 11.7e9),  # shared-attn reuse: active FLOP-params > stored
}


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_param_counts_plausible(arch):
    cfg = get_config(arch)
    total, active = EXPECTED_PARAMS[arch]
    assert abs(cfg.param_count() - total) / total < 0.25, cfg.param_count()
    assert abs(cfg.active_param_count() - active) / active < 0.25


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_smoke_variant_bounds(arch):
    s = smoke_variant(get_config(arch))
    assert s.num_layers <= 3
    assert s.d_model <= 512
    if s.moe:
        assert s.moe.num_experts <= 4
    assert s.vocab_size <= 1024


def test_exact_assigned_geometry():
    c = get_config("qwen2-7b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads, c.d_ff,
            c.vocab_size, c.qkv_bias) == (28, 3584, 28, 4, 18944, 152064, True)
    g = get_config("gemma3-27b")
    assert g.sliding_window == 1024 and g.global_every == 6
    assert g.attention_kind_at(0) == AttentionKind.SLIDING
    assert g.attention_kind_at(5) == AttentionKind.FULL
    z = get_config("zamba2-7b")
    assert z.layer_pattern[5] == BlockKind.HYBRID_SHARED_ATTN
    assert sum(1 for b in z.layer_pattern if b == BlockKind.MAMBA2) == 68
    r = get_config("rwkv6-1.6b")
    assert r.num_heads == 0 and r.attention_kind == AttentionKind.NONE


def test_long_500k_eligibility():
    eligible = {a for a in ASSIGNED_ARCHS if get_config(a).is_subquadratic}
    assert eligible == {"rwkv6-1.6b", "zamba2-7b", "gemma3-27b"}


def test_shapes_table():
    assert get_shape("train_4k").kind == "train"
    assert get_shape("decode_32k").is_decode
    assert get_shape("long_500k").global_batch == 1
    with pytest.raises(KeyError):
        get_shape("nope")


def test_unknown_arch_raises():
    with pytest.raises(KeyError):
        get_config("resnet-50")


def test_all_archs_have_sources():
    for a in list_configs():
        assert get_config(a).source, a


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_smoke_name_resolves_to_smoke_variant(arch):
    # the reduced model is reachable only by its explicit "-smoke" name;
    # the plain name stays the published configuration
    assert get_config(arch + "-smoke") == smoke_variant(get_config(arch))
    assert get_config(arch).name == arch
    assert arch + "-smoke" not in list_configs()


def test_smoke_name_of_unknown_arch_raises():
    with pytest.raises(KeyError, match="nope"):
        get_config("nope-smoke")
