"""granite-4.0-h-micro: the registered published widths, the layer scan's
unit, the smoke variant, and the defaults of the fields it added, which
leave every other model as it was."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.config import BlockKind, get_config, smoke_variant
from repro.models import build_model
from repro.models.transformer import find_unit

ARCH = "granite-4.0-h-micro"


def test_published_widths():
    c = get_config(ARCH)
    assert (c.num_layers, c.d_model, c.vocab_size, c.num_heads,
            c.num_kv_heads, c.head_dim, c.d_ff) == (
        40, 2048, 100352, 32, 8, 64, 8192)
    assert (c.ssm.num_ssm_heads, c.ssm.head_dim, c.ssm.state_dim,
            c.ssm.expand, c.ssm.conv_width, c.ssm.chunk_size,
            c.ssm.norm_groups) == (64, 64, 128, 2, 4, 256, 1)
    assert not c.rope and c.attention_scale == 0.015625
    assert (c.embedding_multiplier, c.residual_multiplier,
            c.logits_scaling, c.norm_eps, c.tie_embeddings) == (
        12.0, 0.22, 8.0, 1e-5, True)
    kinds = [k for k in c.layer_pattern]
    assert kinds.count(BlockKind.MAMBA2_MLP) == 36
    assert [i for i, k in enumerate(kinds) if k == BlockKind.ATTN_MLP] == [
        5, 15, 25, 35]


def test_the_scan_runs_over_four_units_of_ten():
    unit, reps, rem = find_unit(get_config(ARCH))
    assert (len(unit), reps, rem) == (10, 4, 0)
    assert [k for k, _ in unit].index(BlockKind.ATTN_MLP) == 5


def test_param_count_is_the_programs():
    """3,191,396,096 parameters, by the config's count and by the
    program's ``init`` under ``eval_shape`` (nothing is drawn)."""
    cfg = get_config(ARCH)
    params = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert cfg.param_count() == n == 3_191_396_096


def test_smoke_variant_keeps_one_layer_of_each_kind_at_gqa_4_to_1():
    s = smoke_variant(get_config(ARCH))
    assert s.layer_pattern == (BlockKind.MAMBA2_MLP, BlockKind.ATTN_MLP)
    assert s.num_heads // s.num_kv_heads == 4
    assert s.ssm.norm_groups == 1 and not s.rope
    assert s.residual_multiplier == 0.22 and s.logits_scaling == 8.0


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "zamba2-7b",
                                  "rwkv6-1.6b"])
def test_new_fields_default_to_the_old_behaviour(arch):
    c = get_config(arch)
    assert c.rope and c.attention_scale == 0.0
    assert (c.embedding_multiplier, c.residual_multiplier,
            c.logits_scaling) == (1.0, 1.0, 1.0)
    if c.ssm is not None:
        assert c.ssm.norm_groups == 0


def test_mamba2_gated_norm_groups():
    """One group normalises all of d_inner together; the default (0) one
    group per SSM head, zamba2's behaviour."""
    import jax.numpy as jnp

    from repro.models import ssm

    cfg = dataclasses.replace(smoke_variant(get_config(ARCH)),
                              dtype="float32")
    d_inner, H, _, _ = ssm._dims(cfg)
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.normal(size=(2, d_inner)) *
                    np.repeat(rng.uniform(0.5, 4, H), d_inner // H),
                    jnp.float32)
    z = jnp.asarray(rng.normal(size=(2, d_inner)), jnp.float32)
    params = {"norm": jnp.ones((d_inner,), jnp.float32)}
    g = y * jax.nn.silu(z)
    one = ssm._gated_norm(params, y, z, cfg)
    want = g / jnp.sqrt(jnp.mean(g * g, -1, keepdims=True) + cfg.norm_eps)
    np.testing.assert_allclose(one, want, rtol=1e-5, atol=1e-6)
    per_head = ssm._gated_norm(params, y, z, dataclasses.replace(
        cfg, ssm=dataclasses.replace(cfg.ssm, norm_groups=0)))
    gh = g.reshape(2, H, -1)
    want_h = (gh / jnp.sqrt(jnp.mean(gh * gh, -1, keepdims=True)
                            + cfg.norm_eps)).reshape(2, d_inner)
    np.testing.assert_allclose(per_head, want_h, rtol=1e-5, atol=1e-6)
