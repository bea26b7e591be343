"""Launch-layer tests runnable on the single host device: input_specs
shapes, eligibility rules, microbatch math equivalence, analytic roofline
sanity. (Full-mesh lowering is exercised by repro.launch.dryrun in its own
process — it needs the 512-device XLA flag.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config, get_shape, smoke_variant
from repro.launch.roofline import analytic_collectives, collective_bytes
from repro.launch.steps import eligible, input_specs
from repro.models import build_model
from repro.training.optimizer import adamw_init, adamw_update, lr_schedule


class TestInputSpecs:
    def test_train_shape(self):
        cfg = get_config("granite-3-8b")
        s = input_specs(cfg, get_shape("train_4k"))
        assert s["tokens"].shape == (256, 4096)
        assert s["labels"].shape == (256, 4096)
        assert "prefix_embeds" not in s

    def test_vlm_prefix(self):
        cfg = get_config("paligemma-3b")
        s = input_specs(cfg, get_shape("prefill_32k"))
        assert s["prefix_embeds"].shape == (32, 256, 1152)

    def test_decode_shape(self):
        cfg = get_config("rwkv6-1.6b")
        s = input_specs(cfg, get_shape("decode_32k"))
        assert s["token"].shape == (128,)
        assert s["lengths"].shape == (128,)

    def test_eligibility(self):
        ok, _ = eligible(get_config("rwkv6-1.6b"), "long_500k")
        assert ok
        ok, why = eligible(get_config("qwen2-7b"), "long_500k")
        assert not ok and "full-attention" in why
        assert eligible(get_config("gemma3-27b"), "long_500k")[0]  # sliding window


def test_microbatch_equivalence(rng_key):
    """k-microbatched accumulated gradients == full-batch gradients."""
    cfg = dataclasses.replace(smoke_variant(get_config("stablelm-1.6b")), dtype="float32")
    m = build_model(cfg, remat="none")
    params = m.init(rng_key)
    B, S, k = 4, 16, 2
    toks = jax.random.randint(rng_key, (B, S), 0, cfg.vocab_size)
    labels = jnp.roll(toks, -1, axis=1)

    def loss_fn(p, t, l):
        return m.forward_train(p, t, l)[0]

    full_loss, full_grads = jax.value_and_grad(loss_fn)(params, toks, labels)

    mb_loss = 0.0
    mb_grads = jax.tree.map(jnp.zeros_like, params)
    for i in range(k):
        sl = slice(i * B // k, (i + 1) * B // k)
        l, g = jax.value_and_grad(loss_fn)(params, toks[sl], labels[sl])
        mb_loss += l / k
        mb_grads = jax.tree.map(lambda a, b: a + b / k, mb_grads, g)

    np.testing.assert_allclose(float(mb_loss), float(full_loss), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(mb_grads), jax.tree.leaves(full_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_analytic_collectives_policies():
    cfg = get_config("granite-3-8b")
    train = get_shape("train_4k")
    decode = get_shape("decode_32k")

    fsdp = analytic_collectives(cfg, train, policy="fsdp", tp_acts=True)
    tp = analytic_collectives(cfg, train, policy="tp", tp_acts=True)
    repl = analytic_collectives(cfg, decode, policy="replicate", tp_acts=False)

    assert fsdp["weight_ag"] > 0 and tp["weight_ag"] == 0
    assert fsdp["grad_ar"] == tp["grad_ar"] > 0  # grads sync regardless
    assert repl["total"] == 0.0  # replicated decode: no collectives
    pod2 = analytic_collectives(cfg, train, policy="fsdp", tp_acts=True, pods=2)
    assert pod2["pod_ar"] > 0 and pod2["total"] > fsdp["total"]


class TestCompileCache:
    """The persistent compile cache: JAX_COMPILATION_CACHE_DIR wins where
    it is set; otherwise one fixed directory inside the checkout."""

    @pytest.fixture(autouse=True)
    def _restore(self):
        was = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", was)

    def test_env_var_honoured(self, monkeypatch, tmp_path):
        from repro.launch import compile_cache

        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        # JAX reads the variable itself; the code sets no other directory
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_fixed_path_in_checkout(self, monkeypatch):
        import pathlib

        from repro.launch import compile_cache

        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        repo = pathlib.Path(__file__).resolve().parents[1]
        want = str(repo / ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert compile_cache.enable_compile_cache() == want  # every run


class TestDeviceKinds:
    def test_v5e_kind_maps_to_v5e(self):
        from repro.launch.roofline import check_device_hardware, hardware_for_device

        assert hardware_for_device("TPU v5 lite") == "v5e"
        check_device_hardware("TPU v5 lite", "v5e")

    def test_unknown_kind_raises(self):
        from repro.launch.roofline import hardware_for_device

        with pytest.raises(ValueError, match="unknown device kind"):
            hardware_for_device("TPU v9 imaginary")

    def test_spec_that_does_not_match_device_raises(self):
        from repro.launch.roofline import check_device_hardware

        with pytest.raises(ValueError, match="does not describe"):
            check_device_hardware("TPU v5 lite", "v5e_half")

    def test_cpu_has_no_roofline(self):
        from repro.launch.roofline import check_device_hardware, hardware_for_device

        assert hardware_for_device("cpu") is None
        check_device_hardware("cpu", "v5e")  # functional runs only
