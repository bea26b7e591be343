"""Compile the main path's kernels and the full-width decode step for a
TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts (block shapes off
the (8, 128) tiling, unaligned dynamic row loads, programs larger than
HBM), so these compiles guard every change at no chip time. Nothing
runs: no result or time is checked here.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and a test module
that described it while being collected would give pytest-xdist workers
different test sets. Where the program's backend check would route to
the jnp reference (this process sees the CPU), the fixture steers
``repro.kernels.ops`` to the Pallas kernels itself.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_sgemm import PAPER_GEMM_SHAPES

HBM_BYTES = 16e9  # one TPU v5e chip
STABLELM = dict(heads=32, head_dim=64)


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        # otherwise the compiler writes its logs outside the checkout
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this environment
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def chip(topo):
    """Shapes on one described chip, with the persistent cache off (an
    entry compiled for an absent chip cannot be read back) and the
    kernel dispatch steered to the compiled Pallas kernels."""
    from jax.experimental.compilation_cache import compilation_cache

    from repro.kernels import ops

    sharding = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_use_pallas", lambda force_pallas: True)
        mp.setattr(ops, "_interpret", lambda: False)

        def shape(s, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(s, dtype, sharding=sharding)

        yield shape
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel"
    return compiled


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", sorted(PAPER_GEMM_SHAPES))
def test_batched_gemm_paper_shapes(chip, name, dtype):
    from repro.kernels import ops

    g, R = PAPER_GEMM_SHAPES[name], 8
    _compile(ops.batched_gemm, chip((R, g.M, g.K), dtype),
             chip((R, g.K, g.N), dtype))


def test_grouped_gemm(chip):
    from repro.kernels import ops
    from repro.kernels.grouped_gemm import make_group_layout

    _, groups, T = make_group_layout(np.array([300, 5, 129, 0]), bm=128)
    _compile(lambda x, w, g: ops.grouped_gemm(x, w, g, bm=128),
             chip((T, 1152), jnp.bfloat16), chip((4, 1152, 128), jnp.bfloat16),
             chip(groups.shape, jnp.int32))


@pytest.mark.parametrize("seq", [24, 512])
def test_flash_attention_stablelm(chip, seq):
    from repro.kernels import ops

    q = chip((1, STABLELM["heads"], seq, STABLELM["head_dim"]), jnp.bfloat16)
    _compile(ops.flash_attention, q, q, q)


def test_decode_attention_tenant_vmapped(chip):
    from repro.kernels import ops

    R, B, H, D, S = 2, 2, STABLELM["heads"], STABLELM["head_dim"], 1024
    _compile(jax.vmap(ops.decode_attention),
             chip((R, B, H, D), jnp.bfloat16),
             chip((R, B, H, S, D), jnp.bfloat16),
             chip((R, B, H, S, D), jnp.bfloat16),
             chip((R, B), jnp.int32))


def test_wkv6_scan_rwkv6_widths(chip):
    from repro.config import get_config
    from repro.kernels import ops

    cfg = get_config("rwkv6-1.6b")
    N = cfg.ssm.head_dim
    BH, T = cfg.d_model // N, 512
    x = chip((BH, T, N), jnp.bfloat16)
    _compile(ops.wkv6_scan, x, x, x, x, chip((BH, N), jnp.bfloat16))


@pytest.mark.parametrize("program", ["decode_all", "prefill"])
def test_stablelm_step_fits_one_chip(chip, program):
    """The served programs of full-width stablelm-1.6b with R=2 tenants
    (bf16, 128-token prompts, 32 new tokens, 2 slots per tenant) compile
    and fit one chip's HBM: arguments, outputs and temporaries together."""
    from repro.config import get_config
    from repro.models import build_model
    from repro.serving.engine import engine_programs

    R, B, prompt, cache_len = 2, 2, 128, 128 + 32 + 8
    model = build_model(get_config("stablelm-1.6b"))
    keys = jax.ShapeDtypeStruct((R, 2), jnp.uint32)
    as_chip = lambda t: jax.tree.map(lambda s: chip(s.shape, s.dtype), t)
    params = as_chip(jax.eval_shape(jax.vmap(model.init), keys))
    progs = engine_programs(model, cache_len)
    if program == "decode_all":
        caches = as_chip(jax.eval_shape(jax.vmap(
            lambda _: model.init_caches(B, cache_len)), jnp.arange(R)))
        args = (params, chip((R, B), jnp.int32), caches,
                chip((R, B), jnp.int32))
    else:
        args = (params, chip((), jnp.int32), chip((1, prompt), jnp.int32))
    compiled = getattr(progs, program).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel"
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 6e9  # both tenants' weights
    print(f"{program}: arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB, "
          f"total {total / 1e9:.2f} GB")
    assert total < HBM_BYTES, f"{program}: {total / 1e9:.2f} GB"
