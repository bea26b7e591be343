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

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_sgemm import PAPER_GEMM_SHAPES

HBM_BYTES = 16e9  # one TPU v5e chip
STABLELM = dict(heads=32, head_dim=64)
# the served programs' temporaries at the test's shapes: decode reads the
# layer-major stack in place, and prefill picks its tenant layer by layer
# (a tenant-major tree relaid 5.20 GB and gathered 2.47 GB)
TEMP_BYTES = {"decode_all": 0.5e9, "prefill": 2.47e9}


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
    """The decode kernel under the engine's tenant vmap, as the decode
    step calls it: the stacks passed whole, the tenant index mapped. The
    mapped axis folds into the kernel's rows, so it is one kernel call
    and no loop over tenants."""
    from repro.kernels import ops

    R, B, H, D, S = 2, 2, STABLELM["heads"], STABLELM["head_dim"], 1024

    def step(q, k_new, v_new, k_stack, v_stack, lengths):
        return jax.vmap(
            lambda q, kn, vn, n, t: ops.decode_attention(
                q, kn, vn, k_stack, v_stack, n, 0, t))(
                    q, k_new, v_new, lengths, jnp.arange(R))

    compiled = _compile(step, chip((R, B, H, D), jnp.bfloat16),
                        chip((R, B, H, D), jnp.bfloat16),
                        chip((R, B, H, D), jnp.bfloat16),
                        chip((1, R, B, H, D, S), jnp.bfloat16),
                        chip((1, R, B, H, D, S), jnp.bfloat16),
                        chip((R, B), jnp.int32))
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert " while(" not in text


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
    as_chip = lambda t: jax.tree.map(lambda s: chip(s.shape, s.dtype), t)
    params, caches = _stacked_shapes(model, R, B, cache_len)
    params = as_chip(params)
    progs = engine_programs(model, cache_len)
    if program == "decode_all":
        caches = as_chip(caches)
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
    assert mem.temp_size_in_bytes <= TEMP_BYTES[program], (
        f"{program}: temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB")


#: granite-4.0-h-micro at the rag cell's sizes: 2048-token prompts, a
#: cache of 2048 + 32 + 8 positions; temporaries 0.03 GB (decode_all) and
#: 0.42 GB (prefill), with room to spare
GRANITE_TEMP_BYTES = {"decode_all": 0.1e9, "prefill": 0.6e9}


@pytest.mark.parametrize("program", ["decode_all", "prefill"])
def test_granite_hybrid_step_fits_one_chip(chip, program):
    """The served programs of full-width granite-4.0-h-micro (36 Mamba2 +
    4 attention layers) with R=2 bf16 tenants compile with the Pallas
    attention kernels at GQA 4:1 and leave at least 1 GB of one chip's
    HBM: both tenants' weights (12.77 GB), the caches (conv tails, f32
    SSM state, KV), outputs and temporaries together."""
    from repro.config import get_config
    from repro.models import build_model
    from repro.serving.engine import engine_programs

    R, B, prompt = 2, 2, 2048
    cache_len = prompt + 32 + 8
    model = build_model(get_config("granite-4.0-h-micro"))
    as_chip = lambda t: jax.tree.map(lambda s: chip(s.shape, s.dtype), t)
    params, caches = _stacked_shapes(model, R, B, cache_len)
    params = as_chip(params)
    progs = engine_programs(model, cache_len)
    if program == "decode_all":
        args = (params, chip((R, B), jnp.int32), as_chip(caches),
                chip((R, B), jnp.int32))
    else:
        args = (params, chip((), jnp.int32), chip((1, prompt), jnp.int32))
    compiled = getattr(progs, program).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel"
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 12.7e9  # both tenants' weights
    print(f"{program}: arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB, "
          f"total {total / 1e9:.2f} GB")
    assert total < HBM_BYTES - 1e9, f"{program}: {total / 1e9:.2f} GB"
    assert mem.temp_size_in_bytes <= GRANITE_TEMP_BYTES[program], (
        f"{program}: temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB")


#: (arch, cache length) of the served decode steps: the stablelm cells'
#: caches (chat 512 + 32 + 8, longprompt 2048 + 4 + 8) and the granite
#: rag cell's (2048 + 32 + 8)
IN_PLACE_CASES = {"stablelm-1.6b-552": ("stablelm-1.6b", 552),
                  "stablelm-1.6b-2060": ("stablelm-1.6b", 2060),
                  "granite-4.0-h-micro-2088": ("granite-4.0-h-micro", 2088)}
_HLO_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1,
              "s8": 1, "u8": 1}


def _copied_bytes(text):
    """(name, bytes) of every copy and pad in an HLO module's text."""
    for m in re.finditer(r"%(\S+) = (\w+)\[([\d,]*)\]\S* (copy|copy-start|pad)\(",
                         text):
        dims = [int(d) for d in m.group(3).split(",") if d]
        yield m.group(1), int(np.prod(dims)) * _HLO_BYTES.get(m.group(2), 4)


@pytest.mark.parametrize("case", sorted(IN_PLACE_CASES))
def test_decode_all_reads_and_writes_the_caches_in_place(chip, case):
    """The engine's donated decode step at full width (R=2 tenants of 2
    slots, bf16) for a described v5e: no copy or pad in the compiled
    program yields as many bytes as one layer's K (or V) of one tenant,
    and the K/V caches are aliased to the step's outputs. A kernel that reads
    a layout other than the stored one, a layer sliced out for it, a pad
    of its S or a position written inside the layer scan each copy a
    layer's K and V on every step."""
    from repro.config import get_config
    from repro.models import build_model
    from repro.serving.engine import donate_caches, engine_programs, split_kv

    arch, cache_len = IN_PLACE_CASES[case]
    R, B = 2, 2
    model = build_model(get_config(arch))
    as_chip = lambda t: jax.tree.map(lambda s: chip(s.shape, s.dtype), t)
    params, caches = _stacked_shapes(model, R, B, cache_len)
    kv = frozenset(model.kv_entries())
    decode_all = donate_caches(engine_programs(model, cache_len).decode_all, kv)
    compiled = decode_all.lower(
        as_chip(params), chip((R, B), jnp.int32),
        *split_kv(as_chip(caches), kv), chip((R, B), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel"
    cfg = model.cfg
    layer_kv = B * cfg.num_kv_heads * cfg.head_dim * cache_len * 2
    copies = [(name, n) for name, n in _copied_bytes(text) if n >= layer_kv]
    assert not copies, copies
    kv_bytes = sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(split_kv(caches, kv)[0]))
    assert compiled.memory_analysis().alias_size_in_bytes >= kv_bytes


def _stacked_shapes(model, r, b, cache_len):
    """Shapes of the engine's stacked weights and caches (nothing drawn)."""
    from repro.core.tenancy import broadcast_tenants, init_stacked

    return (jax.eval_shape(
                lambda: init_stacked(model.init, jax.random.PRNGKey(0), r)),
            jax.eval_shape(
                lambda: broadcast_tenants(model.init_caches(b, cache_len), r)))


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "llama4-maverick-400b-a17b",
                                  "zamba2-7b", "rwkv6-1.6b"])
def test_decode_all_moves_no_unit_leaf(arch):
    """Where no TPU compiler can be described, the same guard as the
    temporaries above, on the CPU: the tenant-vmapped decode step's
    jaxpr transposes no leaf of the stacked weights' or caches' layer
    stack (``"unit"``) on its way in or out. A tenant-major stack makes
    the scan relay every leaf to ``[reps, R, ...]`` on every call."""
    from repro.config import get_config
    from repro.models import build_model
    from repro.serving.engine import engine_programs

    R, B, cache_len = 2, 2, 16
    model = build_model(get_config(f"{arch}-smoke"))
    params, caches = _stacked_shapes(model, R, B, cache_len)
    ids = jax.ShapeDtypeStruct((R, B), jnp.int32)
    args = (params, ids, caches, ids)
    decode_all = engine_programs(model, cache_len).decode_all
    (call,) = jax.make_jaxpr(decode_all)(*args).jaxpr.eqns
    body = call.params["jaxpr"].jaxpr
    paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(args)[0]]
    unit = {v for p, v in zip(paths, body.invars)
            if p[0].idx in (0, 2) and p[1].key == "unit"}
    _, out_caches = jax.tree.unflatten(
        jax.tree.structure(jax.eval_shape(decode_all, *args)), body.outvars)
    unit |= set(jax.tree.leaves(out_caches["unit"]))
    assert len(unit) > 2
    moved = [e for e in body.eqns if e.primitive.name == "transpose"
             and unit & set(e.invars + e.outvars)]
    assert not moved, moved[:3]


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                if isinstance(sub, ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from _eqns(sub)


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "granite-4.0-h-micro"])
def test_decode_all_pads_no_cache_and_donates(arch, monkeypatch):
    """Where no TPU compiler can be described, the guard above on the
    CPU: traced with the Pallas decode kernel, ``decode_all``'s jaxpr pads
    nothing as large as one layer's K of one tenant (the kernel reads the
    stored S, 552 here, unpadded), and the engine's decode call donates
    its K/V caches: the stacks a step was given are deleted after it,
    and a recurrent layer's state (granite's Mamba2 layers) is not."""
    from repro.config import get_config
    from repro.kernels import ops
    from repro.models import build_model
    from repro.serving import EngineConfig, InferenceRequest, MultiTenantEngine
    from repro.serving.engine import engine_programs, split_kv

    R, B, cache_len = 2, 2, 552
    model = build_model(get_config(f"{arch}-smoke"))
    params, caches = _stacked_shapes(model, R, B, cache_len)
    ids = jax.ShapeDtypeStruct((R, B), jnp.int32)
    monkeypatch.setattr(ops, "_use_pallas", lambda force_pallas: True)
    monkeypatch.setattr(ops, "_interpret", lambda: True)
    fresh = engine_programs.__wrapped__(model, cache_len)  # traced anew
    jaxpr = jax.make_jaxpr(fresh.decode_all)(params, ids, caches, ids)
    monkeypatch.setattr(ops, "_use_pallas", lambda force_pallas: False)
    cfg = model.cfg
    layer = B * cfg.num_kv_heads * cfg.head_dim * cache_len
    pads = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "pad"
            and e.invars[0].aval.size >= layer]
    assert not pads, pads[:2]

    from repro.core.tenancy import init_stacked
    eng = MultiTenantEngine(
        model, init_stacked(model.init, jax.random.PRNGKey(0), R),
        EngineConfig(num_tenants=R, slots_per_tenant=B, cache_len=32))
    eng.submit(InferenceRequest(tenant_id=1, prompt=[3, 4, 5],
                                max_new_tokens=4))
    eng.step()
    kv, state = split_kv(eng.caches, frozenset(model.kv_entries()))
    eng.step()
    assert all(x.is_deleted() for x in jax.tree.leaves(kv))
    assert not any(x.is_deleted() for x in jax.tree.leaves(state))
    assert not any(x.is_deleted() for x in jax.tree.leaves(eng.caches))
