"""Plain float32 reference of the served Mamba2 + attention hybrid
(granite-4.0-h), and its fp8 control.

The model as the configuration file states it, under the catalog's own
keys. Every layer is ``x + residual_multiplier * mixer(rmsnorm(x))``,
then ``x + residual_multiplier * mlp(rmsnorm(x))``, with RMSNorm of unit
scale (eps ``rms_norm_eps``) and a SiLU-gated MLP of
``shared_intermediate_size``. ``layer_types`` names each layer's mixer:

- ``mamba``: the published Mamba2 recurrence, token by token (a
  sequential ``lax.scan``, not the chunked form the program uses).
  Projections z, x, B, C, dt (no bias); a depthwise causal conv of width
  ``mamba_d_conv`` with bias over x, B and C, then SiLU;
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` and
  ``y_t = S_t C_t + D x_t``; then RMSNorm of ``y * silu(z)`` over
  ``mamba_n_groups`` groups of channels, and ``out_proj``.
- ``attention``: causal GQA with no position embedding
  (``position_embedding_type: "nope"``), scores scaled by
  ``attention_multiplier``.

Embeddings are multiplied by ``embedding_multiplier``; the final RMSNorm
feeds the tied head (the embedding table), whose logits are divided by
``logits_scaling``. Every matmul runs in float32 at the highest
precision, and the recurrence's state is float32. It imports nothing of
the program.

Weights are drawn here from the seed, by the program's random-key layout
(tenant ``t`` from ``fold_in(PRNGKey(seed), t)``; per tenant
``split(key, 8)``: the embedding from key 0 (truncated normal times
0.02), the layers from ``split(key 4, layers)``, which holds where the
layer pattern is whole periods; per layer ``split(key, 2)`` into the
mixer's key and the MLP's, ``split(., 3)`` for up, down, gate. An
attention mixer splits its key in 4 for q, k, v, o. A Mamba2 mixer
splits it in 8: z, x, B, C, dt from keys 0-4, the conv weights of x, B,
C from keys 5-7 (normal times 0.1), and ``out_proj`` from key 2 again,
as the program draws it; conv biases and ``dt_bias`` zero, ``D`` one,
``A_log = log(linspace(1, 16, heads))``. Each matrix is a truncated
normal on [-2, 2] times ``1/sqrt(fan_in)``, cast to the served dtype;
``A_log``, ``D`` and ``dt_bias`` stay float32). The reference takes no
array from the program: if the program's weights were not the seed's,
the comparison fails. The weight draw, the fp8 round trip and the
matmul are ``reference/lm.py``'s.

``errors`` reads, for each served token, the larger of two distances:
how far below the reference's best logit it lies (``reference/lm.py``'s
gap), and how far the logit the program served it with lies from the
reference's logit of it. With random weights this model's greedy token
is, by a wide margin, the token it was fed (the tied head meets the
embedding times 12), so the gap alone reads 0 whatever the layers
compute; the served logit carries every layer's arithmetic.
``control_errors`` reads the same with the reference computed in fp8
(e4m3, per-row scales on activations, per-output-channel scales on
weights, in every matmul) in the program's place: at each position the
token fp8 puts first, with its fp8 logit.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.lm import HIGHEST, _matrix, _mm, _tenant_keys

#: rows (sequences x positions) per block of the reference
BLOCK_TOKENS = 8192
#: query rows per block of the attention scores
QUERY_BLOCK = 512


class Sizes:
    def __init__(self, c: Dict[str, Any]):
        self.d = int(c["hidden_size"])
        self.heads = int(c["num_attention_heads"])
        self.kv_heads = int(c["num_key_value_heads"])
        self.head_dim = self.d // self.heads
        self.ff = int(c["shared_intermediate_size"])
        self.types = tuple(c["layer_types"])
        self.layers = int(c["num_hidden_layers"])
        if len(self.types) != self.layers:
            raise ValueError(f"{len(self.types)} layer_types for "
                             f"{self.layers} layers")
        self.vocab = int(c["vocab_size"])
        self.eps = float(c["rms_norm_eps"])
        self.m_heads = int(c["mamba_n_heads"])
        self.m_head_dim = int(c["mamba_d_head"])
        self.m_state = int(c["mamba_d_state"])
        self.m_groups = int(c["mamba_n_groups"])
        self.m_conv = int(c["mamba_d_conv"])
        self.d_inner = int(c["mamba_expand"]) * self.d
        if self.m_heads * self.m_head_dim != self.d_inner:
            raise ValueError("mamba_n_heads x mamba_d_head != d_inner")
        if self.m_groups != 1:
            raise ValueError("the program's layout draws one group of B, C")
        if c["position_embedding_type"] != "nope":
            raise ValueError("the reference has no position embedding")
        self.attn_scale = float(c["attention_multiplier"])
        self.embed_mult = float(c["embedding_multiplier"])
        self.res_mult = float(c["residual_multiplier"])
        self.logits_scaling = float(c["logits_scaling"])
        self.dtype = jnp.dtype(c["dtype"])

    def key(self):
        return tuple(sorted((k, str(v)) for k, v in vars(self).items()))


# ------------------------------------------------------------------ weights
def _layer_keys(seed, tenant, layer, z):
    lk = jax.random.split(_tenant_keys(seed, tenant)[4], z.layers)[layer]
    return jax.random.split(lk, 2)


@functools.partial(jax.jit, static_argnums=(2,))
def _draw_embed(seed, tenant, skey):
    z = _SIZES[skey]
    return _matrix(_tenant_keys(seed, tenant)[0], z.vocab, z.d, z.dtype,
                   scale=0.02)


def _mlp_weights(key, z):
    m = jax.random.split(key, 3)
    return {"up": _matrix(m[0], z.d, z.ff, z.dtype),
            "down": _matrix(m[1], z.ff, z.d, z.dtype),
            "gate": _matrix(m[2], z.d, z.ff, z.dtype)}


@functools.partial(jax.jit, static_argnums=(3,))
def _draw_attention(seed, tenant, layer, skey):
    z = _SIZES[skey]
    ka, km = _layer_keys(seed, tenant, layer, z)
    a = jax.random.split(ka, 4)
    hq, hkv = z.heads * z.head_dim, z.kv_heads * z.head_dim
    return {"wq": _matrix(a[0], z.d, hq, z.dtype),
            "wk": _matrix(a[1], z.d, hkv, z.dtype),
            "wv": _matrix(a[2], z.d, hkv, z.dtype),
            "wo": _matrix(a[3], hq, z.d, z.dtype),
            **_mlp_weights(km, z)}


@functools.partial(jax.jit, static_argnums=(3,))
def _draw_mamba(seed, tenant, layer, skey):
    z = _SIZES[skey]
    ka, km = _layer_keys(seed, tenant, layer, z)
    k = jax.random.split(ka, 8)
    H, N, W, C = z.m_heads, z.m_state, z.m_conv, z.d_inner

    def conv(key, width):
        return (jax.random.normal(key, (W, width)) * 0.1).astype(z.dtype)

    return {"wz": _matrix(k[0], z.d, C, z.dtype),
            "wx": _matrix(k[1], z.d, C, z.dtype),
            "wB": _matrix(k[2], z.d, N, z.dtype),
            "wC": _matrix(k[3], z.d, N, z.dtype),
            "wdt": _matrix(k[4], z.d, H, z.dtype),
            "conv_x": conv(k[5], C), "conv_B": conv(k[6], N),
            "conv_C": conv(k[7], N),
            "conv_x_b": jnp.zeros((C,), z.dtype),
            "conv_B_b": jnp.zeros((N,), z.dtype),
            "conv_C_b": jnp.zeros((N,), z.dtype),
            "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
            "D": jnp.ones((H,), jnp.float32),
            "dt_bias": jnp.zeros((H,), jnp.float32),
            "out_proj": _matrix(k[2], C, z.d, z.dtype),
            **_mlp_weights(km, z)}


#: jit keys the programs by a hashable tuple; the sizes behind it
_SIZES: Dict[Tuple, Sizes] = {}


def _register(z: Sizes) -> Tuple:
    _SIZES[z.key()] = z
    return z.key()


# -------------------------------------------------------------------- maths
def _rmsnorm(x, eps, groups: int = 1):
    shape = x.shape
    x = x.reshape(*shape[:-1], groups, shape[-1] // groups)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x.reshape(shape)


def _mlp(p, x, z, fp8):
    h = _rmsnorm(x, z.eps)
    y = jax.nn.silu(_mm(h, p["gate"], fp8)) * _mm(h, p["up"], fp8)
    return x + z.res_mult * _mm(y, p["down"], fp8)


def _causal_conv(u, w, b):
    """Depthwise causal conv over time: u (B, S, C), w (W, C); output t
    is ``sum_j w[j] u[t - W + 1 + j] + b``."""
    W = w.shape[0]
    S = u.shape[1]
    pad = jnp.pad(u, ((0, 0), (W - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    out = sum(pad[:, j:j + S, :] * w[j] for j in range(W))
    return out + b.astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _mamba_layer(p, x, skey, fp8):
    z = _SIZES[skey]
    B, S, _ = x.shape
    H, P = z.m_heads, z.m_head_dim
    h = _rmsnorm(x, z.eps)
    gate = _mm(h, p["wz"], fp8)
    xs = jax.nn.silu(_causal_conv(_mm(h, p["wx"], fp8), p["conv_x"],
                                  p["conv_x_b"]))
    Bm = jax.nn.silu(_causal_conv(_mm(h, p["wB"], fp8), p["conv_B"],
                                  p["conv_B_b"]))
    Cm = jax.nn.silu(_causal_conv(_mm(h, p["wC"], fp8), p["conv_C"],
                                  p["conv_C_b"]))
    dt = jax.nn.softplus(_mm(h, p["wdt"], fp8) + p["dt_bias"])   # (B, S, H)
    A = -jnp.exp(p["A_log"])
    xh = xs.reshape(B, S, H, P)

    def step(state, inp):
        dt_t, x_t, b_t, c_t = inp            # (B,H) (B,H,P) (B,N) (B,N)
        state = (jnp.exp(dt_t * A)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        y = jnp.einsum("bhpn,bn->bhp", state, c_t, precision=HIGHEST)
        return state, y

    state0 = jnp.zeros((B, H, P, z.m_state), jnp.float32)
    time_major = (dt.transpose(1, 0, 2), xh.transpose(1, 0, 2, 3),
                  Bm.transpose(1, 0, 2), Cm.transpose(1, 0, 2))
    _, y = jax.lax.scan(step, state0, time_major)
    y = y.transpose(1, 0, 2, 3) + p["D"][:, None] * xh
    y = y.reshape(B, S, z.d_inner) * jax.nn.silu(gate)
    y = _rmsnorm(y, z.eps, z.m_groups)
    x = x + z.res_mult * _mm(y, p["out_proj"], fp8)
    return _mlp(p, x, z, fp8)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _attention_layer(p, x, skey, fp8):
    z = _SIZES[skey]
    B, S, _ = x.shape
    h = _rmsnorm(x, z.eps)

    def heads(y, n):
        return y.reshape(B, S, n, z.head_dim).transpose(0, 2, 1, 3)

    q = heads(_mm(h, p["wq"], fp8), z.heads)
    rep = z.heads // z.kv_heads
    k = jnp.repeat(heads(_mm(h, p["wk"], fp8), z.kv_heads), rep, axis=1)
    v = jnp.repeat(heads(_mm(h, p["wv"], fp8), z.kv_heads), rep, axis=1)
    outs = []
    for q0 in range(0, S, QUERY_BLOCK):
        qb = q[:, :, q0:q0 + QUERY_BLOCK]
        s = jnp.einsum("bhqd,bhkd->bhqk", qb, k, precision=HIGHEST)
        s = s * z.attn_scale
        rows = q0 + jnp.arange(qb.shape[2])
        s = jnp.where(rows[:, None] >= jnp.arange(S)[None, :], s, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1),
                               v, precision=HIGHEST))
    o = jnp.concatenate(outs, axis=2)
    o = o.transpose(0, 2, 1, 3).reshape(B, S, z.heads * z.head_dim)
    x = x + z.res_mult * _mm(o, p["wo"], fp8)
    return _mlp(p, x, z, fp8)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, rows, embed, skey, fp8):
    z = _SIZES[skey]
    h = _rmsnorm(x.reshape(-1, z.d)[rows], z.eps)
    return _mm(h, embed.T, fp8) / z.logits_scaling


# ------------------------------------------------------------------ driver
def _logits(config: Dict[str, Any], seed: int,
            seqs: Sequence[Tuple[int, List[int], List[int]]],
            fp8: bool) -> List[np.ndarray]:
    """Per sequence, the logits (n, vocab) at the positions that produced
    its served tokens: prompt + served[:-1] goes in, and position
    ``len(prompt) - 1 + j`` predicts served token ``j``."""
    z = Sizes(config)
    skey = _register(z)
    S = max(len(p) + len(t) - 1 for _, p, t in seqs)
    S = -(-S // 128) * 128
    per_block = max(1, BLOCK_TOKENS // S)
    # a fixed number of rows per block: one compile of the head
    n_rows = per_block * max(len(t) for _, _, t in seqs)
    out: List[np.ndarray] = [None] * len(seqs)
    by_tenant: Dict[int, List[int]] = {}
    for i, (tenant, _, _) in enumerate(seqs):
        by_tenant.setdefault(tenant, []).append(i)
    for tenant, idx in sorted(by_tenant.items()):
        embed = _draw_embed(seed, tenant, skey)
        for b in range(0, len(idx), per_block):
            block = idx[b:b + per_block]
            toks = np.zeros((per_block, S), np.int32)
            rows = []
            for j, i in enumerate(block):
                _, prompt, served = seqs[i]
                full = list(prompt) + list(served[:-1])
                toks[j, :len(full)] = full
                rows.extend(j * S + len(prompt) - 1 + t
                            for t in range(len(served)))
            x = (jnp.take(embed, jnp.asarray(toks), axis=0)
                 .astype(jnp.float32) * z.embed_mult)
            for layer, kind in enumerate(z.types):
                if kind == "mamba":
                    x = _mamba_layer(_draw_mamba(seed, tenant, layer, skey),
                                     x, skey, fp8)
                elif kind == "attention":
                    x = _attention_layer(
                        _draw_attention(seed, tenant, layer, skey), x, skey,
                        fp8)
                else:
                    raise ValueError(f"layer type {kind!r}")
            padded = rows + [0] * (n_rows - len(rows))
            logits = np.asarray(_head(x, jnp.asarray(padded, jnp.int32),
                                      embed, skey, fp8))
            at = 0
            for i in block:
                n = len(seqs[i][2])
                out[i] = logits[at:at + n]
                at += n
        del embed
    return out


def _errors(ref, picked, values) -> List[float]:
    """Per position: the larger of how far below its best the reference
    puts the picked token, and how far the logit it was picked with lies
    from the reference's logit of it."""
    at = np.arange(len(picked))
    mine = ref[at, picked]
    return np.maximum(ref.max(axis=-1) - mine,
                      np.abs(np.asarray(values, np.float64) - mine)).tolist()


def errors(config, seed, seqs, values) -> np.ndarray:
    """Each served token's error against the reference (``_errors``);
    ``values[i]`` holds the logits the program served sequence ``i``'s
    tokens with."""
    ref = _logits(config, seed, seqs, fp8=False)
    out: List[float] = []
    for (_, _, served), lg, v in zip(seqs, ref, values):
        out.extend(_errors(lg, np.asarray(served), v))
    return np.asarray(out, np.float64)


def widest_error(config, seed, seqs, values) -> float:
    """The widest error over every served token of ``seqs``; inf for none
    or for a token served without its logit (a run that finished nothing,
    or whose tokens the engine did not compute, is not correct)."""
    if not seqs or any(v is None or len(v) != len(t)
                       for (_, _, t), v in zip(seqs, values)):
        return float("inf")
    return float(errors(config, seed, seqs, values).max())


def control_errors(config, seed, seqs) -> np.ndarray:
    """The same errors with the reference in fp8 in the program's place:
    at each position of the same sequences, the token fp8 puts first,
    with its fp8 logit."""
    ref = _logits(config, seed, seqs, fp8=False)
    low = _logits(config, seed, seqs, fp8=True)
    out: List[float] = []
    for r, c in zip(ref, low):
        out.extend(_errors(r, c.argmax(axis=-1), c.max(axis=-1)))
    return np.asarray(out, np.float64)
