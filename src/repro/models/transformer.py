"""Decoder-only LM assembled from a ModelConfig.

The layer stack runs as ``lax.scan`` over the pattern's smallest repeating
unit (dense: unit=1; llama4: [dense, moe]; zamba2: [5x mamba2, shared-attn];
gemma3: [5x local, global]) so that full-scale dry-runs lower to compact HLO
— 81 layers become one scan over 13 units plus a short unrolled remainder.

Zamba2's shared attention block is the one weight-sharing case: its params
live OUTSIDE the scanned (stacked) pytree and are closed over, so every
application reuses the same weights — exactly the paper's semantics.

Three entry points (all pure):
    forward_train   tokens -> (loss, metrics)
    forward_prefill tokens -> (last-token logits, caches)
    forward_decode  token  -> (logits, new caches)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import AttentionKind, BlockKind, Modality, ModelConfig
from repro.distributed.constraints import constrain
from repro.models import attention, layers, moe, rwkv, ssm

Params = Dict[str, Any]
Cache = Any


# ----------------------------------------------------------------- unit finding
def _extended_pattern(cfg: ModelConfig) -> List[Tuple[BlockKind, AttentionKind]]:
    return [
        (kind, cfg.attention_kind_at(i)) for i, kind in enumerate(cfg.layer_pattern)
    ]


def find_unit(cfg: ModelConfig) -> Tuple[List[Tuple[BlockKind, AttentionKind]], int, int]:
    """Smallest repeating unit of (block kind, attention kind).

    Returns (unit, num_repeats, num_remainder). Remainder layers (pattern
    tail shorter than one unit) are unrolled.
    """
    ext = _extended_pattern(cfg)
    n = len(ext)
    for u in range(1, n + 1):
        unit = ext[:u]
        reps = n // u
        if reps == 0:
            continue
        if all(ext[i] == unit[i % u] for i in range(reps * u)):
            rem = n - reps * u
            if all(ext[reps * u + j] == unit[j] for j in range(rem)):
                return unit, reps, rem
    return ext, 1, 0  # fallback: whole pattern as one unit


# ----------------------------------------------------------------- block init
def _block_init(key: jax.Array, kind: BlockKind, cfg: ModelConfig, dtype) -> Params:
    keys = jax.random.split(key, 2)
    if kind in (BlockKind.ATTN_MLP, BlockKind.HYBRID_SHARED_ATTN):
        return {
            "norm1": layers.rmsnorm_init(cfg.d_model, dtype),
            "attn": attention.attn_init(keys[0], cfg, dtype),
            "norm2": layers.rmsnorm_init(cfg.d_model, dtype),
            "mlp": layers.mlp_init(keys[1], cfg.d_model, cfg.d_ff, cfg.mlp_gated, dtype),
        }
    if kind == BlockKind.ATTN_MOE:
        return {
            "norm1": layers.rmsnorm_init(cfg.d_model, dtype),
            "attn": attention.attn_init(keys[0], cfg, dtype),
            "norm2": layers.rmsnorm_init(cfg.d_model, dtype),
            "moe": moe.moe_init(keys[1], cfg, dtype),
        }
    if kind == BlockKind.MAMBA2:
        return {
            "norm": layers.rmsnorm_init(cfg.d_model, dtype),
            "mamba": ssm.mamba2_init(keys[0], cfg, dtype),
        }
    if kind == BlockKind.RWKV6:
        return rwkv.rwkv6_init(keys[0], cfg, dtype)
    raise ValueError(kind)


def _block_cache(
    kind: BlockKind,
    attn_kind: AttentionKind,
    cfg: ModelConfig,
    batch: int,
    seq_len: int,
    dtype,
) -> Cache:
    if kind in (BlockKind.ATTN_MLP, BlockKind.ATTN_MOE, BlockKind.HYBRID_SHARED_ATTN):
        return attention.init_cache(cfg, attn_kind, batch, seq_len, dtype)
    if kind == BlockKind.MAMBA2:
        return ssm.init_cache(cfg, batch, dtype)
    if kind == BlockKind.RWKV6:
        return rwkv.init_cache(cfg, batch, dtype)
    raise ValueError(kind)


# ----------------------------------------------------------------- block apply
def _apply_block(
    params: Params,
    x: jax.Array,
    kind: BlockKind,
    attn_kind: AttentionKind,
    cfg: ModelConfig,
    mode: str,                      # "train" | "prefill" | "decode"
    cache: Optional[Cache],
    lengths: Optional[jax.Array],
) -> Tuple[jax.Array, Optional[Cache], jax.Array]:
    """Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)

    if kind in (BlockKind.ATTN_MLP, BlockKind.ATTN_MOE, BlockKind.HYBRID_SHARED_ATTN):
        h = layers.rmsnorm(params["norm1"], x, cfg.norm_eps)
        if mode == "train":
            a = attention.attn_forward(params["attn"], h, cfg, attn_kind)
            new_cache = None
        elif mode == "prefill":
            a, new_cache = attention.attn_prefill_with_cache(
                params["attn"], h, cfg, attn_kind, cache
            )
        elif mode == "prefill_continue":
            a, new_cache = attention.attn_prefill_continue(
                params["attn"], h, cfg, attn_kind, cache, lengths
            )
        else:
            a, new_cache = attention.attn_decode(
                params["attn"], h, cfg, attn_kind, cache, lengths
            )
        x = x + a
        h = layers.rmsnorm(params["norm2"], x, cfg.norm_eps)
        if kind == BlockKind.ATTN_MOE:
            y, aux = moe.moe_forward(params["moe"], h, cfg)
        else:
            y = layers.mlp(params["mlp"], h, cfg.mlp_gated)
        return x + y, new_cache, aux

    if kind == BlockKind.MAMBA2:
        h = layers.rmsnorm(params["norm"], x, cfg.norm_eps)
        if mode == "decode":
            y, new_cache = ssm.mamba2_decode(params["mamba"], h, cfg, cache)
        elif mode == "prefill_continue":
            y, new_cache = ssm.mamba2_forward(
                params["mamba"], h, cfg, return_cache=True, init_cache_state=cache
            )
        else:
            y, new_cache = ssm.mamba2_forward(
                params["mamba"], h, cfg, return_cache=(mode == "prefill")
            )
            if mode == "train":
                new_cache = None
        return x + y, new_cache, aux

    if kind == BlockKind.RWKV6:
        if mode == "train":
            dummy = rwkv.init_cache(cfg, x.shape[0], x.dtype)
            y, _ = rwkv.rwkv6_block(params, x, cfg, dummy, "train")
            return y, None, aux
        # rwkv's "prefill" path is already continuation-correct: it honors
        # the incoming wkv/shift state, zero or not.
        rmode = "prefill" if mode == "prefill_continue" else mode
        y, new_cache = rwkv.rwkv6_block(params, x, cfg, cache, rmode)
        return y, new_cache, aux

    raise ValueError(kind)


# ----------------------------------------------------------------- model
@dataclasses.dataclass(frozen=True)
class Model:
    """Bundles a config with pure apply functions (params are external).

    remat: "none" | "block" — "block" wraps the scanned unit body in
    jax.checkpoint for training (activation memory = one residual per layer,
    everything else recomputed in the backward pass).
    """

    cfg: ModelConfig
    remat: str = "block"

    # -------------------------------------------------------------- init
    def init(self, key: jax.Array) -> Params:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        unit, reps, rem = find_unit(cfg)
        keys = jax.random.split(key, 8)

        params: Params = {
            "embed": layers.embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype),
            "final_norm": layers.rmsnorm_init(cfg.d_model, dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = layers.dense_init(
                keys[1], cfg.d_model, cfg.vocab_size, dtype
            )
        if cfg.num_prefix_embeddings:
            fed = cfg.frontend_embed_dim or cfg.d_model
            params["frontend_proj"] = layers.dense_init(keys[2], fed, cfg.d_model, dtype)

        # shared attention block (zamba2): single copy
        if any(k == BlockKind.HYBRID_SHARED_ATTN for k, _ in unit):
            params["shared_attn"] = _block_init(
                keys[3], BlockKind.HYBRID_SHARED_ATTN, cfg, dtype
            )

        # stacked per-unit params, drawn by one vmapped block init (the
        # same values as a loop over reps, in a program 1/reps the size)
        unit_keys = jax.random.split(keys[4], max(reps, 1) * len(unit)).reshape(
            max(reps, 1), len(unit), -1
        )
        unit_params: Dict[str, Any] = {}
        for p, (kind, _) in enumerate(unit):
            if kind == BlockKind.HYBRID_SHARED_ATTN:
                continue  # shared, not stacked
            unit_params[f"pos{p}"] = jax.vmap(
                lambda k, _kind=kind: _block_init(k, _kind, cfg, dtype)
            )(unit_keys[:reps, p])
        params["unit"] = unit_params

        # remainder layers, unrolled
        rem_keys = jax.random.split(keys[5], max(rem, 1))
        rem_params: Dict[str, Any] = {}
        for j in range(rem):
            kind, _ = unit[j]
            if kind == BlockKind.HYBRID_SHARED_ATTN:
                continue
            rem_params[f"rem{j}"] = _block_init(rem_keys[j], kind, cfg, dtype)
        params["rem"] = rem_params
        return params

    # -------------------------------------------------------------- caches
    def init_caches(self, batch: int, seq_len: int, dtype=None) -> Cache:
        cfg = self.cfg
        dtype = dtype or jnp.dtype(cfg.dtype)
        unit, reps, rem = find_unit(cfg)
        unit_caches = {
            f"pos{p}": jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (reps,) + x.shape).copy()
                if reps > 1
                else x[None],
                _block_cache(kind, ak, cfg, batch, seq_len, dtype),
            )
            for p, (kind, ak) in enumerate(unit)
        }
        rem_caches = {
            f"rem{j}": _block_cache(unit[j][0], unit[j][1], cfg, batch, seq_len, dtype)
            for j in range(rem)
        }
        return {"unit": unit_caches, "rem": rem_caches}

    # -------------------------------------------------------------- embedding
    def _embed(self, params: Params, tokens: jax.Array) -> jax.Array:
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0)
        if cfg.scale_embed:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
        return constrain(x, "batch", None, None)

    def _logits(self, params: Params, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = x @ params["embed"].T
        else:
            logits = x @ params["lm_head"]
        if cfg.logit_softcap > 0.0:
            logits = layers.softcap(logits.astype(jnp.float32), cfg.logit_softcap)
        return constrain(logits, "batch", None, "model")

    # -------------------------------------------------------------- stack walk
    def _run_stack(
        self,
        params: Params,
        x: jax.Array,
        mode: str,
        caches: Optional[Cache],
        lengths: Optional[jax.Array],
    ) -> Tuple[jax.Array, Optional[Cache], jax.Array]:
        cfg = self.cfg
        unit, reps, rem = find_unit(cfg)
        shared = params.get("shared_attn")
        aux_total = jnp.zeros((), jnp.float32)

        def unit_body(carry, xs):
            x, aux = carry
            x = constrain(x, "batch", None, None)
            u_params, u_caches = xs
            new_caches = {}
            for p, (kind, ak) in enumerate(unit):
                pkey = f"pos{p}"
                bparams = shared if kind == BlockKind.HYBRID_SHARED_ATTN else u_params[pkey]
                bcache = None if u_caches is None else u_caches[pkey]
                if mode == "train" and self.remat == "block":
                    # per-block remat INSIDE the unit: the unit-level
                    # checkpoint bounds the scan, this bounds the recompute
                    # working set to one block's internals.
                    x, a = jax.checkpoint(
                        lambda bp, xx, _kind=kind, _ak=ak: _apply_block(
                            bp, xx, _kind, _ak, cfg, mode, None, None
                        )[::2],
                        prevent_cse=False,
                    )(bparams, x)
                    nc = None
                else:
                    x, nc, a = _apply_block(
                        bparams, x, kind, ak, cfg, mode, bcache, lengths
                    )
                aux = aux + a
                if u_caches is not None:
                    new_caches[pkey] = nc
            return (x, aux), (new_caches if u_caches is not None else 0)

        # scanned segment
        if reps > 0:
            unit_caches = None if caches is None else caches["unit"]
            # shared-attn positions have no stacked params; give scan a dummy leaf
            u_params_xs = dict(params["unit"])
            for p, (kind, _) in enumerate(unit):
                if kind == BlockKind.HYBRID_SHARED_ATTN:
                    u_params_xs[f"pos{p}"] = jnp.zeros((reps,), jnp.int8)  # placeholder

            def unit_body_wrapped(carry, xs):
                u_params, u_caches = xs
                # restore sentinel -> shared handled inside unit_body
                return unit_body(carry, (u_params, u_caches))

            if mode == "train" and self.remat == "block":
                unit_body_wrapped = jax.checkpoint(
                    unit_body_wrapped, prevent_cse=False
                )

            (x, aux_total), new_unit_caches = jax.lax.scan(
                unit_body_wrapped,
                (x, aux_total),
                (u_params_xs, unit_caches),
            )
        else:
            new_unit_caches = None

        # remainder, unrolled
        new_rem_caches = {}
        for j in range(rem):
            kind, ak = unit[j]
            bparams = shared if kind == BlockKind.HYBRID_SHARED_ATTN else params["rem"][f"rem{j}"]
            bcache = None if caches is None else caches["rem"][f"rem{j}"]
            x, nc, a = _apply_block(bparams, x, kind, ak, cfg, mode, bcache, lengths)
            aux_total = aux_total + a
            if caches is not None:
                new_rem_caches[f"rem{j}"] = nc

        new_caches = (
            None if caches is None else {"unit": new_unit_caches, "rem": new_rem_caches}
        )
        return x, new_caches, aux_total

    # -------------------------------------------------------------- entrypoints
    def forward_train(
        self,
        params: Params,
        tokens: jax.Array,
        labels: jax.Array,
        prefix_embeds: Optional[jax.Array] = None,
        loss_mask: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """tokens/labels: (B, S) (S includes prefix positions for VLM/audio)."""
        cfg = self.cfg
        x = self._embed(params, tokens)
        if cfg.num_prefix_embeddings and prefix_embeds is not None:
            pref = prefix_embeds.astype(x.dtype) @ params["frontend_proj"]
            x = jnp.concatenate([pref, x[:, prefix_embeds.shape[1]:, :]], axis=1)
            pmask = jnp.arange(x.shape[1])[None, :] >= prefix_embeds.shape[1]
            loss_mask = pmask if loss_mask is None else loss_mask * pmask
        x, _, aux = self._run_stack(params, x, "train", None, None)
        logits = self._logits(params, x)
        ce = layers.cross_entropy(logits, labels, loss_mask)
        loss = ce + aux
        return loss, {"ce": ce, "aux": aux, "loss": loss}

    def forward_prefill(
        self,
        params: Params,
        tokens: jax.Array,
        cache_len: int,
        prefix_embeds: Optional[jax.Array] = None,
        caches: Optional[Cache] = None,
        start: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Cache]:
        """Prefill. Returns (last-position logits, caches).

        Fresh sequences: leave ``caches``/``start`` unset. CHUNKED
        continuation: pass the previous chunk's caches and the absolute
        position of this chunk's first token (traced scalar) — one compile
        per chunk length, exact state carry for attention/SSM/RWKV. Not
        supported for sliding-window ring caches (gemma3-style local
        layers raise NotImplementedError).
        """
        cfg = self.cfg
        B, S = tokens.shape
        x = self._embed(params, tokens)
        if cfg.num_prefix_embeddings and prefix_embeds is not None:
            pref = prefix_embeds.astype(x.dtype) @ params["frontend_proj"]
            x = jnp.concatenate([pref, x[:, prefix_embeds.shape[1]:, :]], axis=1)
        if caches is None:
            caches = self.init_caches(B, cache_len)
            x, new_caches, _ = self._run_stack(params, x, "prefill", caches, None)
        else:
            start = jnp.asarray(0 if start is None else start, jnp.int32)
            x, new_caches, _ = self._run_stack(
                params, x, "prefill_continue", caches, start
            )
        logits = self._logits(params, x[:, -1:, :])
        return logits[:, 0, :], new_caches

    def forward_decode(
        self,
        params: Params,
        token: jax.Array,       # (B,) int32 current token
        caches: Cache,
        lengths: jax.Array,     # (B,) tokens already in cache
    ) -> Tuple[jax.Array, Cache]:
        """One decode step. Returns (logits (B, V), new caches)."""
        x = self._embed(params, token[:, None])
        x, new_caches, _ = self._run_stack(params, x, "decode", caches, lengths)
        logits = self._logits(params, x)
        return logits[:, 0, :], new_caches


def build_model(cfg: ModelConfig, remat: str = "block") -> Model:
    return Model(cfg, remat=remat)
