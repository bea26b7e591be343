"""Decoder-only LM assembled from a ModelConfig.

The layer stack runs as ``lax.scan`` over the pattern's smallest repeating
unit (dense: unit=1; llama4: [dense, moe]; zamba2: [5x mamba2, shared-attn];
gemma3: [5x local, global]; granite-4.0-h: [5x mamba2, attn, 4x mamba2],
each with its MLP) so that full-scale dry-runs lower to compact HLO
— 81 layers become one scan over 13 units plus a short unrolled remainder.

Zamba2's shared attention block is the one weight-sharing case: its params
live OUTSIDE the scanned (stacked) pytree and are closed over, so every
application reuses the same weights — exactly the paper's semantics.

Three entry points (all pure):
    forward_train   tokens -> (loss, metrics)
    forward_prefill tokens -> (last-token logits, caches)
    forward_decode  token  -> (logits, new caches)

The decode step reads every attention layer's cache in place and writes
the new token's keys and values after the layer stack (``decode_step``
then ``write_kv``), so no layer's cache passes through the scan.

``forward_prefill`` and ``forward_decode`` also serve one tenant straight
out of an R-tenant stacked tree (``core.tenancy``) given its index: the
leaves outside the layer stack are picked on their tenant axis, and the
scan over the layer-major stack picks the tenant's row of each layer, so
the tenant's stack is never gathered whole.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp

from repro.config import AttentionKind, BlockKind, Modality, ModelConfig
from repro.core.tenancy import LAYER_MAJOR_KEY, take_tenant
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


_ATTN_KINDS = (BlockKind.ATTN_MLP, BlockKind.ATTN_MOE, BlockKind.HYBRID_SHARED_ATTN)


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
    if kind == BlockKind.MAMBA2_MLP:
        return {
            "norm1": layers.rmsnorm_init(cfg.d_model, dtype),
            "mamba": ssm.mamba2_init(keys[0], cfg, dtype),
            "norm2": layers.rmsnorm_init(cfg.d_model, dtype),
            "mlp": layers.mlp_init(keys[1], cfg.d_model, cfg.d_ff, cfg.mlp_gated, dtype),
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
    if kind in _ATTN_KINDS:
        return attention.init_cache(cfg, attn_kind, batch, seq_len, dtype)
    if kind in (BlockKind.MAMBA2, BlockKind.MAMBA2_MLP):
        return ssm.init_cache(cfg, batch, dtype)
    if kind == BlockKind.RWKV6:
        return rwkv.init_cache(cfg, batch, dtype)
    raise ValueError(kind)


# ----------------------------------------------------------------- block apply
def _residual(y: jax.Array, cfg: ModelConfig) -> jax.Array:
    """A residual branch's output, times the model's multiplier."""
    if cfg.residual_multiplier == 1.0:
        return y
    return y * cfg.residual_multiplier


def _attention(
    params: Params,
    h: jax.Array,
    attn_kind: AttentionKind,
    cfg: ModelConfig,
    mode: str,
    cache: Optional[Cache],
    lengths: Optional[jax.Array],
) -> Tuple[jax.Array, Optional[Cache]]:
    if mode == "train":
        return attention.attn_forward(params, h, cfg, attn_kind), None
    if mode == "prefill":
        return attention.attn_prefill_with_cache(params, h, cfg, attn_kind, cache)
    if mode == "prefill_continue":
        return attention.attn_prefill_continue(
            params, h, cfg, attn_kind, cache, lengths)
    return attention.attn_decode(params, h, cfg, attn_kind, cache, lengths)


def _mamba2(
    params: Params,
    h: jax.Array,
    cfg: ModelConfig,
    mode: str,
    cache: Optional[Cache],
) -> Tuple[jax.Array, Optional[Cache]]:
    if mode == "decode":
        return ssm.mamba2_decode(params, h, cfg, cache)
    if mode == "prefill_continue":
        return ssm.mamba2_forward(
            params, h, cfg, return_cache=True, init_cache_state=cache)
    y, new_cache = ssm.mamba2_forward(
        params, h, cfg, return_cache=(mode == "prefill"))
    return y, (None if mode == "train" else new_cache)


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
    """Returns (x, new_cache, aux_loss).

    A mixer (attention or Mamba2) then an MLP or MoE, each
    ``x + residual_multiplier * f(rmsnorm(x))``; the bare Mamba2 and
    RWKV6 blocks have no second branch. The layer kinds' bodies run
    under the named scopes ``attention``, ``mamba2`` and ``mlp``, which
    the profiler's op metadata carries."""
    aux = jnp.zeros((), jnp.float32)

    if kind in _ATTN_KINDS or kind == BlockKind.MAMBA2_MLP:
        h = layers.rmsnorm(params["norm1"], x, cfg.norm_eps)
        if kind == BlockKind.MAMBA2_MLP:
            with jax.named_scope("mamba2"):
                a, new_cache = _mamba2(params["mamba"], h, cfg, mode, cache)
        else:
            with jax.named_scope("attention"):
                a, new_cache = _attention(
                    params["attn"], h, attn_kind, cfg, mode, cache, lengths)
        x = x + _residual(a, cfg)
        h = layers.rmsnorm(params["norm2"], x, cfg.norm_eps)
        if kind == BlockKind.ATTN_MOE:
            y, aux = moe.moe_forward(params["moe"], h, cfg)
        else:
            with jax.named_scope("mlp"):
                y = layers.mlp(params["mlp"], h, cfg.mlp_gated)
        return x + _residual(y, cfg), new_cache, aux

    if kind == BlockKind.MAMBA2:
        h = layers.rmsnorm(params["norm"], x, cfg.norm_eps)
        with jax.named_scope("mamba2"):
            y, new_cache = _mamba2(params["mamba"], h, cfg, mode, cache)
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
        if cfg.embedding_multiplier != 1.0:
            x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
        return constrain(x, "batch", None, None)

    def _logits(self, params: Params, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = x @ params["embed"].T
        else:
            logits = x @ params["lm_head"]
        if cfg.logits_scaling != 1.0:
            logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
        if cfg.logit_softcap > 0.0:
            logits = layers.softcap(logits.astype(jnp.float32), cfg.logit_softcap)
        return constrain(logits, "batch", None, "model")

    # -------------------------------------------------------------- tenants
    @staticmethod
    def _outside_stack(params: Params, tenant: Optional[jax.Array]) -> Params:
        """With a ``tenant`` index, ``params`` is the stacked tree: pick
        the tenant's leaves outside the layer stack, and leave the stack
        layer-major for ``_run_stack`` to pick layer by layer."""
        if tenant is None:
            return params
        rest = take_tenant(
            {k: v for k, v in params.items() if k != LAYER_MAJOR_KEY}, tenant)
        return {**rest, LAYER_MAJOR_KEY: params[LAYER_MAJOR_KEY]}

    # -------------------------------------------------------------- stack walk
    def _run_stack(
        self,
        params: Params,
        x: jax.Array,
        mode: str,
        caches: Optional[Cache],
        lengths: Optional[jax.Array],
        tenant: Optional[jax.Array] = None,
        kv_tenant: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Optional[Cache], jax.Array]:
        """``tenant``: an index into the stack's tenant axis, where
        ``params`` comes from ``_outside_stack``; the caches are the
        tenant's own.

        In decode mode the attention layers read their caches in place
        (``attention.KVRead``): the layer scan carries none of them, and
        their entries of the new caches are the new token's rows.
        ``kv_tenant``: those caches are the R-tenant stacks
        (``core.tenancy``'s layout), and this reads that tenant's rows."""
        cfg = self.cfg
        unit, reps, rem = find_unit(cfg)
        shared = params.get("shared_attn")
        aux_total = jnp.zeros((), jnp.float32)
        in_place = self.kv_entries() if mode == "decode" else set()

        def kv_read(c, layer, layered):
            """An attention entry as (L, T, B, Hkv, D, S) stacks."""
            k, v = (x if layered else x[None] for x in (c["k"], c["v"]))
            if kv_tenant is None:
                k, v = jnp.expand_dims(k, 1), jnp.expand_dims(v, 1)
            return attention.KVRead(k, v, layer,
                                    0 if kv_tenant is None else kv_tenant)

        def unit_body(carry, xs):
            x, aux = carry
            x = constrain(x, "batch", None, None)
            u_params, u_caches, layer = xs
            new_caches = {}
            for p, (kind, ak) in enumerate(unit):
                pkey = f"pos{p}"
                if kind == BlockKind.HYBRID_SHARED_ATTN:
                    bparams = shared
                elif tenant is None:
                    bparams = u_params[pkey]
                else:
                    # one layer of the layer-major stack is tenant-major
                    bparams = take_tenant(u_params[pkey], tenant)
                if ("unit", pkey) in in_place:
                    bcache = kv_read(caches["unit"][pkey], layer, True)
                else:
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
            unit_caches = None if caches is None else {
                k: c for k, c in caches["unit"].items()
                if ("unit", k) not in in_place}
            # shared-attn positions have no stacked params; give scan a dummy leaf
            u_params_xs = dict(params["unit"])
            for p, (kind, _) in enumerate(unit):
                if kind == BlockKind.HYBRID_SHARED_ATTN:
                    u_params_xs[f"pos{p}"] = jnp.zeros((reps,), jnp.int8)  # placeholder

            body = unit_body  # the shared sentinel is handled inside it
            if mode == "train" and self.remat == "block":
                body = jax.checkpoint(body, prevent_cse=False)

            (x, aux_total), new_unit_caches = jax.lax.scan(
                body,
                (x, aux_total),
                (u_params_xs, unit_caches, jnp.arange(reps)),
            )
        else:
            new_unit_caches = None

        # remainder, unrolled
        new_rem_caches = {}
        for j in range(rem):
            kind, ak = unit[j]
            bparams = shared if kind == BlockKind.HYBRID_SHARED_ATTN else params["rem"][f"rem{j}"]
            bcache = None if caches is None else caches["rem"][f"rem{j}"]
            if ("rem", f"rem{j}") in in_place:
                bcache = kv_read(bcache, 0, False)
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
        tenant: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Cache]:
        """Prefill. Returns (last-position logits, caches).

        Fresh sequences: leave ``caches``/``start`` unset. CHUNKED
        continuation: pass the previous chunk's caches and the absolute
        position of this chunk's first token (traced scalar) — one compile
        per chunk length, exact state carry for attention/SSM/RWKV. Not
        supported for sliding-window ring caches (gemma3-style local
        layers raise NotImplementedError).

        ``tenant``: ``params`` is the stacked tree and this prefills that
        tenant; ``caches`` (if given) and the result are the tenant's own.
        """
        cfg = self.cfg
        B, S = tokens.shape
        params = self._outside_stack(params, tenant)
        x = self._embed(params, tokens)
        if cfg.num_prefix_embeddings and prefix_embeds is not None:
            pref = prefix_embeds.astype(x.dtype) @ params["frontend_proj"]
            x = jnp.concatenate([pref, x[:, prefix_embeds.shape[1]:, :]], axis=1)
        if caches is None:
            caches = self.init_caches(B, cache_len)
            x, new_caches, _ = self._run_stack(
                params, x, "prefill", caches, None, tenant)
        else:
            start = jnp.asarray(0 if start is None else start, jnp.int32)
            x, new_caches, _ = self._run_stack(
                params, x, "prefill_continue", caches, start, tenant
            )
        logits = self._logits(params, x[:, -1:, :])
        return logits[:, 0, :], new_caches

    def kv_entries(self) -> Set[Tuple[str, str]]:
        """The cache entries that attention layers own, as ``(group,
        key)``: ``("unit", "pos<p>")`` and ``("rem", "rem<j>")``."""
        unit, _, rem = find_unit(self.cfg)
        return ({("unit", f"pos{p}") for p, (kind, _) in enumerate(unit)
                 if kind in _ATTN_KINDS}
                | {("rem", f"rem{j}") for j in range(rem)
                   if unit[j][0] in _ATTN_KINDS})

    def decode_step(
        self,
        params: Params,
        token: jax.Array,       # (B,) int32 current token
        caches: Cache,
        lengths: jax.Array,     # (B,) tokens already in cache
        tenant: Optional[jax.Array] = None,
        kv_tenant: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Cache]:
        """One decode step that writes no key or value. Returns (logits
        (B, V), new) where ``new`` holds each recurrent layer's new state
        and, in each attention entry (``kv_entries``), the new token's
        rows ``{"k", "v"}: (..., B, Hkv, D)`` for ``write_kv``.

        ``tenant``: as in ``forward_decode``. ``kv_tenant``: the attention
        entries of ``caches`` are the R-tenant stacks, passed whole (not
        mapped by a tenant vmap), and this reads that tenant's rows of
        them in place; the other entries are the tenant's own."""
        params = self._outside_stack(params, tenant)
        x = self._embed(params, token[:, None])
        x, new, _ = self._run_stack(
            params, x, "decode", caches, lengths, tenant, kv_tenant)
        logits = self._logits(params, x)
        return logits[:, 0, :], new

    def write_kv(self, caches: Cache, new: Cache, lengths: jax.Array) -> Cache:
        """``caches`` after ``decode_step``: the new token's rows written
        into each attention entry at each slot's next position, the other
        entries ``new``'s. ``lengths``: (B,), or (R, B) for R-tenant
        stacks."""
        kv = self.kv_entries()
        return {g: {k: attention.write_rows(c, new[g][k], lengths)
                    if (g, k) in kv else new[g][k]
                    for k, c in sub.items()}
                for g, sub in caches.items()}

    def forward_decode(
        self,
        params: Params,
        token: jax.Array,       # (B,) int32 current token
        caches: Cache,
        lengths: jax.Array,     # (B,) tokens already in cache
        tenant: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Cache]:
        """One decode step. Returns (logits (B, V), new caches).

        ``tenant``: ``params`` is the stacked tree and this decodes that
        tenant; ``caches`` and the result are the tenant's own."""
        logits, new = self.decode_step(params, token, caches, lengths, tenant)
        return logits, self.write_kv(caches, new, lengths)


def build_model(cfg: ModelConfig, remat: str = "block") -> Model:
    return Model(cfg, remat=remat)
