"""Decoder-only transformer covering the dense, moe, and vlm families.

* params-as-scan-xs: per-layer params are stacked [L, ...] and consumed
  by ``lax.scan`` — with ZeRO/FSDP-sharded weights XLA then all-gathers
  one layer at a time inside the loop (the FSDP pattern), and compile
  time is O(1) in depth;
* remat: the layer body is wrapped in ``jax.checkpoint`` for training;
* activations between layers are sharding-constrained to
  (batch, seq, model) — Megatron-style activation partitioning that
  keeps the scan carry 1/TP of its replicated size;
* vlm (PaliGemma): a stub patch-embedding prefix is concatenated before
  the token embeddings and attended with prefix-LM masking.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..distributed.sharding import constrain
from ..obs import spans
from . import layers as nn
from .config import ModelConfig


def init(cfg: ModelConfig, key: jax.Array) -> Dict:
    k_embed, k_layers, k_final, k_head = jax.random.split(key, 4)

    def init_layer(k):
        ka, km, k1, k2 = jax.random.split(k, 4)
        p = {
            "ln1": nn.init_norm(k1, cfg),
            "attn": nn.init_attention(ka, cfg),
            "ln2": nn.init_norm(k2, cfg),
        }
        if cfg.family == "moe":
            p["moe"] = nn.init_moe(km, cfg)
        else:
            p["mlp"] = nn.init_mlp(km, cfg)
        return p

    params = {
        "embed": nn.init_embed(k_embed, cfg),
        "layers": jax.vmap(init_layer)(jax.random.split(k_layers, cfg.n_layers)),
        "final_norm": nn.init_norm(k_final, cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"table": nn.embed_init(k_head, (cfg.vocab, cfg.d_model), nn.dt(cfg))}
    return params


def _layer_fwd(cfg: ModelConfig, lp: Dict, h: jax.Array, *,
               prefix_len: int, attn_impl: str) -> Tuple[jax.Array, jax.Array]:
    """One decoder layer, full-sequence. Returns (h, aux_loss)."""
    h = constrain(h, "batch", None, "residual")
    attn_in = nn.apply_norm(cfg, lp["ln1"], h)
    h = h + nn.attention_block(
        cfg, lp["attn"], attn_in,
        causal=True, window=cfg.sliding_window,
        prefix_len=prefix_len, attn_impl=attn_impl,
    )
    mlp_in = nn.apply_norm(cfg, lp["ln2"], h)
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "moe":
        out, aux = nn.moe_block(cfg, lp["moe"], mlp_in)
        h = h + out
    else:
        h = h + nn.mlp_block(cfg, lp["mlp"], mlp_in)
    h = constrain(h, "batch", None, "residual")
    return h, aux


def _embed_inputs(cfg: ModelConfig, params: Dict, tokens: jax.Array,
                  patches: Optional[jax.Array]) -> jax.Array:
    x = nn.embed(cfg, params["embed"], tokens)
    if cfg.family == "vlm":
        assert patches is not None, "vlm requires stub patch embeddings"
        x = jnp.concatenate([patches.astype(x.dtype), x], axis=1)
    return x


def forward(cfg: ModelConfig, params: Dict, tokens: jax.Array, *,
            patches: Optional[jax.Array] = None,
            remat: bool = False, attn_impl: str = "auto",
            ) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward. Returns (logits [B, L, V], aux_loss)."""
    x = _embed_inputs(cfg, params, tokens, patches)
    x = constrain(x, "batch", None, "residual")
    prefix = cfg.prefix_len if cfg.family == "vlm" else 0

    body = functools.partial(_layer_fwd, cfg, prefix_len=prefix,
                             attn_impl=attn_impl)

    def scan_body(h, lp):
        h2, aux = body(lp, h)
        return h2, aux

    if remat:
        scan_body = jax.checkpoint(
            scan_body, policy=jax.checkpoint_policies.nothing_saveable
        )
    x, auxs = nn.scan_layers(scan_body, x, params["layers"])
    x = nn.apply_norm(cfg, params["final_norm"], x)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = nn.unembed(cfg, head, x)
    return logits, auxs.sum()


# ---------------------------------------------------------------------------
# KV-cache serving paths
# ---------------------------------------------------------------------------

def cache_size(cfg: ModelConfig, max_len: int) -> int:
    """Sliding-window archs keep a ring buffer of the window only."""
    if cfg.sliding_window is not None:
        return min(max_len, cfg.sliding_window)
    return max_len


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None) -> Dict:
    S = cache_size(cfg, max_len)
    dtype = dtype or (jnp.int8 if cfg.kv_dtype == "int8" else nn.dt(cfg))
    shape = (cfg.n_layers, batch, S, cfg.n_kv_heads, cfg.d_head)
    cache = {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "lens": jnp.zeros((batch,), jnp.int32),
    }
    if dtype == jnp.int8:
        # symmetric per-(position, head) scales; 1/(2*hd) size overhead
        cache["k_scale"] = jnp.zeros(shape[:-1], jnp.float32)
        cache["v_scale"] = jnp.zeros(shape[:-1], jnp.float32)
    return cache


def prefill(cfg: ModelConfig, params: Dict, tokens: jax.Array, *,
            patches: Optional[jax.Array] = None,
            max_len: Optional[int] = None,
            attn_impl: str = "auto") -> Tuple[jax.Array, Dict]:
    """Process the full prompt, return (last-position logits, filled cache)."""
    B, Lt = tokens.shape
    x = _embed_inputs(cfg, params, tokens, patches)
    L = x.shape[1]
    S = cache_size(cfg, max_len or L)
    prefix = cfg.prefix_len if cfg.family == "vlm" else 0

    def scan_body(h, lp):
        h = constrain(h, "batch", None, "residual")
        attn_in = nn.apply_norm(cfg, lp["ln1"], h)
        q, k, v = nn.qkv_project(lp["attn"], attn_in)
        if cfg.pos == "rope":
            pos = jnp.arange(L)[None]
            q = nn.apply_rope(q, jnp.broadcast_to(pos, (B, L)), cfg.rope_theta)
            k = nn.apply_rope(k, jnp.broadcast_to(pos, (B, L)), cfg.rope_theta)
        from ..kernels import ops
        attn = ops.attention(q, k, v, causal=True, window=cfg.sliding_window,
                             logit_softcap=cfg.logit_softcap, impl=attn_impl,
                             prefix_len=prefix)
        attn = jnp.einsum("blhk,hkd->bld", attn, lp["attn"]["wo"])
        h = h + attn
        mlp_in = nn.apply_norm(cfg, lp["ln2"], h)
        if cfg.family == "moe":
            out, _ = nn.moe_block(cfg, lp["moe"], mlp_in)
            h = h + out
        else:
            h = h + nn.mlp_block(cfg, lp["mlp"], mlp_in)
        # cache the trailing S positions (ring-aligned: position p sits at
        # slot p % S once the window has wrapped; for p >= L - S that is
        # the same contiguous tail order when S divides L or L <= S).
        k_keep = k[:, -S:].astype(nn.dt(cfg))
        v_keep = v[:, -S:].astype(nn.dt(cfg))
        if cfg.sliding_window is not None and L > S:
            # roll so that slot i holds absolute position (L - S + i)
            # consistent with decode's pos % S ring indexing
            shift = L % S
            k_keep = jnp.roll(k_keep, shift, axis=1)
            v_keep = jnp.roll(v_keep, shift, axis=1)
        return h, (k_keep, v_keep)

    h, (ks, vs) = nn.scan_layers(scan_body, x, params["layers"])
    h = nn.apply_norm(cfg, params["final_norm"], h[:, -1])
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = nn.unembed(cfg, head, h)

    if L < S:
        pad = S - L
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    cache = {"lens": jnp.full((B,), min(L, S), jnp.int32)}
    if cfg.kv_dtype == "int8":
        cache["k"], cache["k_scale"] = nn.quantize_kv(ks)
        cache["v"], cache["v_scale"] = nn.quantize_kv(vs)
    else:
        cache["k"], cache["v"] = ks, vs
    return logits, cache


def decode_step_paged(cfg: ModelConfig, params: Dict, pool: Dict,
                      tokens: jax.Array,        # [B] int32 current token
                      page_table: jax.Array,    # [B, pages_per_seq] int32
                      seq_lens: jax.Array,      # [B] tokens BEFORE this step
                      *,
                      max_pages: Optional[int] = None,
                      ) -> Tuple[jax.Array, Dict]:
    """One decode iteration against the vLLM-style paged KV pool
    (serving/kv_cache.py). The whole decode set goes through one batched
    paged-attention call per layer: the new token's K/V rides along as a
    fused kernel operand (so attention never reads a page aliased with a
    same-step scatter) and is scattered into the page owning slot
    ``seq_lens[b]`` only for the pool carry. ``max_pages`` statically
    trims the kernel's page grid to the deepest live sequence.

    pool: {"k": [L, n_pages, Hk, page, hd], "v": ...}.
    Returns (logits, new_pool)."""
    from ..kernels import ops
    B = tokens.shape[0]
    page_size = pool["k"].shape[3]
    x = nn.embed(cfg, params["embed"], tokens)        # [B, d]
    page_idx = seq_lens // page_size
    offset = seq_lens % page_size
    phys = jnp.take_along_axis(page_table, page_idx[:, None], axis=1)[:, 0]

    def scan_body(h, xs):
        lp, kp, vp = xs                                # [n_pages, Hk, page, hd]
        h = constrain(h, "batch", "model")
        with jax.named_scope(spans.QKV):
            attn_in = nn.apply_norm(cfg, lp["ln1"], h)
            q = jnp.einsum("bd,dhk->bhk", attn_in, lp["attn"]["wq"])
            k_new = jnp.einsum("bd,dhk->bhk", attn_in, lp["attn"]["wk"])
            v_new = jnp.einsum("bd,dhk->bhk", attn_in, lp["attn"]["wv"])
            if cfg.pos == "rope":
                q = nn.apply_rope(q, seq_lens, cfg.rope_theta)
                k_new = nn.apply_rope(k_new, seq_lens, cfg.rope_theta)
        with jax.named_scope(spans.ATTENTION):
            attn = ops.batched_paged_decode_attention(
                q, kp, vp, page_table, seq_lens, k_new, v_new,
                max_pages=max_pages, logit_softcap=cfg.logit_softcap)
        with jax.named_scope(spans.KV_WRITE):
            kp = kp.at[phys, :, offset].set(k_new.astype(kp.dtype))
            vp = vp.at[phys, :, offset].set(v_new.astype(vp.dtype))
        with jax.named_scope(spans.OUT_PROJ_MLP):
            h = h + jnp.einsum("bhk,hkd->bd", attn, lp["attn"]["wo"])
            mlp_in = nn.apply_norm(cfg, lp["ln2"], h)
            if cfg.family == "moe":
                h = h + nn.moe_block_decode(cfg, lp["moe"], mlp_in)
            else:
                h = h + nn.mlp_block(cfg, lp["mlp"], mlp_in)
        return h, (kp, vp)

    h, (ks, vs) = nn.scan_layers(
        scan_body, x, (params["layers"], pool["k"], pool["v"]))
    with jax.named_scope(spans.HEAD):
        h = nn.apply_norm(cfg, params["final_norm"], h)
        head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        logits = nn.unembed(cfg, head, h)
    return logits, {"k": ks, "v": vs}


def prefill_chunk_paged(cfg: ModelConfig, params: Dict, pool: Dict,
                        tokens: jax.Array,       # [B, C] chunk token ids
                        page_table: jax.Array,   # [B, pages_per_seq] int32
                        q_offset: jax.Array,     # [B] int32 abs pos of col 0
                        ) -> Tuple[jax.Array, Dict]:
    """One prefill chunk against the paged pool, via the fused
    chunked-prefill kernel. Per layer: project the slab's Q/K/V at
    absolute positions ``[q_offset, q_offset + C)``, scatter K/V into the
    pages owning those slots, then attend the slab against *everything
    resident* — prefix-tree pages and the chunks scattered by earlier
    calls — with query-offset causal masking. Resuming from a cached
    prefix is just starting at ``q_offset > 0``.

    pool: {"k": [L, n_pages, Hk, page, hd], "v": ...}.
    Returns (last-position logits [B, V], new_pool)."""
    from ..kernels import ops
    B, C = tokens.shape
    page_size = pool["k"].shape[3]
    x = nn.embed(cfg, params["embed"], tokens)           # [B, C, d]
    positions = q_offset[:, None] + jnp.arange(C)[None]  # [B, C]
    phys = jnp.take_along_axis(page_table, positions // page_size, axis=1)
    offset = positions % page_size
    kv_lens = q_offset + C

    def scan_body(h, xs):
        lp, kp, vp = xs                                # [n_pages, Hk, page, hd]
        h = constrain(h, "batch", None, "residual")
        with jax.named_scope(spans.QKV):
            attn_in = nn.apply_norm(cfg, lp["ln1"], h)
            # [B, C, H/Hk, hd]
            q, k, v = nn.qkv_project(lp["attn"], attn_in)
            if cfg.pos == "rope":
                q = nn.apply_rope(q, positions, cfg.rope_theta)
                k = nn.apply_rope(k, positions, cfg.rope_theta)
        with jax.named_scope(spans.KV_WRITE):
            kp = kp.at[phys, :, offset].set(k.astype(kp.dtype))
            vp = vp.at[phys, :, offset].set(v.astype(vp.dtype))
        with jax.named_scope(spans.ATTENTION):
            attn = ops.chunked_prefill_attention(
                q, kp, vp, page_table, q_offset, kv_lens,
                logit_softcap=cfg.logit_softcap)
        with jax.named_scope(spans.OUT_PROJ_MLP):
            h = h + jnp.einsum("blhk,hkd->bld", attn, lp["attn"]["wo"])
            mlp_in = nn.apply_norm(cfg, lp["ln2"], h)
            if cfg.family == "moe":
                out, _ = nn.moe_block(cfg, lp["moe"], mlp_in)
                h = h + out
            else:
                h = h + nn.mlp_block(cfg, lp["mlp"], mlp_in)
        return h, (kp, vp)

    h, (ks, vs) = nn.scan_layers(
        scan_body, x, (params["layers"], pool["k"], pool["v"]))
    with jax.named_scope(spans.HEAD):
        h = nn.apply_norm(cfg, params["final_norm"], h[:, -1])
        head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        logits = nn.unembed(cfg, head, h)
    return logits, {"k": ks, "v": vs}


def prefill_kv(cfg: ModelConfig, params: Dict, tokens: jax.Array, *,
               patches: Optional[jax.Array] = None,
               attn_impl: str = "auto"
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Prefill returning raw per-layer K/V [L, B, S, Hk, hd] (for
    scattering into the paged pool) plus last-position logits."""
    logits, cache = prefill(cfg, params, tokens, patches=patches,
                            max_len=tokens.shape[1], attn_impl=attn_impl)
    return logits, cache["k"], cache["v"]


def decode_step(cfg: ModelConfig, params: Dict, cache: Dict,
                tokens: jax.Array,            # [B] int32 current token
                pos: jax.Array,               # [] int32 absolute position
                ) -> Tuple[jax.Array, Dict]:
    """One decode iteration for the whole batch. Returns (logits, cache)."""
    B = tokens.shape[0]
    x = nn.embed(cfg, params["embed"], tokens)    # [B, d]
    S = cache["k"].shape[2]
    new_lens = jnp.minimum(cache["lens"] + 1, S)
    quant = "k_scale" in cache                    # int8 KV cache path

    def scan_body(h, xs):
        if quant:
            lp, kc, vc, ksc, vsc = xs
            scales = (ksc, vsc)
        else:
            lp, kc, vc = xs
            scales = None
        h = constrain(h, "batch", "model")
        attn_in = nn.apply_norm(cfg, lp["ln1"], h)
        attn, kc, vc, scales = nn.attention_decode(
            cfg, lp["attn"], attn_in, kc, vc, pos, new_lens,
            window=cfg.sliding_window, kv_scales=scales,
        )
        h = h + attn
        mlp_in = nn.apply_norm(cfg, lp["ln2"], h)
        if cfg.family == "moe":
            h = h + nn.moe_block_decode(cfg, lp["moe"], mlp_in)
        else:
            h = h + nn.mlp_block(cfg, lp["mlp"], mlp_in)
        out = (kc, vc) + (scales if quant else ())
        return h, out

    xs = (params["layers"], cache["k"], cache["v"])
    if quant:
        xs = xs + (cache["k_scale"], cache["v_scale"])
    h, saved = nn.scan_layers(scan_body, x, xs)
    h = nn.apply_norm(cfg, params["final_norm"], h)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = nn.unembed(cfg, head, h)
    new_cache = {"k": saved[0], "v": saved[1], "lens": new_lens}
    if quant:
        new_cache["k_scale"], new_cache["v_scale"] = saved[2], saved[3]
    return logits, new_cache
