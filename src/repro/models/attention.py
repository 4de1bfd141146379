"""GQA attention: full / sliding-window / chunked / prefix masks, KV-cache
decode, cross-attention, and a memory-safe blockwise (flash-style) path.

Shapes: x (B, S, D); q (B, S, H, hd); k/v (B, S, KV, hd); GQA groups H//KV.
Long sequences use ``blockwise_attn`` — an online-softmax scan over KV blocks
(the XLA-level equivalent of FlashAttention) so the S x S score matrix is
never materialized; the Pallas flash kernel (kernels/flash_attention) is the
TPU hot path with identical semantics, selected with ``attn_impl='pallas'``.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import (AX_DATA, AX_MODEL, ModelConfig, constrain, dense_init,
                     fsdp_spec, rope)

NEG_INF = -1e30


def init_attn(key, cfg: ModelConfig, *, cross: bool = False):
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    dt = cfg.jdtype
    params = {
        "wq": dense_init(ks[0], (D, H, hd), dt),
        "wk": dense_init(ks[1], (D, KV, hd), dt),
        "wv": dense_init(ks[2], (D, KV, hd), dt),
        "wo": dense_init(ks[3], (H, hd, D), dt),
    }
    specs = {
        "wq": fsdp_spec(P(None, AX_MODEL, None), cfg),
        "wk": fsdp_spec(P(None, AX_MODEL, None), cfg),
        "wv": fsdp_spec(P(None, AX_MODEL, None), cfg),
        "wo": fsdp_spec(P(AX_MODEL, None, None), cfg),
    }
    return params, specs


def _mask_fn(kind: str, window: int, prefix_len: int):
    """Returns mask(qpos, kpos) -> bool (True = attend)."""
    def mask(qpos, kpos):
        causal = kpos[None, :] <= qpos[:, None]
        if kind == "bidir":
            return jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
        if kind == "causal":
            return causal
        if kind == "swa":
            return causal & (qpos[:, None] - kpos[None, :] < window)
        if kind == "chunked":
            return causal & (qpos[:, None] // window == kpos[None, :] // window)
        if kind == "prefix":
            bidir = (qpos[:, None] < prefix_len) & (kpos[None, :] < prefix_len)
            return causal | bidir
        raise ValueError(kind)
    return mask


def _plain_attn(q, k, v, qpos, kpos, mask_kind, window, prefix_len):
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, k).astype(jnp.float32)
    scores *= hd ** -0.5
    m = _mask_fn(mask_kind, window, prefix_len)(qpos, kpos)
    scores = jnp.where(m[None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", p, v)
    return out.reshape(B, S, H, hd)


def hflat_blockwise_attn(q, k, v, qpos, kpos, mask_kind, window, prefix_len,
                         q_block: int = 1024, kv_block: int = 1024):
    """§Perf variant: H-flat GQA — KV heads broadcast to H inside the score
    einsums so every tensor carries a single head axis that shards H-over-
    model (H=48 splits 16 ways; the grouped (KV=8, G=6) layout cannot, and
    GSPMD falls back to 'involuntary full rematerialization' + fp32 score
    all-gathers — see docs/architecture.md, "LM-substrate notes")."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    Skv = k.shape[1]
    q_block = min(q_block, S)
    kv_block = min(kv_block, Skv)
    nq, nk = S // q_block, Skv // kv_block
    mask = _mask_fn(mask_kind, window, prefix_len)
    scale = hd ** -0.5
    head_spec = P(AX_DATA, AX_MODEL, None, None)

    qh = constrain(q.transpose(0, 2, 1, 3), head_spec)      # (B,H,S,hd)
    # broadcast KV->H as a view; XLA fuses it into the dots
    kh = jnp.broadcast_to(k.transpose(0, 2, 1, 3)[:, :, None],
                          (B, KV, G, Skv, hd)).reshape(B, H, Skv, hd)
    vh = jnp.broadcast_to(v.transpose(0, 2, 1, 3)[:, :, None],
                          (B, KV, G, Skv, hd)).reshape(B, H, Skv, hd)
    kh = constrain(kh, head_spec)
    vh = constrain(vh, head_spec)
    qb = qh.reshape(B, H, nq, q_block, hd)
    kb = kh.reshape(B, H, nk, kv_block, hd)
    vb = vh.reshape(B, H, nk, kv_block, hd)
    qp = qpos.reshape(nq, q_block)
    kp = kpos.reshape(nk, kv_block)

    def q_step(_, qi):
        qblk, qpb = qi                                      # (B,H,q,hd),(q,)

        def kv_step(carry, ki):
            m_run, l_run, acc = carry
            kblk, vblk, kpb = ki
            s = jnp.einsum("bhqd,bhtd->bhqt", qblk, kblk)
            s = constrain((s * scale).astype(jnp.float32), head_spec)
            mm = mask(qpb, kpb)[None, None]
            s = jnp.where(mm, s, NEG_INF)
            m_new = jnp.maximum(m_run, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + p.sum(-1)
            pv = jnp.einsum("bhqt,bhtd->bhqd", p.astype(qblk.dtype), vblk)
            acc = acc * corr[..., None].astype(acc.dtype) + pv
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, H, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, q_block), jnp.float32)
        a0 = jnp.zeros((B, H, q_block, hd), qblk.dtype)
        (m_f, l_f, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kb.transpose(2, 0, 1, 3, 4), vb.transpose(2, 0, 1, 3, 4), kp))
        out = acc / jnp.maximum(l_f, 1e-30)[..., None].astype(acc.dtype)
        return None, out                                    # (B,H,q,hd)

    _, outs = jax.lax.scan(q_step, None,
                           (qb.transpose(2, 0, 1, 3, 4), qp))
    out = outs.transpose(1, 2, 0, 3, 4).reshape(B, H, S, hd)
    return out.transpose(0, 2, 1, 3)


def blockwise_attn(q, k, v, qpos, kpos, mask_kind, window, prefix_len,
                   q_block: int = 1024, kv_block: int = 1024):
    """Online-softmax attention, O(S*B) memory: scan over KV blocks per Q block."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    Skv = k.shape[1]
    q_block = min(q_block, S)
    kv_block = min(kv_block, Skv)
    assert S % q_block == 0 and Skv % kv_block == 0
    nq, nk = S // q_block, Skv // kv_block
    mask = _mask_fn(mask_kind, window, prefix_len)
    scale = hd ** -0.5

    qg = q.reshape(B, nq, q_block, KV, G, hd)
    qp = qpos.reshape(nq, q_block)
    kb = k.reshape(B, nk, kv_block, KV, hd)
    vb = v.reshape(B, nk, kv_block, KV, hd)
    kp = kpos.reshape(nk, kv_block)

    def q_step(_, qi):
        qblk, qpb = qi                                  # (B,q,KV,G,hd),(q,)

        def kv_step(carry, ki):
            m_run, l_run, acc = carry
            kblk, vblk, kpb = ki
            s = jnp.einsum("bqkgh,btkh->bkgqt", qblk, kblk)
            s = (s * scale).astype(jnp.float32)
            mm = mask(qpb, kpb)[None, None, None]
            s = jnp.where(mm, s, NEG_INF)
            m_new = jnp.maximum(m_run, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + p.sum(-1)
            pv = jnp.einsum("bkgqt,btkh->bkgqh", p.astype(qblk.dtype), vblk)
            acc = acc * corr[..., None].astype(acc.dtype) + pv
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, KV, G, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_block), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_block, hd), qblk.dtype)
        (m_f, l_f, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4), kp))
        out = acc / jnp.maximum(l_f, 1e-30)[..., None].astype(acc.dtype)
        return None, out.transpose(0, 3, 1, 2, 4)       # (B,q,KV,G,hd)

    _, outs = jax.lax.scan(q_step, None,
                           (qg.transpose(1, 0, 2, 3, 4, 5), qp))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, hd)
    return out


def attention(params, x, pos, cfg: ModelConfig, *, mask_kind: str,
              kv_x: Optional[jnp.ndarray] = None,
              kv_pos: Optional[jnp.ndarray] = None,
              prefix_len: int = 0):
    """Full-sequence attention (training / prefill).

    ``kv_x`` switches to cross-attention (keys/values from encoder output).
    """
    B, S, D = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    src = x if kv_x is None else kv_x
    k = jnp.einsum("bsd,dhk->bshk", src, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, params["wv"])
    if kv_x is None:
        q, k = rope(q, k, pos, cfg.rope_theta)
        kpos = pos
    else:
        kpos = kv_pos
        mask_kind = "bidir"
    q = constrain(q, P(AX_DATA, None, AX_MODEL, None))
    flash = (cfg.attn_impl == "pallas" and kv_x is None
             and mask_kind in ("causal", "bidir"))
    if flash:
        from repro.kernels.flash_attention.ops import flash_attention
        out = flash_attention(q, k, v, causal=(mask_kind == "causal"))
    elif cfg.opt_attn_layout and kv_x is None:
        out = hflat_blockwise_attn(q, k, v, pos, kpos, mask_kind, cfg.window,
                                   prefix_len)
    elif S > 2048 or k.shape[1] > 2048:
        out = blockwise_attn(q, k, v, pos, kpos, mask_kind, cfg.window,
                             prefix_len)
    else:
        out = _plain_attn(q, k, v, pos, kpos, mask_kind, cfg.window,
                          prefix_len)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


# ---------------------------------------------------------------------------
# KV-cache decode
# ---------------------------------------------------------------------------
def init_kv_cache(cfg: ModelConfig, n_layers: int, batch: int, max_len: int,
                  dtype=None):
    """Ring-buffer KV cache. For swa/chunked archs max_len = window size.

    ``opt_kv_quant`` (§Perf): int8 storage + per-(pos, head) scales — halves
    the decode HBM traffic, which dominates every decode cell's roofline.
    """
    dtype = dtype or cfg.jdtype
    KV, hd = cfg.n_kv_heads, cfg.hd
    cache_len = min(max_len, cfg.window) if cfg.attn in ("swa", "chunked") \
        else max_len
    store = jnp.int8 if cfg.opt_kv_quant else dtype
    cache = {
        "k": jnp.zeros((n_layers, batch, cache_len, KV, hd), store),
        "v": jnp.zeros((n_layers, batch, cache_len, KV, hd), store),
        "idx": jnp.full((cache_len,), jnp.int32(-1)),   # absolute positions
    }
    if cfg.opt_kv_quant:
        cache["k_scale"] = jnp.zeros((n_layers, batch, cache_len, KV),
                                     jnp.bfloat16)
        cache["v_scale"] = jnp.zeros((n_layers, batch, cache_len, KV),
                                     jnp.bfloat16)
    return cache


def cache_specs(cfg: ModelConfig, shard_seq: bool):
    """KV cache sharding: batch over data; seq over model for big caches
    (split-KV decode), else heads over model when they divide."""
    if shard_seq:
        kv = P(None, AX_DATA, AX_MODEL, None, None)
        sc = P(None, AX_DATA, AX_MODEL, None)
    else:
        kv = P(None, AX_DATA, None, AX_MODEL, None)
        sc = P(None, AX_DATA, None, AX_MODEL)
    specs = {"k": kv, "v": kv, "idx": P(None)}
    if cfg.opt_kv_quant:
        specs["k_scale"] = sc
        specs["v_scale"] = sc
    return specs


def decode_attention(params, x, cache_k, cache_v, cache_idx, pos,
                     cfg: ModelConfig, *, kv_x=None, kv_pos=None,
                     k_scale=None, v_scale=None):
    """One-token attention against the cache (already containing this token's
    k/v written by the caller via ``update_cache``)."""
    B = x.shape[0]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])     # (B,1,H,hd)
    if k_scale is not None:                              # int8 cache dequant
        cache_k = cache_k.astype(cfg.jdtype) * k_scale[..., None]
        cache_v = cache_v.astype(cfg.jdtype) * v_scale[..., None]
    if kv_x is None:
        posv = jnp.full((B, 1), pos, jnp.int32)
        q, _ = rope(q, q, posv, cfg.rope_theta)          # rotate q only
        k, v = cache_k, cache_v                          # (B,Sc,KV,hd)
        valid = (cache_idx >= 0) & (cache_idx <= pos)
        if cfg.attn == "swa":
            valid &= pos - cache_idx < cfg.window
        elif cfg.attn == "chunked":
            valid &= cache_idx // cfg.window == pos // cfg.window
    else:
        k = jnp.einsum("bsd,dhk->bshk", kv_x, params["wk"])
        v = jnp.einsum("bsd,dhk->bshk", kv_x, params["wv"])
        valid = jnp.ones((k.shape[1],), bool)
    B, S, H, hd = q.shape[0], k.shape[1], q.shape[2], q.shape[3]
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,btkh->bkgt", qg, k).astype(jnp.float32) * hd ** -0.5
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    out = jnp.einsum("bkgt,btkh->bkgh", p, v).reshape(B, 1, H, hd)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


def update_cache(params, x, cache_k, cache_v, cache_idx, pos,
                 cfg: ModelConfig, k_scale=None, v_scale=None):
    """Write this token's k/v into the ring buffer; returns updated cache."""
    B = x.shape[0]
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])     # (B,1,KV,hd)
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    posv = jnp.full((B, 1), pos, jnp.int32)
    _, k = rope(k, k, posv, cfg.rope_theta)
    slot = pos % cache_k.shape[1]
    if k_scale is not None:                              # int8 quantization
        ks = jnp.max(jnp.abs(k), axis=-1) / 127.0        # (B,1,KV)
        vs = jnp.max(jnp.abs(v), axis=-1) / 127.0
        k = jnp.clip(jnp.round(k / jnp.maximum(ks[..., None], 1e-8)),
                     -127, 127).astype(jnp.int8)
        v = jnp.clip(jnp.round(v / jnp.maximum(vs[..., None], 1e-8)),
                     -127, 127).astype(jnp.int8)
        k_scale = jax.lax.dynamic_update_slice(
            k_scale, ks.astype(k_scale.dtype), (0, slot, 0))
        v_scale = jax.lax.dynamic_update_slice(
            v_scale, vs.astype(v_scale.dtype), (0, slot, 0))
    cache_k = jax.lax.dynamic_update_slice(cache_k, k, (0, slot, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v, (0, slot, 0, 0))
    cache_idx = jax.lax.dynamic_update_slice(
        cache_idx, jnp.asarray(pos, jnp.int32)[None], (slot,))
    if k_scale is not None:
        return cache_k, cache_v, cache_idx, k_scale, v_scale
    return cache_k, cache_v, cache_idx
