"""Shared transformer layers: norms, RoPE, flash-style chunked attention
(causal / bidirectional / sliding-window / cross), GQA, MLA, gated MLP.

Attention is blockwise (running log-sum-exp over KV chunks) so >=32k-token
sequences never materialize an (S x S) score matrix.  Causal attention
iterates only the chunks at-or-below the diagonal (a static python loop over
query chunks with exactly the needed KV scan length), so compiled FLOPs track
the `S(S+1)/2` triangle rather than the full square.  SWA additionally
restricts each query chunk's KV range to its window -> O(S*w) compute.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, MLAConfig
from repro.distributed.sharding import constraint
from repro.models.params import PSpec

# ------------------------------------------------------------------- norms --

def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


def layernorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


def norm(x: jax.Array, w: jax.Array, kind: str) -> jax.Array:
    return rmsnorm(x, w) if kind == "rmsnorm" else layernorm(x, w)


# -------------------------------------------------------------------- RoPE --

def rope_freqs(dh: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, dh) rotated pairwise; positions: (..., S) int32."""
    dh = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(dh, theta), jnp.float32)
    # angles: (..., S, 1, dh/2)
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs[None, None, :]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    out = jnp.stack([y1, y2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


# -------------------------------------------------- blockwise attention -----

def _attn_block(q, k, v, scale, mask):
    """One (q_chunk x kv_chunk) block: returns (scores_max, exp_sum, pv)."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, -1e30)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    return m, l, pv


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    q_offset: int = 0,
                    q_chunk: int = 1024, kv_chunk: int = 1024) -> jax.Array:
    """Blockwise attention.

    q: (B, Sq, H, dh); k, v: (B, Skv, Hkv, dh) with H % Hkv == 0 (GQA).
    ``q_offset`` is the absolute position of q[0] relative to k[0] (used by
    chunked prefill; 0 for self-attention).  window > 0 = sliding window.
    Returns (B, Sq, H, dh).
    """
    b, sq, h, dh = q.shape
    _, skv, hkv, _ = k.shape
    dv = v.shape[-1]
    g = h // hkv
    scale = dh ** -0.5
    qg = q.reshape(b, sq, hkv, g, dh)

    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    # pad KV to a chunk multiple: dynamic_slice clamps out-of-range starts,
    # which would silently misalign kv_pos on the last chunk otherwise
    skv_pad = ((skv + kv_chunk - 1) // kv_chunk) * kv_chunk
    if skv_pad != skv:
        k = jnp.pad(k, ((0, 0), (0, skv_pad - skv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, skv_pad - skv), (0, 0), (0, 0)))
    n_q = (sq + q_chunk - 1) // q_chunk
    outs = []
    for qi in range(n_q):
        q0 = qi * q_chunk
        qc = min(q_chunk, sq - q0)
        qblk = jax.lax.dynamic_slice_in_dim(qg, q0, qc, axis=1)
        q_pos_hi = q_offset + q0 + qc - 1  # last query position in block
        # KV range this block can see
        if causal:
            kv_hi = min(q_pos_hi + 1, skv)
        else:
            kv_hi = skv
        kv_lo = 0
        if window > 0:
            kv_lo = max(0, q_offset + q0 - window + 1)
        # align to chunks (static)
        c_lo = kv_lo // kv_chunk
        c_hi = (kv_hi + kv_chunk - 1) // kv_chunk
        n_kv = max(c_hi - c_lo, 1)

        q_pos = q_offset + q0 + jnp.arange(qc)

        def body(carry, ci):
            m_run, l_run, acc = carry
            k0 = (c_lo + ci) * kv_chunk
            kblk = jax.lax.dynamic_slice_in_dim(k, k0, kv_chunk, axis=1)
            vblk = jax.lax.dynamic_slice_in_dim(v, k0, kv_chunk, axis=1)
            kv_pos = k0 + jnp.arange(kv_chunk)
            mask = jnp.ones((qc, kv_chunk), bool)
            if causal:
                mask &= q_pos[:, None] >= kv_pos[None, :]
            if window > 0:
                mask &= q_pos[:, None] - kv_pos[None, :] < window
            mask &= (kv_pos < skv)[None, :]
            m, l, pv = _attn_block(qblk, kblk, vblk, scale,
                                   mask[None, None, None, :, :])
            m_new = jnp.maximum(m_run, m)
            corr_old = jnp.exp(m_run - m_new)
            corr_new = jnp.exp(m - m_new)
            l_new = l_run * corr_old + l * corr_new
            # shapes -- m,l: (b,hkv,g,qc); acc/pv: (b,qc,hkv,g,dv)
            corr_old_b = jnp.transpose(corr_old, (0, 3, 1, 2))[..., None]
            corr_new_b = jnp.transpose(corr_new, (0, 3, 1, 2))[..., None]
            acc_new = acc * corr_old_b + pv * corr_new_b
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, qc), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, qc), jnp.float32)
        a0 = jnp.zeros((b, qc, hkv, g, dv), jnp.float32)
        (m_f, l_f, acc_f), _ = jax.lax.scan(body, (m0, l0, a0),
                                            jnp.arange(n_kv))
        l_b = jnp.transpose(l_f, (0, 3, 1, 2))[..., None]
        outs.append((acc_f / jnp.maximum(l_b, 1e-30)).astype(q.dtype))
    out = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    return out.reshape(b, sq, h, dv)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array, *, window: int = 0,
                     kv_chunk: int = 2048, scale: Optional[float] = None,
                     return_lse: bool = False, kv_scales=None):
    """Single-position decode: q (B,1,H,dh) vs cache (B,L,Hkv,dh).

    ``cache_len`` (scalar int32) = number of valid cache positions.  SWA only
    attends to the last ``window`` positions.  Memory-bound by design: one
    pass over the cache with a running LSE.  ``return_lse`` exposes the raw
    (acc, m, l) triple for cross-shard combination (flash-decoding).
    """
    b, _, h, dh = q.shape
    _, L, hkv, _ = k_cache.shape
    dv = v_cache.shape[-1]
    g = h // hkv
    scale = dh ** -0.5 if scale is None else scale
    qg = q.reshape(b, 1, hkv, g, dh)
    kv_chunk = min(kv_chunk, L)
    n_kv = (L + kv_chunk - 1) // kv_chunk
    L_pad = n_kv * kv_chunk
    if L_pad != L:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, L_pad - L), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, L_pad - L), (0, 0), (0, 0)))
        if kv_scales is not None:
            kv_scales = tuple(jnp.pad(s, ((0, 0), (0, L_pad - L), (0, 0)))
                              for s in kv_scales)

    def body(carry, ci):
        m_run, l_run, acc = carry
        k0 = ci * kv_chunk
        kblk = jax.lax.dynamic_slice_in_dim(k_cache, k0, kv_chunk, axis=1)
        vblk = jax.lax.dynamic_slice_in_dim(v_cache, k0, kv_chunk, axis=1)
        if kv_scales is not None:  # int8 cache: dequantize per chunk
            ksb = jax.lax.dynamic_slice_in_dim(kv_scales[0], k0, kv_chunk, axis=1)
            vsb = jax.lax.dynamic_slice_in_dim(kv_scales[1], k0, kv_chunk, axis=1)
            kblk = kv_dequantize(kblk, ksb, q.dtype)
            vblk = kv_dequantize(vblk, vsb, q.dtype)
        kv_pos = k0 + jnp.arange(kv_chunk)
        mask = kv_pos < cache_len
        if window > 0:
            mask &= kv_pos >= cache_len - window
        m, l, pv = _attn_block(qg, kblk, vblk, scale,
                               mask[None, None, None, None, :])
        m_new = jnp.maximum(m_run, m)
        c_o = jnp.exp(m_run - m_new)
        c_n = jnp.exp(m - m_new)
        l_new = l_run * c_o + l * c_n
        c_o_b = jnp.transpose(c_o, (0, 3, 1, 2))[..., None]
        c_n_b = jnp.transpose(c_n, (0, 3, 1, 2))[..., None]
        return (m_new, l_new, acc * c_o_b + pv * c_n_b), None

    m0 = jnp.full((b, hkv, g, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, 1), jnp.float32)
    a0 = jnp.zeros((b, 1, hkv, g, dv), jnp.float32)
    (m_f, l_f, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(n_kv))
    if return_lse:
        return acc, m_f, l_f
    l_b = jnp.transpose(l_f, (0, 3, 1, 2))[..., None]
    return (acc / jnp.maximum(l_b, 1e-30)).astype(q.dtype).reshape(b, 1, h, dv)


# ----------------------------------------------------------- GQA attention --

def gqa_abstract(cfg: ModelConfig) -> Dict[str, PSpec]:
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    tp_heads = "tp" if h % 16 == 0 else None  # uneven head counts stay local
    p: Dict[str, PSpec] = {
        "wq": PSpec((d, h, dh), ("fsdp", tp_heads, None)),
        "wk": PSpec((d, hkv, dh), ("fsdp", None, None)),
        "wv": PSpec((d, hkv, dh), ("fsdp", None, None)),
        "wo": PSpec((h, dh, d), (tp_heads, None, "fsdp")),
    }
    if cfg.qkv_bias:
        p["bq"] = PSpec((h, dh), (tp_heads, None), init="zeros")
        p["bk"] = PSpec((hkv, dh), (None, None), init="zeros")
        p["bv"] = PSpec((hkv, dh), (None, None), init="zeros")
    return p


def gqa_apply(p, x: jax.Array, cfg: ModelConfig, positions: jax.Array,
              *, cache: Optional[Dict] = None, cache_index=None,
              kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
              causal: bool = True) -> Tuple[jax.Array, Optional[Dict]]:
    """GQA attention.  If `cache` is given, runs single-token decode and
    returns the updated cache.  `kv_override` supplies external K/V source
    states (cross-attention)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cdt))
    if "bq" in p:
        q = q + p["bq"].astype(cdt)
    if kv_override is None:
        src = x
    else:
        src = kv_override[0]
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"].astype(cdt))
    if "bk" in p:
        k = k + p["bk"].astype(cdt)
        v = v + p["bv"].astype(cdt)
    if kv_override is None:  # self-attention: rope
        q = apply_rope(q, positions, cfg.rope_theta)
        src_pos = positions if cache is None else positions
        k = apply_rope(k, src_pos, cfg.rope_theta)
    q = constraint(q, "dp", None, "tp" if cfg.n_heads % 16 == 0 else None, None)

    if cache is not None:
        # single-token decode against the cache
        L = cache["k"].shape[1]
        int8_cache = bool(cfg.kv_cache_int8_scale)
        ks = vs = None
        if int8_cache:
            k, ks_new = kv_quantize(k)
            v, vs_new = kv_quantize(v)
        if cfg.seq_shard_decode and not (cfg.attn_window and L <= cfg.attn_window):
            if int8_cache:
                out, k_cache, v_cache, ks, vs = seqshard_decode_gqa_int8(
                    q, cache["k"], cache["v"], cache["ks"], cache["vs"],
                    k, v, ks_new, vs_new, cache_index, cfg.decode_batch_axes)
            else:
                out, k_cache, v_cache = seqshard_decode_gqa(
                    q, cache["k"], cache["v"], k, v, cache_index,
                    cfg.decode_batch_axes)
        elif cfg.attn_window and L <= cfg.attn_window:
            # rolling window cache: slot = index mod window; every resident
            # entry is in-window by construction (keys carry absolute RoPE,
            # softmax is order-invariant)
            slot = jnp.mod(cache_index, L)
            k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
            scales = None
            if int8_cache:
                ks = jax.lax.dynamic_update_slice_in_dim(cache["ks"], ks_new, slot, axis=1)
                vs = jax.lax.dynamic_update_slice_in_dim(cache["vs"], vs_new, slot, axis=1)
                scales = (ks, vs)
            clen = jnp.minimum(cache_index + 1, L)
            out = decode_attention(q, k_cache, v_cache, clen, window=0,
                                   kv_scales=scales)
        else:
            k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, cache_index, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, cache_index, axis=1)
            scales = None
            if int8_cache:
                ks = jax.lax.dynamic_update_slice_in_dim(cache["ks"], ks_new, cache_index, axis=1)
                vs = jax.lax.dynamic_update_slice_in_dim(cache["vs"], vs_new, cache_index, axis=1)
                scales = (ks, vs)
            out = decode_attention(q, k_cache, v_cache, cache_index + 1,
                                   window=cfg.attn_window, kv_scales=scales)
        new_cache = {"k": k_cache, "v": v_cache}
        if int8_cache:
            new_cache["ks"], new_cache["vs"] = ks, vs
    else:
        out = flash_attention(q, k, v, causal=causal and kv_override is None,
                              window=cfg.attn_window)
        if cfg.kv_cache_int8_scale:  # prefill fills an int8 cache
            kq, kss = kv_quantize(k)
            vq, vss = kv_quantize(v)
            new_cache = {"k": kq, "v": vq, "ks": kss, "vs": vss}
        else:
            new_cache = {"k": k, "v": v}  # prefill: return built cache
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(cdt))
    return constraint(y, "dp", None, None), new_cache


# --------------------------------------------------------------------- MLA --

def mla_abstract(cfg: ModelConfig) -> Dict[str, PSpec]:
    m: MLAConfig = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk = m.qk_nope_dim
    return {
        "w_dq": PSpec((d, m.q_lora_rank), ("fsdp", None)),
        "q_norm": PSpec((m.q_lora_rank,), (None,), init="ones"),
        "w_uq": PSpec((m.q_lora_rank, h, qk + m.qk_rope_dim), (None, "tp", None)),
        "w_dkv": PSpec((d, m.kv_lora_rank + m.qk_rope_dim), ("fsdp", None)),
        "kv_norm": PSpec((m.kv_lora_rank,), (None,), init="ones"),
        "w_uk": PSpec((m.kv_lora_rank, h, qk), (None, "tp", None)),
        "w_uv": PSpec((m.kv_lora_rank, h, m.v_head_dim), (None, "tp", None)),
        "wo": PSpec((h, m.v_head_dim, d), ("tp", None, "fsdp")),
    }


def mla_apply(p, x: jax.Array, cfg: ModelConfig, positions: jax.Array,
              *, cache: Optional[Dict] = None, cache_index=None
              ) -> Tuple[jax.Array, Optional[Dict]]:
    """Multi-head Latent Attention.

    Prefill/train: expanded form (materialize per-head K/V from the latent).
    Decode: absorbed form — the cache stores only (c_kv, k_rope), queries are
    projected into the latent space, giving the MQA-like memory profile that
    makes MLA's 32k cache 8-9x smaller than GQA's."""
    m: MLAConfig = cfg.mla
    cdt = jnp.dtype(cfg.compute_dtype)
    b, s, _ = x.shape
    h, qk, qr = cfg.n_heads, m.qk_nope_dim, m.qk_rope_dim

    cq = rmsnorm(jnp.einsum("bsd,dr->bsr", x, p["w_dq"].astype(cdt)), p["q_norm"])
    q = jnp.einsum("bsr,rhk->bshk", cq, p["w_uq"].astype(cdt))
    q_nope, q_rope = q[..., :qk], q[..., qk:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    dkv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"].astype(cdt))
    c_kv = rmsnorm(dkv[..., :m.kv_lora_rank], p["kv_norm"])
    k_rope = apply_rope(dkv[..., None, m.kv_lora_rank:], positions, cfg.rope_theta)

    scale = (qk + qr) ** -0.5
    if cache is not None:
        # absorbed decode: fold W_uk into q and attend in the latent space —
        # equivalent to MQA with one (kv_lora+rope)-dim kv head, so it reuses
        # the chunked/flash decode path (and seq-sharding) directly.
        q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["w_uk"].astype(cdt))
        q_abs = jnp.concatenate([q_lat, q_rope], axis=-1)      # (B,1,H,r+qr)
        k_new = jnp.concatenate([c_kv, k_rope[:, :, 0, :]],
                                axis=-1)[:, :, None, :]        # (B,1,1,r+qr)
        v_new = c_kv[:, :, None, :]                            # (B,1,1,r)
        k_cache_full = jnp.concatenate([cache["ckv"], cache["kr"]],
                                       axis=-1)[:, :, None, :]
        v_cache_full = cache["ckv"][:, :, None, :]
        if cfg.seq_shard_decode:
            o_lat, k_cache_full, v_cache_full = seqshard_decode_gqa(
                q_abs, k_cache_full, v_cache_full, k_new, v_new, cache_index,
                cfg.decode_batch_axes, scale=scale)
        else:
            k_cache_full = jax.lax.dynamic_update_slice_in_dim(
                k_cache_full, k_new, cache_index, axis=1)
            v_cache_full = jax.lax.dynamic_update_slice_in_dim(
                v_cache_full, v_new, cache_index, axis=1)
            o_lat = decode_attention(q_abs, k_cache_full, v_cache_full,
                                     cache_index + 1, scale=scale)
        out = jnp.einsum("bshr,rhv->bshv", o_lat.astype(cdt),
                         p["w_uv"].astype(cdt))
        new_cache = {"ckv": v_cache_full[:, :, 0, :],
                     "kr": k_cache_full[:, :, 0, m.kv_lora_rank:]}
    else:
        k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uk"].astype(cdt))
        v = jnp.einsum("bsr,rhv->bshv", c_kv, p["w_uv"].astype(cdt))
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope, (b, s, h, qr))], axis=-1)
        qfull = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = flash_attention(qfull, k, v, causal=True)
        # flash_attention assumes q/k same dh for v; v dim differs -> handled:
        new_cache = {"ckv": c_kv, "kr": k_rope[:, :, 0, :]}
    y = jnp.einsum("bshv,hvd->bsd", out, p["wo"].astype(cdt))
    return constraint(y, "dp", None, None), new_cache


# --------------------------------------------------------------------- MLP --

def mlp_abstract(cfg: ModelConfig, d_ff: Optional[int] = None) -> Dict[str, PSpec]:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "w1": PSpec((d, f), ("fsdp", "tp")),   # gate
        "w3": PSpec((d, f), ("fsdp", "tp")),   # up
        "w2": PSpec((f, d), ("tp", "fsdp")),   # down
    }


def mlp_apply(p, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    cdt = jnp.dtype(cfg.compute_dtype)
    g = jnp.einsum("bsd,df->bsf", x, p["w1"].astype(cdt))
    u = jnp.einsum("bsd,df->bsf", x, p["w3"].astype(cdt))
    hcat = jax.nn.silu(g) * u
    hcat = constraint(hcat, "dp", None, "tp")
    y = jnp.einsum("bsf,fd->bsd", hcat, p["w2"].astype(cdt))
    return constraint(y, "dp", None, None)


# ------------------------------------------------------- int8 KV cache -----

def kv_quantize(x: jax.Array):
    """HP-MDR-style per-(token, head) exponent alignment: int8 mantissa +
    one bf16 scale per head-vector (1/dh overhead).  Returns (q, scales)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale * 127.0), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)[..., 0]


def kv_dequantize(q: jax.Array, scales: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32)
            * (scales.astype(jnp.float32)[..., None] / 127.0)).astype(dtype)


# ------------------------------------------------ seq-sharded flash decode --

def _lse_combine(acc, m, l, axis_name: str):
    """Flash-decoding cross-shard combine of (acc, m, l) partials."""
    m_g = jax.lax.pmax(m, axis_name)
    corr = jnp.exp(m - m_g)                       # (b,hkv,g,1)
    l_g = jax.lax.psum(l * corr, axis_name)
    corr_b = jnp.transpose(corr, (0, 3, 1, 2))[..., None]
    acc_g = jax.lax.psum(acc * corr_b, axis_name)
    return acc_g, l_g


def _masked_update(cache_local, new, index, lo, L_local):
    """Update position ``index`` if it falls in this shard's [lo, lo+L)."""
    off = index - lo
    in_range = (off >= 0) & (off < L_local)
    upd = jax.lax.dynamic_update_slice_in_dim(
        cache_local, new.astype(cache_local.dtype),
        jnp.clip(off, 0, L_local - 1), axis=1)
    return jnp.where(in_range, upd, cache_local)


def seqshard_decode_gqa(q, k_cache, v_cache, k_new, v_new, index,
                        batch_axes, *, scale=None):
    """Flash-decoding with the KV cache sharded over 'model' on the L axis.

    All heads are computed on every model shard (decode is memory-bound; the
    cache READ is the cost and it is perfectly sharded — wire traffic is one
    (B,1,H,dv)+LSE psum per layer instead of a 1/16-replicated cache)."""
    from repro.distributed.sharding import get_current_mesh, spec as shspec
    from jax.sharding import PartitionSpec as P
    mesh = get_current_mesh()
    b_ax = tuple(batch_axes) if batch_axes else None
    cache_spec = shspec(b_ax, "model", None, None)
    q_spec = shspec(b_ax, None, None, None)

    def body(qs, kc, vc, kn, vn, idx):
        L_local = kc.shape[1]
        lo = jax.lax.axis_index("model") * L_local
        kc = _masked_update(kc, kn, idx, lo, L_local)
        vc = _masked_update(vc, vn, idx, lo, L_local)
        clen_local = jnp.clip(idx + 1 - lo, 0, L_local)
        acc, m, l = decode_attention(qs, kc, vc, clen_local, window=0,
                                     scale=scale, return_lse=True)
        acc_g, l_g = _lse_combine(acc, m, l, "model")
        l_b = jnp.transpose(l_g, (0, 3, 1, 2))[..., None]
        out = (acc_g / jnp.maximum(l_b, 1e-30)).astype(qs.dtype)
        return out.reshape(qs.shape[0], 1, qs.shape[2], vc.shape[-1]), kc, vc

    smap = jax.shard_map(
        body, mesh=mesh,
        in_specs=(q_spec, cache_spec, cache_spec, q_spec, q_spec, P()),
        out_specs=(q_spec, cache_spec, cache_spec),
        check_vma=False)
    return smap(q, k_cache, v_cache, k_new, v_new, index)


def seqshard_decode_gqa_int8(q, k_cache, v_cache, ks_cache, vs_cache,
                             k_new, v_new, ks_new, vs_new, index, batch_axes,
                             *, scale=None):
    """Flash-decoding over an int8, per-(token,head)-aligned KV cache
    (HP-MDR alignment on serving state): cache reads are half the bytes."""
    from repro.distributed.sharding import get_current_mesh, spec as shspec
    from jax.sharding import PartitionSpec as P
    mesh = get_current_mesh()
    b_ax = tuple(batch_axes) if batch_axes else None
    cache_spec = shspec(b_ax, "model", None, None)
    scale_spec = shspec(b_ax, "model", None)
    q_spec = shspec(b_ax, None, None, None)
    new_scale_spec = shspec(b_ax, None, None)

    def body(qs, kc, vc, ksc, vsc, kn, vn, ksn, vsn, idx):
        L_local = kc.shape[1]
        lo = jax.lax.axis_index("model") * L_local
        kc = _masked_update(kc, kn, idx, lo, L_local)
        vc = _masked_update(vc, vn, idx, lo, L_local)
        ksc = _masked_update(ksc, ksn, idx, lo, L_local)
        vsc = _masked_update(vsc, vsn, idx, lo, L_local)
        clen_local = jnp.clip(idx + 1 - lo, 0, L_local)
        acc, m, l = decode_attention(qs, kc, vc, clen_local, window=0,
                                     scale=scale, return_lse=True,
                                     kv_scales=(ksc, vsc))
        acc_g, l_g = _lse_combine(acc, m, l, "model")
        l_b = jnp.transpose(l_g, (0, 3, 1, 2))[..., None]
        out = (acc_g / jnp.maximum(l_b, 1e-30)).astype(qs.dtype)
        return (out.reshape(qs.shape[0], 1, qs.shape[2], vc.shape[-1]),
                kc, vc, ksc, vsc)

    smap = jax.shard_map(
        body, mesh=mesh,
        in_specs=(q_spec, cache_spec, cache_spec, scale_spec, scale_spec,
                  q_spec, q_spec, new_scale_spec, new_scale_spec, P()),
        out_specs=(q_spec, cache_spec, cache_spec, scale_spec, scale_spec),
        check_vma=False)
    return smap(q, k_cache, v_cache, ks_cache, vs_cache, k_new, v_new,
                ks_new, vs_new, index)
