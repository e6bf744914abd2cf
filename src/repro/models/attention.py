"""Attention: GQA/MHA with RoPE / M-RoPE, qk-norm, sliding windows,
full-sequence (train/prefill) and cached single-token (decode) paths.

The jnp einsum formulation is the reference path (and what the dry-run
lowers); a Pallas flash-attention kernel (repro/kernels/flash_attention.py)
is the TPU production path, toggled via ``params.set_use_pallas``.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.dist.sharding import constrain
from repro.models import rotary
from repro.models.params import (Builder, apply_linear, head_rms_norm,
                                 softcap, use_pallas)

NEG_INF = -1e30


def init_attention(b: Builder, cfg: ModelConfig, stack: Tuple[int, ...] = (),
                   cross: bool = False) -> None:
    heads_ax = "heads" if cfg.shard_attn_heads else "fsdp"
    kv_ax = "kv_heads" if cfg.shard_attn_heads else "fsdp"
    bias = cfg.family == "vlm"   # qwen2-vl carries qkv bias
    b.linear("wq", cfg.d_model, cfg.q_dim, ("fsdp", heads_ax), stack, bias=bias)
    b.linear("wk", cfg.d_model, cfg.kv_dim, ("fsdp", kv_ax), stack, bias=bias)
    b.linear("wv", cfg.d_model, cfg.kv_dim, ("fsdp", kv_ax), stack, bias=bias)
    b.linear("wo", cfg.q_dim, cfg.d_model, (heads_ax, "fsdp"), stack,
             scale=0.02 / max(1, cfg.n_layers) ** 0.5)
    if cfg.qk_norm and not cross:
        b.ones("q_norm", (*stack, cfg.head_dim), ((None,) * len(stack)) + (None,))
        b.ones("k_norm", (*stack, cfg.head_dim), ((None,) * len(stack)) + (None,))


def _split_heads(x: jax.Array, n: int, hd: int) -> jax.Array:
    return x.reshape(*x.shape[:-1], n, hd)


def _qkv(p: Dict, cfg: ModelConfig, x: jax.Array,
         angles: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array, jax.Array]:
    q = _split_heads(apply_linear(p["wq"], x), cfg.n_heads, cfg.head_dim)
    k = _split_heads(apply_linear(p["wk"], x), cfg.n_kv_heads, cfg.head_dim)
    v = _split_heads(apply_linear(p["wv"], x), cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm and "q_norm" in p:
        q = head_rms_norm(p["q_norm"], q, cfg.norm_eps)
        k = head_rms_norm(p["k_norm"], k, cfg.norm_eps)
    if angles is not None:
        q = rotary.apply_rope(q, angles)
        k = rotary.apply_rope(k, angles)
    return q, k, v


def _sdpa(cfg: ModelConfig, q: jax.Array, k: jax.Array, v: jax.Array,
          mask: jax.Array) -> jax.Array:
    """q: (B,S,H,hd), k/v: (B,T,K,hd), mask: broadcastable (B,1,S,T) bool.
    Grouped-query: H = K*G. Returns (B,S,H*hd)."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    scale = hd ** -0.5
    qg = q.reshape(B, S, K, G, hd)
    # keep bf16 inputs, fp32 accumulation: numerically identical to
    # upcasting (bf16->f32 is exact) but never materializes fp32 copies of
    # the KV cache (§Perf cell-A finding)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, k,
                        preferred_element_type=jnp.float32) * scale
    scores = softcap(scores, cfg.attn_logit_softcap)
    scores = jnp.where(mask[:, :, None], scores, NEG_INF)   # mask (B,K?,S,T)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(v.dtype).reshape(B, S, H * hd)


# Use the chunked (flash-style) path once the score matrix would exceed
# this many elements per (batch, head) — beyond it, materializing S×T
# scores dominates the memory roofline term.
FLASH_THRESHOLD = 1024 * 2048


def _tile_mask(qi, ki, bq: int, bk: int, causal: bool, window: int):
    qpos = qi * bq + jnp.arange(bq)[:, None]
    kpos = ki * bk + jnp.arange(bk)[None, :]
    msk = jnp.ones((bq, bk), dtype=bool)
    if causal:
        msk &= kpos <= qpos
    if window:
        msk &= kpos > qpos - window
    return msk


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _flash_core(causal: bool, window: int, bq: int, bk: int, G: int,
                qg: jax.Array, k: jax.Array, v: jax.Array):
    """Flash attention core. qg: (B,S,K,G,hd) PRE-SCALED fp32;
    k/v: (B,T,K,hd) fp32. Returns out (B,S,K,G,hd) fp32."""
    out, _ = _flash_fwd_pass(causal, window, bq, bk, qg, k, v)
    return out


def _tile_pairs(nq: int, nk: int, bq: int, bk: int, causal: bool,
                window: int):
    """Static enumeration of (q-tile, kv-tile) pairs with any live entry —
    fully-masked tiles are never visited (causal: ~2× fewer; sliding
    window: O(S·window) instead of O(S²))."""
    pairs = []
    for qi in range(nq):
        q_lo, q_hi = qi * bq, qi * bq + bq - 1
        for ki in range(nk):
            k_lo, k_hi = ki * bk, ki * bk + bk - 1
            if causal and k_lo > q_hi:
                continue
            if window and k_hi < q_lo - window + 1:
                continue
            pairs.append((qi, ki))
    return pairs


def _flash_fwd_pass(causal, window, bq, bk, qg, k, v):
    B, S, K, G, hd = qg.shape
    T = k.shape[1]
    nq, nk = S // bq, T // bk
    qc = jnp.moveaxis(qg.reshape(B, nq, bq, K, G, hd), 1, 0)
    kc = jnp.moveaxis(k.reshape(B, nk, bk, K, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nk, bk, K, hd), 1, 0)

    pairs = _tile_pairs(nq, nk, bq, bk, causal, window)
    qi_a = jnp.array([p[0] for p in pairs], dtype=jnp.int32)
    ki_a = jnp.array([p[1] for p in pairs], dtype=jnp.int32)
    first = jnp.array([i == 0 or pairs[i][0] != pairs[i - 1][0]
                       for i in range(len(pairs))])
    last = jnp.array([i == len(pairs) - 1 or pairs[i][0] != pairs[i + 1][0]
                      for i in range(len(pairs))])

    m0 = jnp.full((B, K, G, bq), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((B, K, G, bq), dtype=jnp.float32)
    a0 = jnp.zeros((B, K, G, bq, hd), dtype=jnp.float32)
    out0 = jnp.zeros((nq, B, K, G, bq, hd), dtype=jnp.float32)
    lse0 = jnp.zeros((nq, B, K, G, bq), dtype=jnp.float32)

    def step(carry, xs):
        m, l, acc, outb, lseb = carry
        qi, ki, fst, lst = xs
        qb = jax.lax.dynamic_index_in_dim(qc, qi, 0, keepdims=False)
        kb = jax.lax.dynamic_index_in_dim(kc, ki, 0, keepdims=False)
        vb = jax.lax.dynamic_index_in_dim(vc, ki, 0, keepdims=False)
        m = jnp.where(fst, m0, m)
        l = jnp.where(fst, l0, l)
        acc = jnp.where(fst, a0, acc)
        s = jnp.einsum("bqkgh,btkh->bkgqt", qb, kb,
                       preferred_element_type=jnp.float32)
        msk = _tile_mask(qi, ki, bq, bk, causal, window)
        s = jnp.where(msk[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgqt,btkh->bkgqh", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        lq = jnp.maximum(l_new, 1e-30)
        tile_out = acc_new / lq[..., None]
        tile_lse = m_new + jnp.log(lq)
        cur_o = jax.lax.dynamic_index_in_dim(outb, qi, 0, keepdims=False)
        cur_s = jax.lax.dynamic_index_in_dim(lseb, qi, 0, keepdims=False)
        outb = jax.lax.dynamic_update_index_in_dim(
            outb, jnp.where(lst, tile_out, cur_o), qi, 0)
        lseb = jax.lax.dynamic_update_index_in_dim(
            lseb, jnp.where(lst, tile_lse, cur_s), qi, 0)
        return (m_new, l_new, acc_new, outb, lseb), None

    (_, _, _, outs, lses), _ = jax.lax.scan(
        step, (m0, l0, a0, out0, lse0), (qi_a, ki_a, first, last))
    out = jnp.moveaxis(outs, 0, 3).reshape(B, K, G, S, hd)      # (B,K,G,S,hd)
    out = jnp.moveaxis(out, 3, 1).reshape(B, S, K, G, hd)
    lse = jnp.moveaxis(lses, 0, 3).reshape(B, K, G, S)
    return out, lse


def _flash_fwd_rule(causal, window, bq, bk, G, qg, k, v):
    out, lse = _flash_fwd_pass(causal, window, bq, bk, qg, k, v)
    return out, (qg, k, v, out, lse)


def _flash_bwd_rule(causal, window, bq, bk, G, res, dout):
    """FlashAttention-2-style backward: probabilities are recomputed per
    tile from (q, k, lse); nothing S×T ever materializes. Two passes:
    k-outer for (dk, dv), q-outer for dq."""
    qg, k, v, out, lse = res
    B, S, K, Gd, hd = qg.shape
    T = k.shape[1]
    nq, nk = S // bq, T // bk
    D = jnp.sum(dout * out, axis=-1)                      # (B,S,K,G)
    Dr = jnp.moveaxis(D.reshape(B, S, K, Gd), 1, 3)       # (B,K,G,S)
    do_r = jnp.moveaxis(dout, 1, 3)                       # (B,K,G,S,hd)

    qc = jnp.moveaxis(qg.reshape(B, nq, bq, K, Gd, hd), 1, 0)
    kc = jnp.moveaxis(k.reshape(B, nk, bk, K, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nk, bk, K, hd), 1, 0)
    lse_c = jnp.moveaxis(lse.reshape(B, K, Gd, nq, bq), 3, 0)   # (nq,B,K,G,bq)
    D_c = jnp.moveaxis(Dr.reshape(B, K, Gd, nq, bq), 3, 0)
    do_c = jnp.moveaxis(do_r.reshape(B, K, Gd, nq, bq, hd), 3, 0)

    def p_tile(qb, kb, lse_b, qi, ki):
        s = jnp.einsum("bqkgh,btkh->bkgqt", qb, kb,
                       preferred_element_type=jnp.float32)
        msk = _tile_mask(qi, ki, bq, bk, causal, window)
        s = jnp.where(msk[None, None, None], s, NEG_INF)
        return jnp.exp(s - lse_b[..., None])              # (B,K,G,bq,bk)

    def idx(a, i):
        return jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)

    # ---- pass 1: dk, dv (pairs grouped by k tile) -------------------------
    pairs_k = sorted(_tile_pairs(nq, nk, bq, bk, causal, window),
                     key=lambda p: (p[1], p[0]))
    qi_k = jnp.array([p[0] for p in pairs_k], dtype=jnp.int32)
    ki_k = jnp.array([p[1] for p in pairs_k], dtype=jnp.int32)
    fst_k = jnp.array([i == 0 or pairs_k[i][1] != pairs_k[i - 1][1]
                       for i in range(len(pairs_k))])
    lst_k = jnp.array([i == len(pairs_k) - 1
                       or pairs_k[i][1] != pairs_k[i + 1][1]
                       for i in range(len(pairs_k))])
    zk = jnp.zeros((B, bk, K, hd), dtype=jnp.float32)
    dk0 = jnp.zeros((nk, B, bk, K, hd), dtype=jnp.float32)

    def k_step(carry, xs):
        dk_acc, dv_acc, dkb, dvb = carry
        qi, ki, fst, lst = xs
        dk_acc = jnp.where(fst, zk, dk_acc)
        dv_acc = jnp.where(fst, zk, dv_acc)
        qb, kb, vb = idx(qc, qi), idx(kc, ki), idx(vc, ki)
        lse_b, D_b, do_b = idx(lse_c, qi), idx(D_c, qi), idx(do_c, qi)
        p = p_tile(qb, kb, lse_b, qi, ki)
        dv_acc = dv_acc + jnp.einsum("bkgqt,bkgqh->btkh", p, do_b)
        dp = jnp.einsum("bkgqh,btkh->bkgqt", do_b, vb)
        ds = p * (dp - D_b[..., None])
        dk_acc = dk_acc + jnp.einsum("bkgqt,bqkgh->btkh", ds, qb)
        dkb = jax.lax.dynamic_update_index_in_dim(
            dkb, jnp.where(lst, dk_acc, idx(dkb, ki)), ki, 0)
        dvb = jax.lax.dynamic_update_index_in_dim(
            dvb, jnp.where(lst, dv_acc, idx(dvb, ki)), ki, 0)
        return (dk_acc, dv_acc, dkb, dvb), None

    (_, _, dks, dvs), _ = jax.lax.scan(
        k_step, (zk, zk, dk0, dk0), (qi_k, ki_k, fst_k, lst_k))
    dk = jnp.moveaxis(dks, 0, 1).reshape(B, T, K, hd)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(B, T, K, hd)

    # ---- pass 2: dq (pairs grouped by q tile) -----------------------------
    pairs_q = _tile_pairs(nq, nk, bq, bk, causal, window)
    qi_q = jnp.array([p[0] for p in pairs_q], dtype=jnp.int32)
    ki_q = jnp.array([p[1] for p in pairs_q], dtype=jnp.int32)
    fst_q = jnp.array([i == 0 or pairs_q[i][0] != pairs_q[i - 1][0]
                       for i in range(len(pairs_q))])
    lst_q = jnp.array([i == len(pairs_q) - 1
                       or pairs_q[i][0] != pairs_q[i + 1][0]
                       for i in range(len(pairs_q))])
    zq = jnp.zeros((B, bq, K, Gd, hd), dtype=jnp.float32)
    dq0 = jnp.zeros((nq, B, bq, K, Gd, hd), dtype=jnp.float32)

    def q_step(carry, xs):
        dq_acc, dqb = carry
        qi, ki, fst, lst = xs
        dq_acc = jnp.where(fst, zq, dq_acc)
        qb, kb, vb = idx(qc, qi), idx(kc, ki), idx(vc, ki)
        lse_b, D_b, do_b = idx(lse_c, qi), idx(D_c, qi), idx(do_c, qi)
        p = p_tile(qb, kb, lse_b, qi, ki)
        dp = jnp.einsum("bkgqh,btkh->bkgqt", do_b, vb)
        ds = p * (dp - D_b[..., None])
        dq_acc = dq_acc + jnp.einsum("bkgqt,btkh->bqkgh", ds, kb)
        dqb = jax.lax.dynamic_update_index_in_dim(
            dqb, jnp.where(lst, dq_acc, idx(dqb, qi)), qi, 0)
        return (dq_acc, dqb), None

    (_, dqs), _ = jax.lax.scan(q_step, (zq, dq0),
                               (qi_q, ki_q, fst_q, lst_q))
    dq = jnp.moveaxis(dqs, 0, 1).reshape(B, S, K, Gd, hd)
    return (dq.astype(qg.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


_flash_core.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _sdpa_flash_jnp(cfg: ModelConfig, q: jax.Array, k: jax.Array,
                    v: jax.Array, *, causal: bool, window: int,
                    bq: int = 512, bk: int = 1024) -> jax.Array:
    """XLA-native flash attention: nested lax.scan over (q-chunks, k-chunks)
    with an online-softmax carry — the score matrix never materializes
    beyond one (bq × bk) tile per head, in EITHER direction (custom_vjp
    recomputes probability tiles in the backward pass, FlashAttention-2
    style). This is the TPU-honest lowering for long sequences when the
    Pallas kernel is off (dry-run / CPU) and mirrors what the Pallas kernel
    does in VMEM.

    No logit softcap support here — archs with softcap take the _sdpa path.
    """
    assert not cfg.attn_logit_softcap, "flash path has no softcap"
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    scale = hd ** -0.5
    bq = min(bq, S)
    bk = min(bk, T)
    assert S % bq == 0 and T % bk == 0, (S, T, bq, bk)
    # stay in the input dtype (bf16 on TPU) with fp32 accumulation inside
    # the tiles — no fp32 copies of q/k/v ever materialize
    qg = (q.reshape(B, S, K, G, hd) * jnp.asarray(scale, q.dtype))
    out = _flash_core(causal, window, bq, bk, G, qg, k, v)
    return out.reshape(B, S, H * hd).astype(v.dtype)


def full_mask(B: int, S: int, T: int, q_offset, causal: bool,
              window: int = 0) -> jax.Array:
    """(B, 1, S, T) boolean mask. q position i attends kv position j."""
    qpos = jnp.arange(S)[:, None] + q_offset          # absolute q positions
    kpos = jnp.arange(T)[None, :]
    m = jnp.ones((S, T), dtype=bool)
    if causal:
        m &= kpos <= qpos
    if window:
        m &= kpos > qpos - window
    return jnp.broadcast_to(m[None, None], (B, 1, S, T))


def attend_full(p: Dict, cfg: ModelConfig, x: jax.Array,
                angles: Optional[jax.Array], *, causal: bool = True,
                window: int = 0,
                kv: Optional[Tuple[jax.Array, jax.Array]] = None) -> jax.Array:
    """Train/prefill attention over the full sequence (or cross-attention
    when kv=(k_src, v_src) activations are given)."""
    B, S, _ = x.shape
    if kv is None:
        q, k, v = _qkv(p, cfg, x, angles)
        mask = full_mask(B, S, S, 0, causal, window)
    else:
        q = _split_heads(apply_linear(p["wq"], x), cfg.n_heads, cfg.head_dim)
        src_k, src_v = kv
        k = _split_heads(apply_linear(p["wk"], src_k), cfg.n_kv_heads, cfg.head_dim)
        v = _split_heads(apply_linear(p["wv"], src_v), cfg.n_kv_heads, cfg.head_dim)
        if angles is not None:
            q = rotary.apply_rope(q, angles)
        mask = jnp.ones((B, 1, S, k.shape[1]), dtype=bool)
    q = constrain(q, "batch", None, "heads", None)
    k = constrain(k, "batch", None, "kv_heads", None)
    v = constrain(v, "batch", None, "kv_heads", None)
    if use_pallas() and kv is None:
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=causal, window=window,
                                   softcap=cfg.attn_logit_softcap)
        out = out.reshape(B, S, cfg.q_dim)
    elif kv is None and _use_flash_jnp(S, k.shape[1]):
        out = _sdpa_flash_jnp(cfg, q, k, v, causal=causal, window=window)
    else:
        out = _sdpa(cfg, q, k, v, mask)
    out = constrain(out, "batch", None, "heads")
    return apply_linear(p["wo"], out)


def _use_flash_jnp(S: int, T: int, bq: int = 512, bk: int = 1024) -> bool:
    return (S * T >= FLASH_THRESHOLD
            and S % min(bq, S) == 0 and T % min(bk, T) == 0)


# ---------------------------------------------------------------------------
# Decode path (single new token against a cache)
# ---------------------------------------------------------------------------
def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, window: int,
                  dtype) -> Dict:
    """Full cache when window==0, else ring buffer of size window."""
    length = window if window else max_len
    shape = (batch, length, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype=dtype),
        "v": jnp.zeros(shape, dtype=dtype),
    }


def _attend_stored_and_new(cfg: ModelConfig, q: jax.Array, k: jax.Array,
                           v: jax.Array, valid: jax.Array, k_new: jax.Array,
                           v_new: jax.Array) -> jax.Array:
    """One query per sequence against its stored cache positions plus its
    own new K/V, which the cache does not hold yet. q: (B,1,H,hd); k/v:
    (B,T,K,hd); valid: (B,T) bool; k_new/v_new: (B,1,K,hd). One softmax
    over [stored | new], weights cast to v's dtype as in ``_sdpa``.
    Returns (B,1,H*hd)."""
    B, _, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    scale = hd ** -0.5
    qg = q.reshape(B, 1, K, G, hd)
    s = jnp.einsum("bskgh,btkh->bkgst", qg, k,
                   preferred_element_type=jnp.float32) * scale
    s_new = jnp.einsum("bskgh,btkh->bkgst", qg, k_new,
                       preferred_element_type=jnp.float32) * scale
    s = softcap(s, cfg.attn_logit_softcap)
    s_new = softcap(s_new, cfg.attn_logit_softcap)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), s_new)
    e = jnp.exp(s - m)
    e_new = jnp.exp(s_new - m)
    den = jnp.sum(e, axis=-1, keepdims=True) + e_new
    w, w_new = (e / den).astype(v.dtype), (e_new / den).astype(v.dtype)
    out = (jnp.einsum("bkgst,btkh->bskgh", w, v,
                      preferred_element_type=jnp.float32)
           + jnp.einsum("bkgst,btkh->bskgh", w_new, v_new,
                        preferred_element_type=jnp.float32))
    return out.astype(v.dtype).reshape(B, 1, H * hd)


def attend_decode(p: Dict, cfg: ModelConfig, x: jax.Array, pos: jax.Array,
                  cache: Dict, angles: Optional[jax.Array], *,
                  window: int = 0,
                  cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
                  table: Optional[jax.Array] = None,
                  ) -> Tuple[jax.Array, Dict]:
    """x: (B,1,D); pos: (B,) int32 per-sequence positions of the new token
    (-1 marks a dead/purged slot: its output row is exact zeros). With
    ``table`` (B, NB) int32 the cache is a paged arena — k/v leaves (P, bk,
    K, hd), logical block j of row b living in physical block table[b, j]
    (full-cache layout only).

    The cache is only read: each query attends its slot's stored positions
    before ``pos`` plus its own new K/V. Returns (out, row), ``row`` the
    new token's {"k", "v"} (B, K, hd) at the cache dtype, for
    ``write_kv_rows`` to put into the pool once every layer has read it."""
    B = x.shape[0]
    if cross_kv is not None:
        q = _split_heads(apply_linear(p["wq"], x), cfg.n_heads, cfg.head_dim)
        k, v = cross_kv     # precomputed (B, T_enc, K, hd)
        mask = jnp.ones((B, 1, 1, k.shape[1]), dtype=bool)
        out = _sdpa(cfg, q, k, v, mask)
        return apply_linear(p["wo"], out), {}

    q, k_new, v_new = _qkv(p, cfg, x, angles)
    k_new = k_new.astype(cache["k"].dtype)
    v_new = v_new.astype(cache["v"].dtype)
    row = {"k": k_new[:, 0], "v": v_new[:, 0]}
    if table is not None:
        assert not window, "paged cache is full-layout only"
        if use_pallas():
            # the kernel reads the new token from the arena: this layer's
            # copy of it, with the row written (the pool itself is written
            # once, by write_kv_rows)
            from repro.kernels import ops as kops
            blk, off, live = _paged_target(cache["k"].shape[1], table, pos)
            pb = jnp.where(live, blk, cache["k"].shape[0])   # P: dropped
            k = cache["k"].at[pb, off].set(k_new[:, 0], mode="drop")
            v = cache["v"].at[pb, off].set(v_new[:, 0], mode="drop")
            out = kops.decode_attention_paged(
                q[:, 0], k, v, pos + 1, table,
                softcap=cfg.attn_logit_softcap)
            out = out.reshape(B, 1, cfg.q_dim)
        else:
            # gather the arena back into the contiguous (B, NB*bk) layout:
            # same shapes and values as the contiguous path for every live
            # position, so the einsum results are bit-identical to it
            bkb, NB = cache["k"].shape[1], table.shape[1]
            L = NB * bkb
            kc = cache["k"][table].reshape(B, L, *cache["k"].shape[2:])
            vc = cache["v"][table].reshape(B, L, *cache["v"].shape[2:])
            valid = jnp.arange(L)[None, :] < pos[:, None]
            out = _attend_stored_and_new(cfg, q, kc, vc, valid, k_new, v_new)
        out = jnp.where((pos >= 0)[:, None, None], out, 0.0)
        return apply_linear(p["wo"], out), row

    L = cache["k"].shape[1]
    if use_pallas():
        # ragged decode kernel: per-slot lengths, block-skipped dead cache;
        # it reads the new token from this layer's copy of the cache
        from repro.kernels import ops as kops
        rows = jnp.arange(B)
        slot = jnp.mod(pos, L) if window else jnp.maximum(pos, 0)
        k = cache["k"].at[rows, slot].set(k_new[:, 0])
        v = cache["v"].at[rows, slot].set(v_new[:, 0])
        out = kops.decode_attention(q[:, 0], k, v, pos + 1, window=window,
                                    softcap=cfg.attn_logit_softcap)
        out = out.reshape(B, 1, cfg.q_dim)
    else:
        kpos = jnp.arange(L)[None, :]                  # (1, L)
        pcol = pos[:, None]
        if window:
            # ring buffer: slot s holds position pos - age, age in [1, L)
            # (age 0 is the slot the new token will take)
            age = jnp.mod(pcol - kpos, L)
            valid = (age >= 1) & (age <= pcol)
        else:
            valid = kpos < pcol
        k = constrain(cache["k"], "batch", "kv_seq" if not window else None,
                      None, None)
        v = constrain(cache["v"], "batch", "kv_seq" if not window else None,
                      None, None)
        out = _attend_stored_and_new(cfg, q, k, v, valid, k_new, v_new)
        # dead rows (pos = -1) attend nothing stored; match the kernel's
        # exact-zero emit instead of the new token's own value
        out = jnp.where((pos >= 0)[:, None, None], out, 0.0)
    return apply_linear(p["wo"], out), row


def _paged_target(bkb: int, table: jax.Array, pos: jax.Array):
    """Where row b's token at ``pos`` lives in a paged arena of ``bkb``-
    token blocks: (physical block, offset, live). Dead rows (pos < 0) and
    positions past the table are not live."""
    NB = table.shape[1]
    safe = jnp.maximum(pos, 0)
    live = (pos >= 0) & (safe // bkb < NB)
    blk = table[jnp.arange(table.shape[0]), jnp.minimum(safe // bkb, NB - 1)]
    return blk, safe % bkb, live


def write_kv_rows(pool: Dict, rows: Dict, pos: jax.Array, *,
                  window: int = 0,
                  table: Optional[jax.Array] = None) -> Dict:
    """Write one decode step's new K/V into a run's pool, in place.

    pool: {"k", "v"} leaves (n, B, L, K, hd), or (n, P, bk, K, hd) with
    ``table``; rows: the layers' ``attend_decode`` rows stacked, leaves
    (n, B, K, hd). Row b goes to slot ``pos`` (``pos mod L`` for a ring)
    of its own slot, or through the block table.

    One dynamic-update-slice per slot and leaf, each (n, 1, 1, K, hd): XLA
    updates a donated pool in place, in the layout it already has. A
    scatter over the slot axes, or reading the old row back, made it
    relayout the whole pool instead. So a row that must not land (a dead
    slot, pos < 0, or a position past its table) writes zeros where zeros
    already are: slot 0 of its own dead row (init and purge zero a row
    whose pos they set to -1), or the paged arena's null block 0, which is
    never allocated."""
    if table is not None:
        blk, off, live = _paged_target(pool["k"].shape[2], table, pos)
        blk, off = jnp.where(live, blk, 0), jnp.where(live, off, 0)
    else:
        L = pool["k"].shape[2]
        live = pos >= 0
        blk = jnp.arange(pos.shape[0], dtype=jnp.int32)
        off = jnp.where(live, jnp.mod(pos, L) if window else pos, 0)

    def one(leaf, new):
        zero = jnp.zeros((), jnp.int32)
        new = jnp.where(live[None, :, None, None], new, 0).astype(leaf.dtype)
        for b in range(pos.shape[0]):
            leaf = jax.lax.dynamic_update_slice(
                leaf, new[:, b][:, None, None], (zero, blk[b], off[b], zero,
                                                 zero))
        return leaf
    return {"k": one(pool["k"], rows["k"]), "v": one(pool["v"], rows["v"])}


def _cache_slots(k: jax.Array, lengths: jax.Array, L: int,
                 window: int) -> jax.Array:
    """Gather prefill K (or V) into the decode-cache slot layout.

    Full cache (window=0): slot s holds position s; live iff s < len.
    Ring: slot s (< window) holds the LATEST position p ≡ s (mod window)
    with p < len. A gather (one source position per slot, per row) instead
    of the old scatter, so per-row ragged lengths cost nothing extra.
    k: (B, S, K, hd) -> (B, L, K, hd)."""
    B, S = k.shape[0], k.shape[1]
    s = jnp.arange(L)[None, :]                               # (1, L)
    if window:
        cycles = (lengths[:, None] - 1 - s) // window        # floor div
        p = s + cycles * window
        valid = (p >= 0) & (s < window)
    else:
        p = jnp.broadcast_to(s, (B, L))
        valid = s < lengths[:, None]
    g = jnp.take_along_axis(k, jnp.clip(p, 0, S - 1)[..., None, None],
                            axis=1)
    return jnp.where(valid[..., None, None], g, jnp.zeros_like(g))


def attend_prefill(p: Dict, cfg: ModelConfig, x: jax.Array,
                   angles: Optional[jax.Array], *, causal: bool = True,
                   window: int = 0, max_len: int = 0,
                   lengths: Optional[jax.Array] = None,
                   ) -> Tuple[jax.Array, Dict]:
    """Full-sequence attention that also materializes the decode cache.

    Full cache: k/v placed at [0, S) of a (B, max_len, ...) buffer.
    Windowed: ring layout — the last `window` live tokens land at slot
    pos%window. `lengths` (B,) marks per-row live prompt lengths when the
    batch is right-padded to a bucket (continuous-batching admission);
    slots past a row's length are zeroed (and masked during decode).
    """
    B, S, _ = x.shape
    q, k, v = _qkv(p, cfg, x, angles)
    if use_pallas():
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=causal, window=window,
                                   softcap=cfg.attn_logit_softcap)
        out = out.reshape(B, S, cfg.q_dim)
    elif _use_flash_jnp(S, S):
        out = _sdpa_flash_jnp(cfg, q, k, v, causal=causal, window=window)
    else:
        mask = full_mask(B, S, S, 0, causal, window)
        out = _sdpa(cfg, q, k, v, mask)
    out = apply_linear(p["wo"], out)

    L = window if window else max_len
    if lengths is None:
        lengths = jnp.full((B,), S, dtype=jnp.int32)
    ck = _cache_slots(k, lengths, L, window).astype(k.dtype)
    cv = _cache_slots(v, lengths, L, window).astype(v.dtype)
    ck = constrain(ck, "batch", "kv_seq" if not window else None, None, None)
    cv = constrain(cv, "batch", "kv_seq" if not window else None, None, None)
    return out, {"k": ck, "v": cv}


def attend_prefill_ext(p: Dict, cfg: ModelConfig, x: jax.Array,
                       angles: Optional[jax.Array], arena: Dict,
                       table: jax.Array, starts: jax.Array,
                       lengths: jax.Array) -> Tuple[jax.Array, Dict]:
    """Tail prefill against a paged prefix (prefix-reuse admission).

    x: (B, St, D) embeds of the UNSHARED tail only — positions start at
    ``starts`` (the caller's rope angles already encode that offset).
    arena: paged k/v leaves (P, bk, K, hd); table: (B, NB) int32 block
    table whose first ``starts[b]`` positions hold the shared prefix;
    starts/lengths: (B,) int32 — prefix length and live TAIL length.

    Each tail query attends [shared prefix | causal tail]. Returns
    (out (B, St, q_dim), tail cache {k,v}: (B, St, K, hd) slot s = tail
    position s, zeroed past ``lengths`` — scatter_paged writes it through
    the table at absolute offsets). jnp path only: prefix-reuse serving is
    admission-rate bound, not prefill-flops bound (DESIGN.md §5.7)."""
    B, S, _ = x.shape
    q, k, v = _qkv(p, cfg, x, angles)
    bk = arena["k"].shape[1]
    NB = table.shape[1]
    Lp = NB * bk
    kp = arena["k"][table].reshape(B, Lp, *arena["k"].shape[2:])
    vp = arena["v"][table].reshape(B, Lp, *arena["v"].shape[2:])
    kk = jnp.concatenate([kp.astype(k.dtype), k], axis=1)   # (B, Lp+S, K, hd)
    vv = jnp.concatenate([vp.astype(v.dtype), v], axis=1)
    prefix_ok = jnp.arange(Lp)[None, :] < starts[:, None]   # (B, Lp)
    tail_ok = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]  # (S, S)
    mask = jnp.concatenate([
        jnp.broadcast_to(prefix_ok[:, None, :], (B, S, Lp)),
        jnp.broadcast_to(tail_ok[None], (B, S, S))], axis=2)
    out = _sdpa(cfg, q, kk, vv, mask[:, None])              # (B,1,S,Lp+S)
    out = apply_linear(p["wo"], out)
    ck = _cache_slots(k, lengths, S, 0).astype(k.dtype)
    cv = _cache_slots(v, lengths, S, 0).astype(v.dtype)
    return out, {"k": ck, "v": cv}
