"""Model assembly: embeddings, kind-run layer stacks (lax.scan), final norm,
LM head; full-sequence forward (train / prefill), cached decode step, and
encoder–decoder wiring.

A model is a sequence of layer *runs* — consecutive layers of the same kind
(see ``ModelConfig.layer_kinds``). Each run's parameters are stacked along a
leading axis and executed with ``lax.scan`` (small HLO, fast compile, remat
per block). A run's parameter tree may instead be a *list* of per-layer
trees — that is the deploy form of a D-Rank-compressed model whose per-layer
ranks differ — in which case the run executes as an unrolled Python loop.

Batch dictionary convention (everything optional except one input):
  tokens      (B, S) int32       — token ids (decoder side for enc-dec)
  embeds      (B, S, D) float    — precomputed frontend embeddings (vlm/audio
                                   stub); replaces token embedding
  positions   (B, S) or (3, B, S) int32 — rope / m-rope position ids
  enc_embeds  (B, T, D) float    — encoder input (audio stub)
  labels      (B, S) int32       — next-token targets (loss)
  loss_mask   (B, S) float       — optional per-token weights
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.dist.sharding import constrain
from repro.models import mamba, rotary, ssm
from repro.models.attention import (attend_decode, attend_full,
                                    attend_prefill, attend_prefill_ext,
                                    init_attention, init_kv_cache,
                                    write_kv_rows)
from repro.models.mlp import apply_mlp, apply_moe, init_mlp, init_moe
from repro.models.params import (Builder, Params, apply_linear, rms_norm,
                                 softcap)

Aux = Dict[str, jax.Array]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def _init_block(b: Builder, cfg: ModelConfig, kind: str, n: int,
                cross: bool = False) -> None:
    """One run of `n` layers of `kind` (stacked along leading dim)."""
    stack = (n,)
    b.rmsnorm("ln1", cfg.d_model, stack)
    if kind in ("attn", "swa", "hymba", "hymba_g"):
        init_attention(b.sub("attn"), cfg, stack)
    if kind in ("hymba", "hymba_g"):
        mamba.init_ssm(b.sub("ssm"), cfg, stack)
        mamba.init_hymba_combine(b, cfg, stack)
    if kind == "mlstm":
        ssm.init_mlstm(b.sub("mlstm"), cfg, stack)
    if kind == "slstm":
        ssm.init_slstm(b.sub("slstm"), cfg, stack)
    if cross:
        b.rmsnorm("ln_cross", cfg.d_model, stack)
        init_attention(b.sub("cross"), cfg, stack, cross=True)
    # FFN (attention-ish kinds only; ssm kinds carry their own projections)
    if kind in ("attn", "swa", "hymba", "hymba_g"):
        b.rmsnorm("ln2", cfg.d_model, stack)
        if cfg.moe.num_experts:
            init_moe(b, cfg, stack)
        elif cfg.d_ff:
            init_mlp(b.sub("mlp"), cfg, cfg.d_ff, stack)


def init_model(cfg: ModelConfig, key: jax.Array) -> Tuple[Params, Params]:
    """Returns (params, specs) — parallel pytrees."""
    b = Builder(key, param_dtype=jnp.dtype(cfg.param_dtype))
    b.normal("embed", (cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
             scale=1.0 / cfg.d_model ** 0.5)
    dec = b.sub("decoder")
    for r, (kind, n) in enumerate(cfg.layer_runs()):
        _init_block(dec.sub(f"run{r}"), cfg, kind, n,
                    cross=cfg.is_encoder_decoder)
    b.rmsnorm("final_norm", cfg.d_model)
    if not cfg.tie_embeddings:
        b.linear("lm_head", cfg.d_model, cfg.vocab_size, ("embed", "vocab"))
    if cfg.is_encoder_decoder:
        enc = b.sub("encoder")
        enc_cfg = cfg.replace(n_layers=cfg.n_encoder_layers,
                              sliding_window=0, local_global_pattern=(0, 0))
        for r, (kind, n) in enumerate(enc_cfg.layer_runs()):
            _init_block(enc.sub(f"run{r}"), enc_cfg, kind, n)
        enc.rmsnorm("enc_norm", cfg.d_model)
    return b.params, b.specs


def param_count(params: Params) -> int:
    return sum(x.size for x in jax.tree.leaves(params)
               if hasattr(x, "size"))


# ---------------------------------------------------------------------------
# Rope angles per kind
# ---------------------------------------------------------------------------
def _angles_for(cfg: ModelConfig, kind: str,
                positions: Optional[jax.Array]) -> Optional[jax.Array]:
    if cfg.rope_kind == "none" or positions is None:
        return None
    local = kind in ("swa", "hymba") and cfg.rope_theta_local > 0
    theta = cfg.rope_theta_local if local else cfg.rope_theta
    if cfg.rope_kind == "mrope":
        return rotary.mrope_angles(positions, cfg.head_dim, theta,
                                   cfg.mrope_sections)
    return rotary.rope_angles(positions, cfg.head_dim, theta)


def _kind_window(cfg: ModelConfig, kind: str) -> int:
    if kind in ("swa", "hymba"):
        return cfg.sliding_window
    return 0


# ---------------------------------------------------------------------------
# Full-sequence block application (train / eval)
# ---------------------------------------------------------------------------
def _block_fwd(kind: str, cfg: ModelConfig, p: Params, x: jax.Array,
               angles: Optional[jax.Array], enc_out: Optional[jax.Array],
               causal: bool) -> Tuple[jax.Array, jax.Array]:
    """Returns (x, moe_aux)."""
    aux = jnp.zeros((), dtype=jnp.float32)
    h = rms_norm(p["ln1"], x, cfg.norm_eps)
    win = _kind_window(cfg, kind)
    if kind in ("attn", "swa"):
        x = x + attend_full(p["attn"], cfg, h, angles, causal=causal,
                            window=win)
    elif kind in ("hymba", "hymba_g"):
        a = attend_full(p["attn"], cfg, h, angles, causal=causal, window=win)
        s = mamba.apply_ssm(p["ssm"], cfg, h)
        x = x + mamba.hymba_combine(p, cfg, a, s)
    elif kind == "mlstm":
        x = x + ssm.apply_mlstm(p["mlstm"], cfg, h)
    elif kind == "slstm":
        x = x + ssm.apply_slstm(p["slstm"], cfg, h)
    if "ln_cross" in p and enc_out is not None:
        h = rms_norm(p["ln_cross"], x, cfg.norm_eps)
        x = x + attend_full(p["cross"], cfg, h, None, kv=(enc_out, enc_out))
    if "ln2" in p:
        h = rms_norm(p["ln2"], x, cfg.norm_eps)
        if "moe" in p:
            out, aux = apply_moe(p, cfg, h)
            x = x + out
        elif "mlp" in p:
            x = x + apply_mlp(p["mlp"], cfg, h)
    return x, aux


def _run_layers(run_p: Any, cfg: ModelConfig, x: jax.Array, body) -> \
        Tuple[jax.Array, jax.Array]:
    """Apply a run. `body(p_layer, x) -> (x, aux)`. Handles the three param
    layouts: list (unrolled, compressed deploy), stacked+scan, stacked+index.
    """
    if isinstance(run_p, list):
        aux = jnp.zeros((), dtype=jnp.float32)
        for pl in run_p:
            x, a = body(pl, x)
            aux = aux + a
        return x, aux
    n = jax.tree.leaves(run_p)[0].shape[0]
    if not cfg.scan_layers:
        aux = jnp.zeros((), dtype=jnp.float32)
        for i in range(n):
            pl = jax.tree.map(lambda a: a[i], run_p)
            x, a = body(pl, x)
            aux = aux + a
        return x, aux

    def scan_body(carry, pl):
        x, aux = carry
        x, a = body(pl, x)
        return (x, aux + a), None

    wrapped = scan_body
    if cfg.remat != "none":
        # "block": save only layer boundaries, recompute the block in the
        # backward pass; "dots": additionally keep matmul outputs (a §Perf
        # memory/compute trade-off knob).
        policy = (jax.checkpoint_policies.dots_saveable
                  if cfg.remat == "dots"
                  else jax.checkpoint_policies.nothing_saveable)
        wrapped = jax.checkpoint(scan_body, policy=policy,
                                 prevent_cse=False)
    (x, aux), _ = jax.lax.scan(wrapped, (x, jnp.zeros((), jnp.float32)),
                               run_p)
    return x, aux


def _stack_forward(stack_p: Params, cfg: ModelConfig, x: jax.Array,
                   kinds_runs, positions, enc_out, causal) -> \
        Tuple[jax.Array, jax.Array]:
    aux = jnp.zeros((), dtype=jnp.float32)
    for r, (kind, n) in enumerate(kinds_runs):
        angles = _angles_for(cfg, kind, positions)
        body = functools.partial(_block_fwd, kind, cfg, angles=angles,
                                 enc_out=enc_out, causal=causal)
        bodyf = lambda pl, xx: body(pl, xx)
        x, a = _run_layers(stack_p[f"run{r}"], cfg, x, bodyf)
        x = constrain(x, "batch", "seq", None)
        aux = aux + a
    return x, aux


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------
def embed_tokens(params: Params, cfg: ModelConfig,
                 tokens: jax.Array) -> jax.Array:
    emb = params["embed"].astype(jnp.dtype(cfg.dtype))
    x = jnp.take(emb, tokens, axis=0)
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model ** 0.5, dtype=x.dtype)
    return x


def lm_logits(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"].astype(x.dtype).T
    else:
        logits = apply_linear(params["lm_head"], x)
    logits = softcap(logits, cfg.logit_softcap)
    return constrain(logits, "batch", "seq", "vocab")


def _default_positions(cfg: ModelConfig, batch: Dict) -> Optional[jax.Array]:
    if cfg.rope_kind == "none":
        return None
    if "positions" in batch:
        return batch["positions"]
    src = batch.get("tokens", batch.get("embeds"))
    B, S = src.shape[0], src.shape[1]
    return rotary.make_positions(B, S, cfg.rope_kind)


def encode(params: Params, cfg: ModelConfig, batch: Dict) -> jax.Array:
    """Encoder stack (enc-dec models). Input: enc_embeds (audio stub) or
    enc_tokens."""
    if "enc_embeds" in batch:
        x = batch["enc_embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        x = embed_tokens(params, cfg, batch["enc_tokens"])
    B, T, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    x = x + rotary.sinusoidal_embed(pos, cfg.d_model).astype(x.dtype)
    enc_cfg = cfg.replace(n_layers=cfg.n_encoder_layers, sliding_window=0,
                          local_global_pattern=(0, 0))
    x, _ = _stack_forward(params["encoder"], enc_cfg, x,
                          enc_cfg.layer_runs(), None, None, causal=False)
    return rms_norm(params["encoder"]["enc_norm"], x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# Forward (train / eval, full sequence)
# ---------------------------------------------------------------------------
def forward(params: Params, cfg: ModelConfig,
            batch: Dict) -> Tuple[jax.Array, Aux]:
    """Full-sequence forward. Returns (logits (B,S,V), aux)."""
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_out = encode(params, cfg, batch)
    if "embeds" in batch:
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        x = embed_tokens(params, cfg, batch["tokens"])
    if cfg.is_encoder_decoder:
        B, S, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        x = x + rotary.sinusoidal_embed(pos, cfg.d_model).astype(x.dtype)
    x = constrain(x, "batch", "seq", None)
    positions = _default_positions(cfg, batch)
    x, moe_aux = _stack_forward(params["decoder"], cfg, x, cfg.layer_runs(),
                                positions, enc_out, causal=True)
    logits = lm_logits(params, cfg, x)
    return logits, {"moe_aux": moe_aux}


def lm_loss(params: Params, cfg: ModelConfig,
            batch: Dict) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token CE. If batch has explicit `labels`, logits align 1:1 with
    them; otherwise labels are tokens shifted left by one."""
    logits, aux = forward(params, cfg, batch)
    if "labels" in batch:
        labels = batch["labels"]
    else:
        labels = jnp.pad(batch["tokens"][:, 1:], ((0, 0), (0, 1)),
                         constant_values=-1)
    mask = (labels >= 0).astype(jnp.float32)
    if "loss_mask" in batch:
        mask = mask * batch["loss_mask"]
    labels_c = jnp.maximum(labels, 0)
    lf = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels_c[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = nll.sum() / denom
    acc = (jnp.argmax(lf, -1) == labels_c).astype(jnp.float32) * mask
    metrics = {
        "loss": loss,
        "ppl_log": loss,                      # exp() applied host-side
        "accuracy": acc.sum() / denom,
        "tokens": mask.sum(),
    }
    if cfg.moe.num_experts:
        loss = loss + cfg.moe.aux_loss_weight * aux["moe_aux"] / max(
            1, cfg.n_layers)
        metrics["moe_aux"] = aux["moe_aux"]
    return loss, metrics


# ---------------------------------------------------------------------------
# Decode (single step with caches)
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               enc_len: int = 0) -> Dict:
    """Cache pytree: per-run stacked caches + per-sequence positions."""
    dtype = jnp.dtype(cfg.dtype)
    runs: Dict[str, Any] = {}
    for r, (kind, n) in enumerate(cfg.layer_runs()):
        win = _kind_window(cfg, kind)
        entry: Dict[str, Any] = {}
        if kind in ("attn", "swa", "hymba", "hymba_g"):
            kv = [init_kv_cache(cfg, batch, max_len, win, dtype)
                  for _ in range(n)]
            entry["kv"] = jax.tree.map(lambda *a: jnp.stack(a), *kv)
        if kind in ("hymba", "hymba_g"):
            ss = [mamba.init_ssm_cache(cfg, batch, dtype) for _ in range(n)]
            entry["ssm"] = jax.tree.map(lambda *a: jnp.stack(a), *ss)
        if kind == "mlstm":
            ms = [ssm.init_mlstm_cache(cfg, batch, dtype) for _ in range(n)]
            entry["mlstm"] = jax.tree.map(lambda *a: jnp.stack(a), *ms)
        if kind == "slstm":
            sl = [ssm.init_slstm_cache(cfg, batch, dtype) for _ in range(n)]
            entry["slstm"] = jax.tree.map(lambda *a: jnp.stack(a), *sl)
        if cfg.is_encoder_decoder:
            entry["cross_kv"] = {
                "k": jnp.zeros((n, batch, enc_len, cfg.n_kv_heads,
                                cfg.head_dim), dtype=dtype),
                "v": jnp.zeros((n, batch, enc_len, cfg.n_kv_heads,
                                cfg.head_dim), dtype=dtype),
            }
        runs[f"run{r}"] = entry
    # pos = -1 marks a dead slot (never admitted / purged): decode leaves it
    # parked at -1 and emits exact-zero attention for it. Admission scatter
    # overwrites pos with the prefilled length.
    return {"runs": runs, "pos": jnp.full((batch,), -1, dtype=jnp.int32)}


def init_cache_paged(cfg: ModelConfig, batch: int, blocks: int,
                     block_len: int) -> Dict:
    """Paged cache pytree: one flat KV block arena per run instead of the
    per-slot (batch, max_len) pool. k/v leaves are (n, blocks, block_len,
    KV, hd); physical block 0 is reserved as the never-allocated null block
    (the sentinel target for dead table entries). Logical-to-physical
    mapping lives OUTSIDE the pytree in the engine's (batch, NB) block
    table. Pure-attention stacks only — recurrent kinds have no paged
    layout (and windowed kinds keep the ring cache)."""
    dtype = jnp.dtype(cfg.dtype)
    assert not cfg.is_encoder_decoder, "paged cache: decoder-only"
    runs: Dict[str, Any] = {}
    for r, (kind, n) in enumerate(cfg.layer_runs()):
        assert kind == "attn", (
            f"paged cache supports pure-attention stacks only, got {kind}")
        runs[f"run{r}"] = {"kv": {
            "k": jnp.zeros((n, blocks, block_len, cfg.n_kv_heads,
                            cfg.head_dim), dtype=dtype),
            "v": jnp.zeros((n, blocks, block_len, cfg.n_kv_heads,
                            cfg.head_dim), dtype=dtype),
        }}
    return {"runs": runs, "pos": jnp.full((batch,), -1, dtype=jnp.int32)}


def _block_decode(kind: str, cfg: ModelConfig, p: Params, cache: Dict,
                  x: jax.Array, pos: jax.Array,
                  angles: Optional[jax.Array],
                  table: Optional[jax.Array] = None) -> Tuple[jax.Array, Dict]:
    """One layer of the decode step. Reads the layer's cache; returns the
    new x and the layer's update: the new token's K/V row under "kv"
    (written into the pool after the layer loop), and the new recurrent
    states in full."""
    upd: Dict[str, Any] = {}
    h = rms_norm(p["ln1"], x, cfg.norm_eps)
    win = _kind_window(cfg, kind)
    if kind in ("attn", "swa"):
        out, upd["kv"] = attend_decode(p["attn"], cfg, h, pos, cache["kv"],
                                       angles, window=win, table=table)
        x = x + out
    elif kind in ("hymba", "hymba_g"):
        a, upd["kv"] = attend_decode(p["attn"], cfg, h, pos, cache["kv"],
                                     angles, window=win, table=table)
        s, upd["ssm"] = mamba.decode_ssm(p["ssm"], cfg, h, cache["ssm"])
        x = x + mamba.hymba_combine(p, cfg, a, s)
    elif kind == "mlstm":
        out, upd["mlstm"] = ssm.decode_mlstm(p["mlstm"], cfg, h,
                                             cache["mlstm"])
        x = x + out
    elif kind == "slstm":
        out, upd["slstm"] = ssm.decode_slstm(p["slstm"], cfg, h,
                                             cache["slstm"])
        x = x + out
    if "ln_cross" in p and "cross_kv" in cache:
        h = rms_norm(p["ln_cross"], x, cfg.norm_eps)
        ckv = (cache["cross_kv"]["k"], cache["cross_kv"]["v"])
        out, _ = attend_decode(p["cross"], cfg, h, pos, {}, None,
                               cross_kv=ckv)
        x = x + out
    if "ln2" in p:
        h = rms_norm(p["ln2"], x, cfg.norm_eps)
        if "moe" in p:
            out, _ = apply_moe(p, cfg, h)
            x = x + out
        elif "mlp" in p:
            x = x + apply_mlp(p["mlp"], cfg, h)
    return x, upd


def decode_step(params: Params, cfg: ModelConfig, cache: Dict,
                tokens_or_embeds: jax.Array,
                positions: Optional[jax.Array] = None,
                table: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, Dict]:
    """One new token per sequence. tokens (B,1) int or embeds (B,1,D).
    With `table` (B, NB) int32 the cache is a paged arena (see
    init_cache_paged) and every KV read/write indirects through it.
    Dead slots (pos = -1) neither advance nor write: their logits row is
    whatever the dead residual stream produces and is ignored upstream.

    The layers only read the KV pool; each run's new K/V rows are written
    after its layer loop, one row per slot (``write_kv_rows``). So a
    caller that donates ``cache`` gets the pool updated in place: the
    step writes 2 × layers × slots rows and copies nothing else.
    Returns (logits (B,1,V), new cache)."""
    pos = cache["pos"]
    if tokens_or_embeds.dtype in (jnp.int32, jnp.int64):
        x = embed_tokens(params, cfg, tokens_or_embeds)
    else:
        x = tokens_or_embeds.astype(jnp.dtype(cfg.dtype))
    if cfg.is_encoder_decoder:
        x = x + rotary.sinusoidal_embed(pos[:, None], cfg.d_model
                                        ).astype(x.dtype)
    new_runs: Dict[str, Any] = {}
    for r, (kind, n) in enumerate(cfg.layer_runs()):
        if positions is not None:
            rp = positions
        elif cfg.rope_kind == "mrope":
            rp = jnp.broadcast_to(pos[None, :, None], (3, pos.shape[0], 1))
        else:
            rp = pos[:, None]
        angles = _angles_for(cfg, kind, rp)
        run_p = params["decoder"][f"run{r}"]
        run_c = cache["runs"][f"run{r}"]

        def layer(pl, cl, xx):
            return _block_decode(kind, cfg, pl, cl, xx, pos, angles, table)

        if isinstance(run_p, list) or not cfg.scan_layers:
            ups = []
            for i in range(n):
                pl = (run_p[i] if isinstance(run_p, list)
                      else jax.tree.map(lambda a: a[i], run_p))
                cl = jax.tree.map(lambda a: a[i], run_c)
                x, u = layer(pl, cl, x)
                ups.append(u)
            upd = jax.tree.map(lambda *a: jnp.stack(a), *ups)
        else:
            def body(xx, pc):
                return layer(pc[0], pc[1], xx)
            x, upd = jax.lax.scan(body, x, (run_p, run_c))
        entry = dict(run_c, **upd)
        if "kv" in upd:
            entry["kv"] = write_kv_rows(run_c["kv"], upd["kv"], pos,
                                        window=_kind_window(cfg, kind),
                                        table=table)
        new_runs[f"run{r}"] = entry
    logits = lm_logits(params, cfg, x)
    # dead slots (pos = -1) stay dead; live slots advance
    return logits, {"runs": new_runs,
                    "pos": jnp.where(pos >= 0, pos + 1, pos)}


# ---------------------------------------------------------------------------
# Prefill (full sequence -> cache)
# ---------------------------------------------------------------------------
def _split_heads(x: jax.Array, n: int, hd: int) -> jax.Array:
    return x.reshape(*x.shape[:-1], n, hd)


def _block_prefill(kind: str, cfg: ModelConfig, p: Params, x: jax.Array,
                   angles, max_len: int, enc_out,
                   lengths: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, Dict]:
    cache: Dict[str, Any] = {}
    h = rms_norm(p["ln1"], x, cfg.norm_eps)
    win = _kind_window(cfg, kind)
    if kind in ("attn", "swa"):
        out, kv = attend_prefill(p["attn"], cfg, h, angles, causal=True,
                                 window=win, max_len=max_len,
                                 lengths=lengths)
        x = x + out
        cache["kv"] = kv
    elif kind in ("hymba", "hymba_g"):
        a, kv = attend_prefill(p["attn"], cfg, h, angles, causal=True,
                               window=win, max_len=max_len,
                               lengths=lengths)
        s, sst = mamba.apply_ssm(p["ssm"], cfg, h, return_cache=True)
        x = x + mamba.hymba_combine(p, cfg, a, s)
        cache["kv"], cache["ssm"] = kv, sst
    elif kind == "mlstm":
        out, mst = ssm.apply_mlstm(p["mlstm"], cfg, h, return_cache=True)
        x = x + out
        cache["mlstm"] = mst
    elif kind == "slstm":
        out, sst = ssm.apply_slstm(p["slstm"], cfg, h, return_cache=True)
        x = x + out
        cache["slstm"] = sst
    if "ln_cross" in p and enc_out is not None:
        hc = rms_norm(p["ln_cross"], x, cfg.norm_eps)
        x = x + attend_full(p["cross"], cfg, hc, None, kv=(enc_out, enc_out))
        # materialize per-layer cross K/V once for the decode loop
        cache["cross_kv"] = {
            "k": _split_heads(apply_linear(p["cross"]["wk"], enc_out),
                              cfg.n_kv_heads, cfg.head_dim),
            "v": _split_heads(apply_linear(p["cross"]["wv"], enc_out),
                              cfg.n_kv_heads, cfg.head_dim),
        }
    if "ln2" in p:
        h = rms_norm(p["ln2"], x, cfg.norm_eps)
        if "moe" in p:
            out, _ = apply_moe(p, cfg, h)
            x = x + out
        elif "mlp" in p:
            x = x + apply_mlp(p["mlp"], cfg, h)
    return x, cache


def prefill(params: Params, cfg: ModelConfig, batch: Dict,
            max_len: int) -> Tuple[jax.Array, Dict]:
    """Process the prompt, build the decode cache. Returns
    (logits of the last live position (B, 1, V), cache).

    `batch["lengths"]` (B,) int32, optional: per-row live prompt lengths
    when prompts are right-padded to a common bucket (continuous-batching
    admission). Cache slots past a row's length are zeroed/masked, the
    returned logits are each row's last LIVE position, and cache `pos`
    starts at the per-row length. Recurrent-state kinds (ssm/lstm) carry
    state through padded steps, so callers only pass `lengths` for pure
    attention stacks — see ContinuousBatcher."""
    lengths = batch.get("lengths")
    enc_out = encode(params, cfg, batch) if cfg.is_encoder_decoder else None
    if "embeds" in batch:
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        x = embed_tokens(params, cfg, batch["tokens"])
    B, S, _ = x.shape
    if cfg.is_encoder_decoder:
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        x = x + rotary.sinusoidal_embed(pos, cfg.d_model).astype(x.dtype)
    x = constrain(x, "batch", "seq", None)
    positions = _default_positions(cfg, batch)

    new_runs: Dict[str, Any] = {}
    for r, (kind, n) in enumerate(cfg.layer_runs()):
        angles = _angles_for(cfg, kind, positions)
        run_p = params["decoder"][f"run{r}"]

        def body(pl, xx):
            return _block_prefill(kind, cfg, pl, xx, angles, max_len,
                                  enc_out, lengths)

        if isinstance(run_p, list):
            caches = []
            for pl in run_p:
                x, c = body(pl, x)
                caches.append(c)
            new_runs[f"run{r}"] = jax.tree.map(lambda *a: jnp.stack(a),
                                               *caches)
        elif not cfg.scan_layers:
            caches = []
            for i in range(n):
                pl = jax.tree.map(lambda a: a[i], run_p)
                x, c = body(pl, x)
                caches.append(c)
            new_runs[f"run{r}"] = jax.tree.map(lambda *a: jnp.stack(a),
                                               *caches)
        else:
            def scan_body(xx, pl):
                return body(pl, xx)
            x, nc = jax.lax.scan(scan_body, x, run_p)
            new_runs[f"run{r}"] = nc
        x = constrain(x, "batch", "seq", None)
    if lengths is None:
        x_last = x[:, -1:]
        pos0 = jnp.full((B,), S, dtype=jnp.int32)
    else:
        x_last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)
        pos0 = lengths.astype(jnp.int32)
    logits = lm_logits(params, cfg, x_last)
    cache = {"runs": new_runs, "pos": pos0}
    return logits, cache


def prefill_ext(params: Params, cfg: ModelConfig, batch: Dict,
                arena: Dict, table: jax.Array) -> Tuple[jax.Array, Dict]:
    """Tail prefill for prefix-reuse admission (paged pool only): process
    the UNSHARED tail of each prompt against a shared prefix already
    resident in the paged arena.

    batch: tokens (B, St) right-padded tail token ids; lengths (B,) int32
    live tail lengths; starts (B,) int32 prefix lengths (tail position i is
    absolute position starts + i). arena: init_cache_paged pytree; table:
    (B, NB) int32 block table (first `starts[b]` positions = the prefix).

    Returns (logits of each row's last live tail position (B, 1, V), tail
    cache) — tail cache leaves are (n, B, St, KV, hd) in slot layout (slot
    s = tail position s), for scatter_paged to write through the table at
    the absolute offsets. Cache `pos` = starts + lengths (total live
    length). Pure-attention stacks only."""
    lengths = batch["lengths"].astype(jnp.int32)
    starts = batch["starts"].astype(jnp.int32)
    x = embed_tokens(params, cfg, batch["tokens"])
    B, S, _ = x.shape
    x = constrain(x, "batch", "seq", None)
    positions = None
    if cfg.rope_kind != "none":
        positions = starts[:, None] + jnp.arange(S)[None, :]

    new_runs: Dict[str, Any] = {}
    for r, (kind, n) in enumerate(cfg.layer_runs()):
        assert kind == "attn", (
            f"prefill_ext supports pure-attention stacks only, got {kind}")
        angles = _angles_for(cfg, kind, positions)
        run_p = params["decoder"][f"run{r}"]
        arena_c = arena["runs"][f"run{r}"]

        def body(pl, cl, xx):
            h = rms_norm(pl["ln1"], xx, cfg.norm_eps)
            out, kv = attend_prefill_ext(pl["attn"], cfg, h, angles,
                                         cl["kv"], table, starts, lengths)
            xx = xx + out
            if "ln2" in pl:
                h = rms_norm(pl["ln2"], xx, cfg.norm_eps)
                if "moe" in pl:
                    out, _ = apply_moe(pl, cfg, h)
                    xx = xx + out
                elif "mlp" in pl:
                    xx = xx + apply_mlp(pl["mlp"], cfg, h)
            return xx, {"kv": kv}

        if isinstance(run_p, list):
            caches = []
            for i, pl in enumerate(run_p):
                cl = jax.tree.map(lambda a: a[i], arena_c)
                x, c = body(pl, cl, x)
                caches.append(c)
            new_runs[f"run{r}"] = jax.tree.map(lambda *a: jnp.stack(a),
                                               *caches)
        elif not cfg.scan_layers:
            caches = []
            for i in range(n):
                pl = jax.tree.map(lambda a: a[i], run_p)
                cl = jax.tree.map(lambda a: a[i], arena_c)
                x, c = body(pl, cl, x)
                caches.append(c)
            new_runs[f"run{r}"] = jax.tree.map(lambda *a: jnp.stack(a),
                                               *caches)
        else:
            def scan_body(xx, pc):
                pl, cl = pc
                return body(pl, cl, xx)
            x, nc = jax.lax.scan(scan_body, x, (run_p, arena_c))
            new_runs[f"run{r}"] = nc
        x = constrain(x, "batch", "seq", None)
    x_last = jnp.take_along_axis(x, (jnp.maximum(lengths, 1) - 1)
                                 [:, None, None], axis=1)
    logits = lm_logits(params, cfg, x_last)
    return logits, {"runs": new_runs, "pos": starts + lengths}
