"""Prefill + token-by-token decode must reproduce the full-sequence forward
logits — for every cache type (full KV, sliding-window ring, mLSTM state,
mamba/SSD state, enc-dec cross-attention) — and the decode step's in-place
row writes must match a reference that writes into a copied pool."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import attention as A
from repro.models import transformer as T
from repro.models.mlp import apply_mlp
from repro.models.params import apply_linear, rms_norm
from repro.serve import aot as aotlib

from conftest import make_batch

# llama-mini: full KV.  gemma3: local/global mix + ring buffer + geglu.
# hymba: parallel attn+ssm, ring + state.  xlstm: pure state.
# seamless: enc-dec cross attention.  granite: MoE decode.
ARCHS = ["llama-mini", "gemma3-12b", "hymba-1.5b", "xlstm-350m",
         "seamless-m4t-medium", "granite-moe-1b-a400m", "qwen3-4b"]


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_forward(arch, rng):
    cfg = get_config(arch).reduced()
    # ring buffers only exercise wraparound if seq > window
    S, split = 24, 12
    batch = make_batch(cfg, jax.random.fold_in(rng, 3), batch=2, seq=S)
    params, _ = T.init_model(cfg, rng)

    full_logits, _ = T.forward(params, cfg, batch)

    prompt = {k: (v[:, :split] if k in ("tokens", "embeds") else v)
              for k, v in batch.items()}
    lp, cache = T.prefill(params, cfg, prompt, max_len=S + 8)
    outs = [lp]
    stream = batch.get("tokens")
    for t in range(split, S):
        tok = stream[:, t:t + 1]
        lg, cache = T.decode_step(params, cfg, cache, tok)
        outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)

    ref = full_logits[:, split - 1:S]
    err = float(jnp.max(jnp.abs(dec - ref)))
    assert err < 2e-3, err


@pytest.mark.slow           # ~80s: longest single test (3× window decode)
def test_decode_window_wraparound(rng):
    """Sliding-window ring cache stays exact long past the window size."""
    cfg = get_config("gemma3-12b").reduced()
    assert cfg.sliding_window == 8
    S = 4 * cfg.sliding_window
    batch = make_batch(cfg, jax.random.fold_in(rng, 4), batch=1, seq=S)
    params, _ = T.init_model(cfg, rng)
    full_logits, _ = T.forward(params, cfg, batch)

    lp, cache = T.prefill(params, cfg,
                          {"tokens": batch["tokens"][:, :1]}, max_len=S)
    outs = [lp]
    for t in range(1, S):
        lg, cache = T.decode_step(params, cfg, cache,
                                  batch["tokens"][:, t:t + 1])
        outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)
    err = float(jnp.max(jnp.abs(dec - full_logits)))
    assert err < 2e-3, err


# ---------------------------------------------------------------------------
# The in-place write path against a step-by-step reference
# ---------------------------------------------------------------------------
def _ref_attend(p, cfg, h, pos, kv, angles, window):
    """The reference: write the new row into a copy of the layer's cache
    (dead rows keep what they hold), then attend over the copy with the
    new token in its slot."""
    q, k_new, v_new = A._qkv(p, cfg, h, angles)
    B, L = h.shape[0], kv["k"].shape[1]
    rows = jnp.arange(B)
    live = pos >= 0
    slot = jnp.where(live, jnp.mod(pos, L) if window else pos, 0)

    def put(c, new):
        new = jnp.where(live[:, None, None], new[:, 0].astype(c.dtype),
                        c[rows, slot])
        return c.at[rows, slot].set(new)
    k, v = put(kv["k"], k_new), put(kv["v"], v_new)
    kpos, pcol = jnp.arange(L)[None, :], pos[:, None]
    if window:
        valid = jnp.mod(pcol - kpos, L) < jnp.minimum(pcol + 1, L)
    else:
        valid = kpos <= pcol
    out = A._sdpa(cfg, q, k, v, valid[:, None, None, :])
    out = jnp.where(live[:, None, None], out, 0.0)
    return apply_linear(p["wo"], out), {"k": k, "v": v}


def _ref_step(params, cfg, cache, tok):
    """One decode step of an attention-only decoder through
    ``_ref_attend``, layer by layer."""
    pos = cache["pos"]
    x = T.embed_tokens(params, cfg, tok)
    runs = {}
    for r, (kind, n) in enumerate(cfg.layer_runs()):
        angles = T._angles_for(cfg, kind, pos[:, None])
        run_p, run_c = params["decoder"][f"run{r}"], cache["runs"][f"run{r}"]
        kvs = []
        for i in range(n):
            pl = jax.tree.map(lambda a: a[i], run_p)
            h = rms_norm(pl["ln1"], x, cfg.norm_eps)
            out, kv = _ref_attend(pl["attn"], cfg, h, pos,
                                  jax.tree.map(lambda a: a[i], run_c["kv"]),
                                  angles, T._kind_window(cfg, kind))
            x = x + out
            x = x + apply_mlp(pl["mlp"], cfg, rms_norm(pl["ln2"], x,
                                                       cfg.norm_eps))
            kvs.append(kv)
        runs[f"run{r}"] = {"kv": jax.tree.map(lambda *a: jnp.stack(a), *kvs)}
    return (T.lm_logits(params, cfg, x),
            {"runs": runs, "pos": jnp.where(pos >= 0, pos + 1, pos)})


def _steppers(cfg):
    return (jax.jit(lambda p, c, t: T.decode_step(p, cfg, c, t)),
            jax.jit(lambda p, c, t: _ref_step(p, cfg, c, t)))


def _assert_close(a, b, tol=1e-4):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.shape == y.shape
        assert float(jnp.max(jnp.abs(x - y))) < tol


def test_in_place_writes_match_reference_past_ring_wraparound(rng):
    """Local layers keep a ring of the window's size: decoding three
    windows past the prompt, every step's logits and whole cache match
    the reference that writes into a copied pool."""
    cfg = get_config("gemma3-12b").reduced()
    W = cfg.sliding_window
    params, _ = T.init_model(cfg, rng)
    toks = jax.random.randint(jax.random.fold_in(rng, 5), (2, 5 + 3 * W),
                              0, cfg.vocab_size)
    _, cache = T.prefill(params, cfg, {"tokens": toks[:, :5]},
                         max_len=8 + 3 * W)
    ref = cache
    step, ref_step = _steppers(cfg)
    for t in range(5, toks.shape[1]):
        lg, cache = step(params, cache, toks[:, t:t + 1])
        lr, ref = ref_step(params, ref, toks[:, t:t + 1])
        _assert_close(lg, lr)
        _assert_close(cache, ref)
    assert int(cache["pos"][0]) > 3 * W


def test_dead_slots_stay_exact_zeros_and_unwritten(rng):
    """A purged slot (pos = -1) and a slot never admitted are written by
    no decode step: their rows stay exact zeros, their pos stays -1, and
    the live slot beside them decodes as the reference does."""
    cfg = get_config("llama-mini").reduced()
    params, _ = T.init_model(cfg, rng)
    toks = jax.random.randint(jax.random.fold_in(rng, 6), (3, 12), 0,
                              cfg.vocab_size)
    _, pre = T.prefill(params, cfg, {"tokens": toks[:2, :4]}, max_len=16)
    cache = aotlib.scatter_rows(T.init_cache(cfg, 3, 16), pre,
                                jnp.asarray([0, 1], jnp.int32))
    cache = aotlib.purge_rows(cache, jnp.asarray([1], jnp.int32))
    ref = cache
    step, ref_step = _steppers(cfg)
    for t in range(4, 12):
        lg, cache = step(params, cache, toks[:, t:t + 1])
        lr, ref = ref_step(params, ref, toks[:, t:t + 1])
        _assert_close(lg[0], lr[0])
        _assert_close(cache, ref)
        for leaf in jax.tree.leaves(cache["runs"]):
            assert not jnp.any(leaf[:, 1:]), "a dead row was written"
        assert cache["pos"].tolist() == [t + 1, -1, -1]


def test_purged_slot_reused_matches_reference(rng):
    """A slot decoded, purged, left dead for a few steps and then reused by
    a fresh admission decodes as the reference does at every step, and
    none of its first tenant's rows survive."""
    cfg = get_config("llama-mini").reduced()
    params, _ = T.init_model(cfg, rng)
    toks = jax.random.randint(jax.random.fold_in(rng, 7), (2, 14), 0,
                              cfg.vocab_size)
    fresh = jax.random.randint(jax.random.fold_in(rng, 8), (1, 3), 0,
                               cfg.vocab_size)
    _, cache = T.prefill(params, cfg, {"tokens": toks[:, :6]}, max_len=16)
    ref = cache
    step, ref_step = _steppers(cfg)
    for t in range(6, 14):
        if t == 9:
            slot = jnp.asarray([0], jnp.int32)
            cache = aotlib.purge_rows(cache, slot)
            ref = aotlib.purge_rows(ref, slot)
        if t == 11:
            _, pre = T.prefill(params, cfg, {"tokens": fresh}, max_len=16)
            slot = jnp.asarray([0], jnp.int32)
            cache = aotlib.scatter_rows(cache, pre, slot)
            ref = aotlib.scatter_rows(ref, pre, slot)
        tok = toks[:, t:t + 1]
        lg, cache = step(params, cache, tok)
        lr, ref = ref_step(params, ref, tok)
        live = [b for b in range(2) if int(ref["pos"][b]) >= 0]
        _assert_close(lg[jnp.asarray(live)], lr[jnp.asarray(live)])
        _assert_close(cache, ref)
    # the reused slot holds its prompt and the three tokens decoded since
    assert int(cache["pos"][0]) == 3 + 3
    for leaf in jax.tree.leaves(cache["runs"]):
        assert not jnp.any(leaf[:, 0, 6:])
