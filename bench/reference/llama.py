"""Plain float32 reference of a Llama-architecture decoder (SmolLM-360M's
family): token embedding, ``n_layers`` pre-norm blocks of grouped-query
attention with rotary positions and a SwiGLU MLP, final RMSNorm, output
head tied to the embedding (or a linear of its own where it is not).

It imports nothing of the program under test. It has no cache, no
batching, no padding and no kernels: one sequence, every position, every
matmul at ``Precision.HIGHEST``. A linear is ``{"w": (d_in, d_out)}`` or
a factorized ``{"B": (d_in, k), "C": (k, d_out)}`` applied as
``(x @ B) @ C``.

Departures from the published model card: none in the equations. Rotary
positions rotate the first half of each head against the second (the
GPT-NeoX layout Llama checkpoints use in Hugging Face transformers).

``sizes`` keys: ``n_layers d_model n_heads n_kv_heads head_dim d_ff
vocab_size rope_theta norm_eps``.

Params layout (``head`` only where the output head is not tied)::

    {"embed": (V, D), "final_norm": (D,), "head": linear,
     "layers": [{"ln1": (D,), "ln2": (D,), "q", "k", "v", "o",
                 "gate", "up", "down": linear}, ...]}

Besides the reference itself, this module is what the harness knows of
the architecture (``bench/harness.py``): ``program_config``, ``sizes``,
``from_program``, ``linear_counts``, ``work`` and ``TINY_MODEL``.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from bench import counts

HIGHEST = jax.lax.Precision.HIGHEST
Quant = Optional[Callable[[jax.Array], jax.Array]]


def int8_quant(x: jax.Array) -> jax.Array:
    """Round ``x`` to symmetric int8 with one per-tensor scale, returned
    in float32: an operand computed at int8 (the precision below the
    configuration's bfloat16)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _q(x, quant: Quant):
    x = x.astype(jnp.float32)
    return quant(x) if quant is not None else x


def _linear(p: Dict, x: jax.Array, quant: Quant) -> jax.Array:
    if "B" in p:
        h = jnp.matmul(_q(x, quant), _q(p["B"], quant), precision=HIGHEST)
        return jnp.matmul(_q(h, quant), _q(p["C"], quant), precision=HIGHEST)
    return jnp.matmul(_q(x, quant), _q(p["w"], quant), precision=HIGHEST)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    """x (S, H, hd): rotate first half against second half."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, None].astype(jnp.float32) * freqs       # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(lp: Dict, sizes: Dict, x: jax.Array,
          quant: Quant = None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decoder block over the residual stream ``x`` (S, D): the new
    stream, and the input of each of the block's linears."""
    S = x.shape[0]
    H, K, hd = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
    eps = sizes["norm_eps"]
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]                       # (S, T)
    h = _rms(x, lp["ln1"], eps)
    q = _linear(lp["q"], h, quant).reshape(S, H, hd)
    k = _linear(lp["k"], h, quant).reshape(S, K, hd)
    v = _linear(lp["v"], h, quant).reshape(S, K, hd)
    q = _rope(q, pos, sizes["rope_theta"])
    k = _rope(k, pos, sizes["rope_theta"])
    kh = jnp.repeat(k, H // K, axis=1)           # head h reads kv head h // G
    vh = jnp.repeat(v, H // K, axis=1)
    s = jnp.einsum("shd,thd->hst", _q(q, quant), _q(kh, quant),
                   precision=HIGHEST) * hd ** -0.5
    w = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("hst,thd->shd", _q(w, quant), _q(vh, quant),
                   precision=HIGHEST).reshape(S, H * hd)
    x = x + _linear(lp["o"], a, quant)
    h2 = _rms(x, lp["ln2"], eps)
    m = (jax.nn.silu(_linear(lp["gate"], h2, quant))
         * _linear(lp["up"], h2, quant))
    x = x + _linear(lp["down"], m, quant)
    return x, {"q": h, "k": h, "v": h, "o": a, "gate": h2, "up": h2,
               "down": m}


def logits(params: Dict, sizes: Dict, tokens: jax.Array,
           quant: Quant = None) -> jax.Array:
    """tokens (S,) int32 -> next-token logits (S, V) float32."""
    x = params["embed"].astype(jnp.float32)[tokens]
    for lp in params["layers"]:
        x, _ = block(lp, sizes, x, quant)
    x = _rms(x, params["final_norm"], sizes["norm_eps"])
    if "head" in params:
        return _linear(params["head"], x, quant)
    return jnp.matmul(_q(x, quant), _q(params["embed"], quant).T,
                      precision=HIGHEST)


def token_gaps(params: Dict, sizes: Dict, tokens: jax.Array,
               served: jax.Array, quant: Quant = None) -> jax.Array:
    """For each position t: how far the float32 logit of ``served[t]``
    lies below the float32 best. With ``quant``, ``served`` is ignored
    and the token is the one a forward at that precision puts first."""
    ref = logits(params, sizes, tokens)
    if quant is not None:
        served = jnp.argmax(logits(params, sizes, tokens, quant), -1)
    pick = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    return ref.max(-1) - pick


LINEARS = ("q", "k", "v", "o", "gate", "up", "down")


def _misfit(x: jax.Array, w: jax.Array, B: jax.Array,
            C: jax.Array) -> jax.Array:
    """1 - cos between ``x @ w`` and ``x @ B @ C`` (Frobenius)."""
    y = jnp.matmul(x, w, precision=HIGHEST)
    z = jnp.matmul(jnp.matmul(x, B, precision=HIGHEST), C,
                   precision=HIGHEST)
    ny, nz = jnp.linalg.norm(y), jnp.linalg.norm(z)
    cos = jnp.where(ny * nz > 0, jnp.vdot(y, z) / (ny * nz), 0.0)
    return 1.0 - cos


def factor_misfits(dense: Dict, served: Dict, sizes: Dict,
                   tokens: jax.Array, n: int) -> List[Dict[str, float]]:
    """How far each served linear lies from the dense linear it stands
    for, on the inputs the dense model gives that linear: per layer and
    linear, 1 - cos between ``x @ w`` (dense) and ``x @ B @ C`` (served),
    over the first ``n`` positions of ``tokens``. 0 where the served
    linear acts as the dense one does, about 1 where it is unrelated or
    zero, about 2 where it is negated. Layer by layer, so one layer's
    activations are held at a time; factors are padded with zeros to one
    rank per shape, which leaves ``B @ C`` as it is."""
    keep = (jnp.arange(tokens.shape[0]) < n)[:, None]
    step = jax.jit(lambda lp, x: block(lp, sizes, x))
    fit = jax.jit(_misfit)
    pad: Dict[Tuple[int, int], int] = {}
    for lp in served["layers"]:
        for k in LINEARS:
            if "B" in lp[k]:
                d_in, r = lp[k]["B"].shape
                shape = (d_in, lp[k]["C"].shape[1])
                pad[shape] = max(pad.get(shape, 0), -(-r // 128) * 128)
    x = dense["embed"].astype(jnp.float32)[tokens]
    out = []
    for lp, sp in zip(dense["layers"], served["layers"]):
        x, ins = step(lp, x)
        row = {}
        for k in LINEARS:
            w, f = lp[k]["w"], sp[k]
            if "B" in f:
                B, C = f["B"], f["C"]
                r = pad[w.shape] - B.shape[1]
                B = jnp.pad(B.astype(jnp.float32), ((0, 0), (0, r)))
                C = jnp.pad(C.astype(jnp.float32), ((0, r), (0, 0)))
            else:
                B, C = f["w"].astype(jnp.float32), jnp.eye(
                    w.shape[1], dtype=jnp.float32)
            row[k] = float(fit(ins[k] * keep, w.astype(jnp.float32), B, C))
        out.append(row)
    return out


# ---------------------------------------------------------------------------
# what the harness knows of the architecture
# ---------------------------------------------------------------------------
# the configuration file's ``model`` block (Hugging Face names) to the
# fields of the program's ``ModelConfig``
HF_TO_PROGRAM = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}

# the ``model`` block of a configuration in the benchmark's CPU tests
# (``bench/tiny.py``): two narrow layers, a short context
TINY_MODEL = {"num_hidden_layers": 2, "hidden_size": 64,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "intermediate_size": 128, "vocab_size": 256,
              "max_position_embeddings": 64}


def program_config(model: Dict) -> Dict:
    """The ``ModelConfig`` fields that the configuration's ``model`` block
    sets; ``head_dim`` is the block's own, or hidden / heads."""
    over = {HF_TO_PROGRAM[k]: v for k, v in model.items()
            if k in HF_TO_PROGRAM}
    over["head_dim"] = model.get(
        "head_dim", model["hidden_size"] // model["num_attention_heads"])
    return over


def sizes(cfg) -> Dict:
    """The reference's ``sizes`` from the program's ``ModelConfig``."""
    return {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "d_ff": cfg.d_ff,
            "vocab_size": cfg.vocab_size, "rope_theta": cfg.rope_theta,
            "norm_eps": cfg.norm_eps}


PROGRAM_NAMES = {"q": ("attn", "wq"), "k": ("attn", "wk"),
                 "v": ("attn", "wv"), "o": ("attn", "wo"),
                 "gate": ("mlp", "w_gate"), "up": ("mlp", "w_up"),
                 "down": ("mlp", "w_down")}


def _plain_linear(node: Dict) -> Dict:
    return ({"B": node["B"], "C": node["C"]} if "B" in node
            else {"w": node["w"]})


def from_program(params: Dict, cfg) -> Dict:
    """The program's parameter tree (arrays or numpy) in the reference's
    layout: the layers of each ``decoder/run{r}`` in ``cfg.layer_runs()``
    order, a run stacked along its leading axis or a list of layers."""
    layers = []
    for r, (_, n) in enumerate(cfg.layer_runs()):
        run = params["decoder"][f"run{r}"]
        for i in range(n):
            lp = run[i] if isinstance(run, list) else jax.tree.map(
                lambda a: a[i], run)
            layer = {"ln1": lp["ln1"]["scale"], "ln2": lp["ln2"]["scale"]}
            for short, (blk, lin) in PROGRAM_NAMES.items():
                layer[short] = _plain_linear(lp[blk][lin])
            layers.append(layer)
    out = {"embed": params["embed"],
           "final_norm": params["final_norm"]["scale"], "layers": layers}
    if "lm_head" in params:
        out["head"] = _plain_linear(params["lm_head"])
    return out


def linear_counts(ref_params: Dict, cfg) -> Tuple[int, int]:
    """Parameters of the layers' linears as served (counted from shapes,
    a shared basis once) and as the dense model has them."""
    lin = [lp[k] for lp in ref_params["layers"] for k in LINEARS]
    dense = (cfg.n_layers * (cfg.d_model * (cfg.n_heads + 2 * cfg.n_kv_heads)
                             * cfg.head_dim
                             + cfg.n_heads * cfg.head_dim * cfg.d_model
                             + 3 * cfg.d_model * cfg.d_ff))
    return counts.param_count(lin), dense


def work(cfg, params: Any) -> Dict:
    """What serving the program's parameter tree ``params`` costs, for the
    per-layer readers: every linear's FLOPs per token (the tied head
    included), the parameter bytes a decode step over ``live`` slots reads
    (all of them, whatever ``live``), each layer's attention window (0:
    the whole context) and the shapes attention and the logits run at."""
    nbytes = counts.param_bytes(params, cfg.dtype)
    return {"linear_flops_per_token": counts.linear_flops_per_token(
                params, cfg.tie_embeddings),
            "decode_param_bytes": lambda live: nbytes,
            "windows": [0] * cfg.n_layers,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "vocab_size": cfg.vocab_size,
            "kv_dtype": cfg.dtype}
