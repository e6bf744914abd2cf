"""AOT-compiled serve executables with a persistent on-disk cache
(DESIGN.md §5.6).

Boot used to pay jit tracing for every prefill bucket plus the decode
step the first time each shape arrived — a pod restart under load was a
latency cliff of several seconds before the first token. This module
makes the serve executables an explicit, ahead-of-time-compiled
*registry*:

* **ExecutableRegistry** is the one dispatch surface the engine calls
  (``decode`` / ``prefill`` / ``scatter`` / ``purge``). Two
  implementations share it:

  - ``TracedRegistry`` — the historical behavior: one ``jax.jit``
    closure per role, compiled lazily on first use, with the batcher's
    ``*_retraces`` counters bumped at trace time (the bucketing
    invariant tests assert on them).
  - ``AotRegistry`` — every entry point is lowered and compiled
    explicitly (``jax.jit(...).lower(avals).compile()``) and the
    compiled executable is **persisted** via
    ``jax.experimental.serialize_executable``. ``warm()`` precompiles
    the whole serving surface at boot — the decode step for every
    elastic-rank rung, every pow2 prefill bucket, and the scatter/purge
    cache helpers — so the steady-state loop never traces.

* **AotCache** is the persistent store: one file per executable under a
  cache directory, keyed by sha256 of (artifact fingerprint ×
  ServeConfig × model fingerprint × jax/jaxlib version × backend ×
  entry signature). A second boot of the same artifact deserializes
  instead of compiling (``aot_compiles == 0``), reaching the first
  token in a fraction of the tracing boot (``benchmarks/boot_ttft.py``
  records the ratio). Any mismatch — different artifact fingerprint,
  different jax version, a corrupt or truncated cache file — simply
  misses and falls back to a fresh compile; the cache can never change
  results, only skip work.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import tempfile
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import jaxlib

from repro import compile_cache
from repro.config import ModelConfig
from repro.obs import trace

# Roles an engine dispatches through the registry. One compiled
# executable exists per (role, variant): decode has one variant per
# elastic-rank rung, prefill one per (rung, bucket), the cache helpers
# one per source batch width.
ROLE_DECODE = "decode"
ROLE_PREFILL = "prefill"
ROLE_SCATTER = "scatter"
ROLE_PURGE = "purge"
# paged-pool roles (DESIGN.md §5.7); only live when ServeConfig.kv_block > 0
ROLE_DECODE_PAGED = "decode_paged"
ROLE_PREFILL_EXT = "prefill_ext"
ROLE_SCATTER_PAGED = "scatter_paged"
ROLE_PURGE_PAGED = "purge_paged"
ROLE_COPY_BLOCKS = "copy_blocks"

AOT_STAT_KEYS = ("aot_compiles", "aot_cache_hits", "aot_deser_failures",
                 "aot_fallbacks", "aot_store_failures")


def default_cache_dir() -> str:
    """Resolution order: ``$REPRO_AOT_CACHE``, then ``repro-aot`` under
    the compile-cache root (``repro.compile_cache.cache_root``)."""
    return os.environ.get("REPRO_AOT_CACHE") or os.path.join(
        compile_cache.cache_root(), "repro-aot")


# ---------------------------------------------------------------------------
# Cache-row helpers (shared by both registries; the engine used to keep
# private copies of these as inline jit closures)
# ---------------------------------------------------------------------------
def scatter_rows(pool: Dict, src: Dict, slots: jax.Array) -> Dict:
    """One whole-pool update: row j of every `src` cache leaf lands in row
    slots[j] of the pool (runs leaves carry a leading stacked-layer axis,
    so batch is axis 1; `pos` is batch-leading). slots[j] >= pool batch
    drops row j — admission pads with out-of-range slots."""
    runs = jax.tree.map(
        lambda pool_l, src_l: pool_l.at[:, slots].set(
            src_l.astype(pool_l.dtype), mode="drop"),
        pool["runs"], src["runs"])
    pos = pool["pos"].at[slots].set(src["pos"], mode="drop")
    return {"runs": runs, "pos": pos}


def purge_rows(pool: Dict, rows: jax.Array) -> Dict:
    """Zero cache rows + positions of quarantined slots so the next tenant
    (or a masked-out dead region) can never attend into poisoned state;
    rows >= batch are padding (dropped)."""
    runs = jax.tree.map(
        lambda leaf: leaf.at[:, rows].set(0, mode="drop"), pool["runs"])
    pos = pool["pos"].at[rows].set(-1, mode="drop")
    return {"runs": runs, "pos": pos}


def scatter_paged(pool: Dict, src: Dict, slots: jax.Array,
                  table: jax.Array, starts: jax.Array) -> Dict:
    """Paged-pool admission write: route each freshly-prefilled row of
    ``src`` (leaves (n, B, S, KV, hd)) through the block table into the
    flat arena (leaves (n, P, bk, KV, hd)). Row j's token i lands at
    absolute position starts[j] + i, i.e. physical block
    table[slots[j], absp // bk], offset absp % bk. Out-of-range slots
    (padding), positions past the table, and null-block (0) table
    entries all resolve to the arena-size sentinel and are dropped —
    shared prefix blocks below ``starts`` are never written."""
    nrows, NB = table.shape
    S = jax.tree.leaves(src["runs"])[0].shape[2]
    i = jnp.arange(S)[None, :]                           # (1, S)
    absp = starts[:, None] + i                           # (B, S)
    tail = (src["pos"] - starts)[:, None]
    srow = jnp.minimum(slots, nrows - 1)

    def _leaf(pool_l, src_l):
        P, bk = pool_l.shape[1], pool_l.shape[2]
        blk = absp // bk
        ok = (i < tail) & (slots[:, None] < nrows) & (blk < NB)
        tb = table[srow[:, None], jnp.minimum(blk, NB - 1)]
        pb = jnp.where(ok & (tb > 0), tb, P)             # P = drop sentinel
        return pool_l.at[:, pb, absp % bk].set(
            src_l.astype(pool_l.dtype), mode="drop")

    runs = jax.tree.map(_leaf, pool["runs"], src["runs"])
    pos = pool["pos"].at[slots].set(src["pos"], mode="drop")
    return {"runs": runs, "pos": pos}


def purge_paged(pool: Dict, rows: jax.Array, blocks: jax.Array) -> Dict:
    """Paged quarantine/retirement: zero the listed *arena blocks* (only
    those whose refcount hit zero — shared prefix blocks another request
    still holds are never listed, the host allocator guarantees it) and
    mark the listed slot rows dead (pos = -1, dropping their decode
    writes and zeroing their outputs). Both arrays are fixed-width with
    out-of-range sentinels (arena size / batch) for padding."""
    runs = jax.tree.map(
        lambda leaf: leaf.at[:, blocks].set(0, mode="drop"), pool["runs"])
    pos = pool["pos"].at[rows].set(-1, mode="drop")
    return {"runs": runs, "pos": pos}


def _hand_over(given: Dict, new: Dict) -> Dict:
    """A decode executable consumes the pool it is given (``runs``, the
    donated argument, updated in place into ``new``'s; positions are not
    donated). Point the caller's dict at the updated pool, so that a
    reference kept from before the step holds live buffers (the updated
    pool beside its own, older ``pos``) and never deleted ones. Returns
    ``new``."""
    given["runs"] = new["runs"]
    return new


def copy_blocks(pool: Dict, src: jax.Array, dst: jax.Array) -> Dict:
    """Copy-on-write fork: arena block src[j] → dst[j] for each j. The
    destination blocks are freshly allocated (refcount 1, unshared), so
    this is the only write a shared block's content ever feeds. Sentinel
    entries (>= arena size) are dropped (gathers clamp harmlessly)."""
    def _leaf(leaf):
        s = jnp.minimum(src, leaf.shape[1] - 1)
        return leaf.at[:, dst].set(leaf[:, s], mode="drop")
    return {"runs": jax.tree.map(_leaf, pool["runs"]), "pos": pool["pos"]}


# ---------------------------------------------------------------------------
# Fingerprints & cache keys
# ---------------------------------------------------------------------------
def live_fingerprint(params, cfg: ModelConfig) -> str:
    """Fingerprint for an in-memory (non-artifact) boot: the param tree's
    structure + leaf shapes/dtypes and the model dims. Weights are jit
    *arguments*, so the executables depend only on shapes — but keying on
    the artifact identity (see ``ckpt.store.artifact_fingerprint`` for
    saved artifacts) keeps invalidation semantics trivially safe."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    h = hashlib.sha256()
    h.update(str(treedef).encode())
    for leaf in leaves:
        h.update(str(jnp.shape(leaf)).encode())
        h.update(str(getattr(leaf, "dtype", type(leaf))).encode())
    h.update(json.dumps({"name": cfg.name, "n_layers": cfg.n_layers,
                         "d_model": cfg.d_model,
                         "vocab_size": cfg.vocab_size},
                        sort_keys=True).encode())
    return "live-" + h.hexdigest()[:32]


def _sig_of(args) -> str:
    """Canonical signature of a call: treedef + flat avals. Part of the
    disk key, so executables can never be replayed against a different
    input structure."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    parts = [str(treedef)]
    for leaf in leaves:
        parts.append(f"{jnp.shape(leaf)}:{getattr(leaf, 'dtype', '?')}")
    return ";".join(parts)


def cache_key(fingerprint: str, role: str, variant: Tuple, sig: str,
              scfg, cfg: ModelConfig) -> str:
    """sha256 over everything that could change the compiled executable:
    artifact fingerprint, serve + model config, jax/jaxlib version and
    backend, and the entry's (role, variant, aval signature)."""
    payload = {
        "fingerprint": fingerprint,
        "role": role,
        "variant": list(variant),
        "sig": sig,
        "scfg": {"batch": scfg.batch, "max_len": scfg.max_len,
                 "kv_block": getattr(scfg, "kv_block", 0)},
        "model": {"name": cfg.name, "n_layers": cfg.n_layers,
                  "d_model": cfg.d_model, "vocab_size": cfg.vocab_size,
                  "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
                  "dtype": str(cfg.dtype)},
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
    }
    return hashlib.sha256(
        json.dumps(payload, sort_keys=True).encode()).hexdigest()


class AotCache:
    """Directory of serialized compiled executables, one ``<key>.aotx``
    file per entry (pickle of ``serialize_executable.serialize`` output:
    the XLA executable bytes plus in/out pytree defs). Writes are atomic
    (tmp + rename) so a crashed boot never leaves a torn entry; reads
    treat *any* failure — missing file, bad pickle, an executable built
    by an incompatible jax/backend — as a miss."""

    def __init__(self, directory: str):
        self.dir = directory
        os.makedirs(directory, exist_ok=True)

    def path(self, key: str) -> str:
        return os.path.join(self.dir, f"{key}.aotx")

    def load(self, key: str):
        """Deserialize the executable for ``key`` or return ``None`` on
        any miss/corruption (the caller recompiles)."""
        from jax.experimental.serialize_executable import (
            deserialize_and_load)
        p = self.path(key)
        if not os.path.exists(p):
            return None
        try:
            with open(p, "rb") as f:
                payload, in_tree, out_tree = pickle.loads(f.read())
            return deserialize_and_load(payload, in_tree, out_tree)
        except Exception:
            return False          # present but unusable: count separately

    def has(self, key: str) -> bool:
        return os.path.exists(self.path(key))

    def store(self, key: str, compiled) -> bool:
        """Persist ``compiled`` under ``key``. Returns False when the
        backend cannot serialize it (nothing is written; the registry
        counts it as ``aot_store_failures``)."""
        from jax.experimental.serialize_executable import serialize
        try:
            blob = pickle.dumps(serialize(compiled))
        except Exception:
            return False
        fd, tmp = tempfile.mkstemp(dir=self.dir, suffix=".tmp")
        with os.fdopen(fd, "wb") as f:
            f.write(blob)
        os.replace(tmp, self.path(key))
        return True

    def keys(self) -> List[str]:
        return sorted(f[:-5] for f in os.listdir(self.dir)
                      if f.endswith(".aotx"))


# ---------------------------------------------------------------------------
# Registries
# ---------------------------------------------------------------------------
class TracedRegistry:
    """The pre-AOT behavior as a registry: one lazily-traced ``jax.jit``
    per role. Trace-time side effects bump the engine's historical
    retrace counters (``prefill_retraces`` / ``decode_retraces`` /
    ``scatter_retraces``) exactly as before — the bucketing invariant
    (≤ ⌈log2(max_len)⌉ prefill traces, 1 decode trace per rung) is
    load-bearing for serving latency and asserted in tests."""

    kind = "traced"

    def __init__(self, cfg: ModelConfig, scfg, stats: Optional[Dict] = None):
        from repro.models import transformer as T
        self.cfg, self.scfg = cfg, scfg
        self.stats = stats if stats is not None else {}
        for k in ("prefill_retraces", "decode_retraces", "scatter_retraces"):
            self.stats.setdefault(k, 0)

        def _decode_fn(p, pool, pos, t):
            self.stats["decode_retraces"] += 1
            return T.decode_step(p, cfg, {"runs": pool, "pos": pos}, t)

        def _prefill_fn(p, b):
            self.stats["prefill_retraces"] += 1
            return T.prefill(p, cfg, b, max_len=scfg.max_len)

        def _scatter_fn(pool, src, slots):
            self.stats["scatter_retraces"] += 1
            return scatter_rows(pool, src, slots)

        def _decode_paged_fn(p, pool, pos, t, tbl):
            self.stats["decode_retraces"] += 1
            return T.decode_step(p, cfg, {"runs": pool, "pos": pos}, t,
                                 table=tbl)

        def _prefill_ext_fn(p, b, arena, tbl):
            self.stats["prefill_retraces"] += 1
            return T.prefill_ext(p, cfg, b, arena, tbl)

        def _scatter_paged_fn(pool, src, slots, tbl, starts):
            self.stats["scatter_retraces"] += 1
            return scatter_paged(pool, src, slots, tbl, starts)

        # decode consumes the pool it is given: updated in place
        self._decode = jax.jit(_decode_fn, donate_argnums=(1,))
        self._prefill = jax.jit(_prefill_fn)
        self._scatter = jax.jit(_scatter_fn, donate_argnums=(0,))
        self._purge = jax.jit(purge_rows, donate_argnums=(0,))
        self._decode_paged = jax.jit(_decode_paged_fn, donate_argnums=(1,))
        # the arena rides along read-only (prefix gathers); not donated
        self._prefill_ext = jax.jit(_prefill_ext_fn)
        self._scatter_paged = jax.jit(_scatter_paged_fn, donate_argnums=(0,))
        self._purge_paged = jax.jit(purge_paged, donate_argnums=(0,))
        self._copy_blocks = jax.jit(copy_blocks, donate_argnums=(0,))

    def bind_stats(self, stats: Dict) -> None:
        """Fold any counts accumulated so far into ``stats`` and make it
        the live counter dict (the engine owns one stats surface)."""
        for k, v in self.stats.items():
            stats[k] = stats.get(k, 0) + v
        self.stats = stats

    # role dispatch — variant hints are accepted (and ignored) so the
    # engine calls both registries identically
    def decode(self, params, cache, tokens, *, level: int = 0):
        logits, new = self._decode(params, cache["runs"], cache["pos"],
                                   tokens)
        return logits, _hand_over(cache, new)

    def prefill(self, params, batch, *, level: int = 0, bucket=None):
        return self._prefill(params, batch)

    def scatter(self, pool, src, slots):
        return self._scatter(pool, src, slots)

    def purge(self, pool, rows):
        return self._purge(pool, rows)

    def decode_paged(self, params, cache, tokens, table, *, level: int = 0):
        logits, new = self._decode_paged(params, cache["runs"], cache["pos"],
                                         tokens, table)
        return logits, _hand_over(cache, new)

    def prefill_ext(self, params, batch, arena, table, *, level: int = 0,
                    bucket=None):
        return self._prefill_ext(params, batch, arena, table)

    def scatter_paged(self, pool, src, slots, table, starts):
        return self._scatter_paged(pool, src, slots, table, starts)

    def purge_paged(self, pool, rows, blocks):
        return self._purge_paged(pool, rows, blocks)

    def copy_blocks(self, pool, src, dst):
        return self._copy_blocks(pool, src, dst)

    def warm(self, ladder: Sequence, bucketed: bool,
             paged: bool = False) -> None:
        """No-op: the traced registry compiles lazily, on first use."""


class AotRegistry:
    """AOT-compiled serve executables behind the same role interface.

    Every dispatch resolves (role, variant) → a compiled executable:
    first from the in-memory table, then from the persistent
    ``AotCache`` (deserialization, ~ms), and only then by an explicit
    ``jax.jit(...).lower(avals).compile()`` whose result is written back
    to the cache. ``warm()`` resolves the entire serving surface up
    front from abstract avals — nothing runs, nothing traces lazily
    afterwards, and a warm cache makes boot O(deserialize) instead of
    O(compile).

    Fallback ladder (nothing here can change results, only cost): a
    cache file that is missing/corrupt/incompatible → compile; a loaded
    executable that rejects the actual runtime avals (``TypeError``) →
    recompile from the live arguments and replace the entry
    (``aot_fallbacks``)."""

    kind = "aot"

    def __init__(self, cfg: ModelConfig, scfg, fingerprint: str,
                 cache_dir: Optional[str] = None,
                 stats: Optional[Dict] = None,
                 device: Optional[jax.Device] = None):
        from repro.models import transformer as T
        self._T = T
        self.cfg, self.scfg = cfg, scfg
        self.fingerprint = fingerprint
        # executables are compiled for one device: a replica's own, or the
        # default device when None
        self.device = device
        self._sharding = (jax.sharding.SingleDeviceSharding(device)
                          if device is not None else None)
        self.cache = AotCache(cache_dir or default_cache_dir())
        self.stats = stats if stats is not None else {}
        for k in AOT_STAT_KEYS:
            self.stats.setdefault(k, 0)
        # the engine's traced-era counters stay present (and zero) so the
        # metrics schema is identical across registries
        for k in ("prefill_retraces", "decode_retraces", "scatter_retraces"):
            self.stats.setdefault(k, 0)
        self._mem: Dict[Tuple, Any] = {}

    def bind_stats(self, stats: Dict) -> None:
        for k, v in self.stats.items():
            stats[k] = stats.get(k, 0) + v
        self.stats = stats

    # ---- role functions --------------------------------------------------
    def _role_fn(self, role: str):
        """The function compiled for ``role`` and its donated arguments.
        Each function carries its role's name, which XLA keeps as the
        executable's module name (``jit_decode``, ``jit_prefill``, …).
        Decode takes the cache as its pool and its positions, and
        donates the pool alone."""
        cfg, scfg, T = self.cfg, self.scfg, self._T
        if role == ROLE_DECODE:
            def decode(p, pool, pos, t):
                return T.decode_step(p, cfg, {"runs": pool, "pos": pos}, t)
            return decode, (1,)
        if role == ROLE_PREFILL:
            def prefill(p, b):
                return T.prefill(p, cfg, b, max_len=scfg.max_len)
            return prefill, ()
        if role == ROLE_SCATTER:
            return scatter_rows, (0,)
        if role == ROLE_PURGE:
            return purge_rows, (0,)
        if role == ROLE_DECODE_PAGED:
            def decode_paged(p, pool, pos, t, tbl):
                return T.decode_step(p, cfg, {"runs": pool, "pos": pos}, t,
                                     table=tbl)
            return decode_paged, (1,)
        if role == ROLE_PREFILL_EXT:
            def prefill_ext(p, b, arena, tbl):
                return T.prefill_ext(p, cfg, b, arena, tbl)
            return prefill_ext, ()
        if role == ROLE_SCATTER_PAGED:
            return scatter_paged, (0,)
        if role == ROLE_PURGE_PAGED:
            return purge_paged, (0,)
        if role == ROLE_COPY_BLOCKS:
            return copy_blocks, (0,)
        raise KeyError(role)

    # ---- resolution ------------------------------------------------------
    def _key(self, role: str, variant: Tuple, args: Tuple) -> str:
        sig = _sig_of(args)
        if self.device is not None:
            sig += f";device={self.device.id}"
        return cache_key(self.fingerprint, role, variant, sig, self.scfg,
                         self.cfg)

    def _compile(self, role: str, args: Tuple):
        """Lower and compile ``role`` for ``args``; abstract avals carry
        no device of their own, so they are placed on this registry's."""
        fn, donate = self._role_fn(role)
        if self._sharding is not None:
            args = jax.tree.map(
                lambda a: (jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                sharding=self._sharding)
                           if isinstance(a, jax.ShapeDtypeStruct) else a),
                args)
        return jax.jit(fn, donate_argnums=donate).lower(*args).compile()

    def _resolve(self, role: str, variant: Tuple, args: Tuple):
        """(role, variant) → compiled executable, via memo → disk →
        compile. ``args`` may mix concrete arrays and ShapeDtypeStructs —
        only shapes/dtypes matter for lowering."""
        memk = (role, variant)
        exe = self._mem.get(memk)
        if exe is not None:
            return exe
        key = self._key(role, variant, args)
        with trace.span("aot_deserialize", role=role,
                        variant=list(variant)):
            exe = self.cache.load(key)
        if exe is False:
            self.stats["aot_deser_failures"] += 1
            exe = None
        if exe is None:
            with trace.span("aot_compile", role=role,
                            variant=list(variant)):
                compiled = self._compile(role, args)
            self.stats["aot_compiles"] += 1
            if not self.cache.store(key, compiled):
                self.stats["aot_store_failures"] += 1
            exe = compiled
        else:
            self.stats["aot_cache_hits"] += 1
        self._remember(role, variant, exe)
        return exe

    def _remember(self, role: str, variant: Tuple, exe) -> None:
        """Keep ``exe`` for (role, variant). For a decode executable, also
        record what its memory analysis says of the donated cache:
        ``decode_alias_bytes``, the output bytes that reuse an input's
        buffer (the whole pool, when it is updated in place), and
        ``decode_temp_bytes``, its scratch (where the backend has them)."""
        self._mem[(role, variant)] = exe
        if role in (ROLE_DECODE, ROLE_DECODE_PAGED):
            ma = exe.memory_analysis()
            if ma is not None:
                self.stats["decode_alias_bytes"] = int(ma.alias_size_in_bytes)
                self.stats["decode_temp_bytes"] = int(ma.temp_size_in_bytes)

    def _call(self, role: str, variant: Tuple, *args):
        exe = self._resolve(role, variant, args)
        try:
            return exe(*args)
        except TypeError:
            # aval drift (e.g. a weak-typed scalar from a caller we don't
            # control): recompile against the live arguments and swap the
            # entry — degraded to a compile, never to a wrong answer
            self.stats["aot_fallbacks"] += 1
            compiled = self._compile(role, args)
            self.stats["aot_compiles"] += 1
            self._remember(role, variant, compiled)
            return compiled(*args)

    # ---- role dispatch ---------------------------------------------------
    def decode(self, params, cache, tokens, *, level: int = 0):
        logits, new = self._call(ROLE_DECODE, (level,), params,
                                 cache["runs"], cache["pos"], tokens)
        return logits, _hand_over(cache, new)

    def prefill(self, params, batch, *, level: int = 0, bucket=None):
        if bucket is None:         # exact-length path (recurrent archs)
            bucket = ("exact", int(batch["tokens"].shape[0]),
                      int(batch["tokens"].shape[1]))
        return self._call(ROLE_PREFILL, (level, bucket), params, batch)

    def scatter(self, pool, src, slots):
        return self._call(ROLE_SCATTER, (int(src["pos"].shape[0]),),
                          pool, src, slots)

    def purge(self, pool, rows):
        return self._call(ROLE_PURGE, (), pool, rows)

    def decode_paged(self, params, cache, tokens, table, *, level: int = 0):
        logits, new = self._call(ROLE_DECODE_PAGED, (level,), params,
                                 cache["runs"], cache["pos"], tokens, table)
        return logits, _hand_over(cache, new)

    def prefill_ext(self, params, batch, arena, table, *, level: int = 0,
                    bucket=None):
        if bucket is None:
            bucket = ("exact", int(batch["tokens"].shape[0]),
                      int(batch["tokens"].shape[1]))
        return self._call(ROLE_PREFILL_EXT, (level, bucket),
                          params, batch, arena, table)

    def scatter_paged(self, pool, src, slots, table, starts):
        src_s = int(jax.tree.leaves(src["runs"])[0].shape[2])
        return self._call(ROLE_SCATTER_PAGED,
                          (int(src["pos"].shape[0]), src_s),
                          pool, src, slots, table, starts)

    def purge_paged(self, pool, rows, blocks):
        return self._call(ROLE_PURGE_PAGED, (), pool, rows, blocks)

    def copy_blocks(self, pool, src, dst):
        return self._call(ROLE_COPY_BLOCKS, (), pool, src, dst)

    # ---- boot-time precompilation ---------------------------------------
    def _cache_aval(self):
        cfg, scfg = self.cfg, self.scfg
        return jax.eval_shape(
            lambda: self._T.init_cache(cfg, scfg.batch, scfg.max_len))

    def prefill_buckets(self) -> List[int]:
        """The pow2 prompt buckets the engine can ever ask for:
        2, 4, … capped at ``max_len`` (which is itself a bucket when not
        a power of two)."""
        out, b = [], 2
        while b < self.scfg.max_len:
            out.append(b)
            b *= 2
        out.append(self.scfg.max_len)
        return sorted(set(out))

    def _ensure(self, role: str, variant: Tuple, args: Tuple) -> None:
        """Warm-path resolve: guarantee this entry will never need a
        compile at dispatch time, as cheaply as possible. A disk-cached
        entry is left ON DISK — deserialization (~0.1s/entry on the
        bigger models) is deferred to first dispatch, so a warm boot's
        time-to-first-token pays only for the executables the first
        request actually touches. Anything missing compiles (and
        persists) now, which is the whole cold-boot cost."""
        if (role, variant) in self._mem:
            return
        if self.cache.has(self._key(role, variant, args)):
            return                 # servable; lazy-deserialized on use
        self._resolve(role, variant, args)

    def warm(self, ladder: Sequence, bucketed: bool,
             paged: bool = False) -> None:
        """Precompile (or cache-verify) the full serving surface: the
        decode step for every elastic-rank rung, every pow2 prefill
        bucket at full rank, and the scatter/purge cache helpers.
        Lowering happens against abstract avals — no model math runs.
        After this returns, steady-state serving performs zero XLA
        compiles (``aot_compiles`` stays flat) no matter which bucket,
        rung or helper a request exercises. With ``paged`` the block-
        arena surface is warmed instead of the contiguous decode/scatter
        (the paged engine never dispatches those roles); the non-paged
        warm set is byte-identical to what it always was."""
        with trace.span("aot_warm", rungs=len(ladder), bucketed=bucketed,
                        paged=paged):
            B = self.scfg.batch
            i32 = jnp.int32
            cache_aval = self._cache_aval()
            tok_aval = jax.ShapeDtypeStruct((B, 1), i32)
            slots_aval = jax.ShapeDtypeStruct((B,), i32)
            # the full-rank decode is loaded now, not left on disk: its
            # memory analysis (decode_alias_bytes) is a boot fact
            if not paged:
                for level, params in enumerate(ladder):
                    (self._ensure if level else self._resolve)(
                        ROLE_DECODE, (level,), (params, cache_aval["runs"],
                                                cache_aval["pos"], tok_aval))
            if bucketed:
                src_aval = None
                for sb in self.prefill_buckets():
                    batch_aval = {
                        "tokens": jax.ShapeDtypeStruct((B, sb), i32),
                        "lengths": jax.ShapeDtypeStruct((B,), i32)}
                    self._ensure(ROLE_PREFILL, (0, sb),
                                 (ladder[0], batch_aval))
                    if src_aval is None:
                        fn, _ = self._role_fn(ROLE_PREFILL)
                        _, src_aval = jax.eval_shape(fn, ladder[0],
                                                     batch_aval)
                if not paged and src_aval is not None:
                    self._ensure(ROLE_SCATTER, (B,),
                                 (cache_aval, src_aval, slots_aval))
            if not paged:
                self._ensure(ROLE_PURGE, (),
                             (cache_aval, slots_aval))
                return
            # ---- paged surface ------------------------------------------
            bkv = int(getattr(self.scfg, "kv_block", 0))
            NB = self.scfg.max_len // bkv
            nblk = B * NB + 1
            arena_aval = jax.eval_shape(
                lambda: self._T.init_cache_paged(self.cfg, B, nblk, bkv))
            tbl_aval = jax.ShapeDtypeStruct((B, NB), i32)
            starts_aval = jax.ShapeDtypeStruct((B,), i32)
            for level, params in enumerate(ladder):
                (self._ensure if level else self._resolve)(
                    ROLE_DECODE_PAGED, (level,),
                    (params, arena_aval["runs"], arena_aval["pos"], tok_aval,
                     tbl_aval))
            pre_fn, _ = self._role_fn(ROLE_PREFILL)
            ext_fn, _ = self._role_fn(ROLE_PREFILL_EXT)
            seen_s = set()
            for sb in self.prefill_buckets():
                batch_aval = {
                    "tokens": jax.ShapeDtypeStruct((B, sb), i32),
                    "lengths": jax.ShapeDtypeStruct((B,), i32)}
                ext_aval = dict(batch_aval, starts=starts_aval)
                self._ensure(ROLE_PREFILL_EXT, (0, sb),
                             (ladder[0], ext_aval, arena_aval, tbl_aval))
                # scatter variants: plain prefill emits max_len-wide src
                # caches, prefill_ext emits bucket-wide ones
                for fn, aval in ((pre_fn, batch_aval), (ext_fn, None)):
                    if aval is not None:
                        _, sa = jax.eval_shape(fn, ladder[0], aval)
                    else:
                        _, sa = jax.eval_shape(fn, ladder[0], ext_aval,
                                               arena_aval, tbl_aval)
                    ss = int(jax.tree.leaves(sa["runs"])[0].shape[2])
                    if ss not in seen_s:
                        seen_s.add(ss)
                        self._ensure(ROLE_SCATTER_PAGED, (B, ss),
                                     (arena_aval, sa, slots_aval,
                                      tbl_aval, starts_aval))
            self._ensure(ROLE_PURGE_PAGED, (),
                         (arena_aval, slots_aval,
                          jax.ShapeDtypeStruct((B * NB,), i32)))
            self._ensure(ROLE_COPY_BLOCKS, (),
                         (arena_aval, slots_aval, slots_aval))
