"""Calibration capture: per-linear input-activation statistics.

The compression pipeline needs, for every compressible weight matrix
``W (d_in, d_out)``, the Gram matrix of its calibration inputs
``G = Σ_batches XᵀX`` (fp64, host-side — the paper keeps the whitening
matrix S in fp64) plus the mean-|X| vector (ASVD's scaling).

Mechanism: model parameters are converted to *list form* (stacked layer runs
→ per-layer trees; see ``transformer._run_layers``), every linear's param
dict gets a ``"_tag"`` string key, and ``apply_linear`` reports ``(tag, x)``
to the active capture target (``repro.models.params.set_capture``). Two
targets exist:

  Collector        eager/host oracle — numpy fp64 accumulation, forward runs
                   op-by-op (never under jit; it raises on tracers).
  StreamingTape +  device-side streaming mode — the forward pass is traced
  StreamingCalibrator  inside a jit'd step function, every tagged activation
                   is reduced to a fp32 partial Gram ON DEVICE (Pallas
                   ``gram_blocked`` on TPU, XLA dot elsewhere), partials are
                   threaded functionally through donated accumulators, and
                   the host flushes them into fp64 sums every few batches
                   (DESIGN.md §7: fp32 partials + fp64 host-sum keep the
                   paper's fp64 S-matrix while calibration runs compiled
                   and multi-device; on a mesh, capture and reduction are
                   pipelined two-stage ``shard_map`` steps, with large
                   (D,D) accumulators optionally sharded row-wise and
                   whitening factors kept per shard until a tree-reduce
                   at finalize — DESIGN.md §1.6).

MoE routed experts are captured separately: the dispatch buffers
``(E, capacity, d)`` that feed the per-expert GEMMs are reported by
``repro.models.mlp._moe_local`` under ``tag/expert{e}`` (padding rows are
exact zeros and contribute nothing to the Gram).
"""
from __future__ import annotations

from typing import Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.dist.sharding import (P, axis_group_size, combined_axis_index,
                                 logical_spec, shard_map)
from repro.models.params import Params, set_capture
from repro.obs import trace


class Collector:
    """Accumulates XᵀX (fp64) and Σ|x| per tag. Eager/host only — this is
    the precision oracle the streaming path is validated against."""

    def __init__(self):
        self.gram: Dict[str, np.ndarray] = {}
        self.absmean: Dict[str, np.ndarray] = {}
        self.count: Dict[str, int] = {}
        # streaming-whitening factors: upper-triangular R with RᵀR ≈ G,
        # for tags captured with StreamingCalibrator(whiten_tags=...) —
        # those tags have no entry in ``gram`` (that is the point: the
        # Gram never exists, on device or host)
        self.chol: Dict[str, np.ndarray] = {}

    def add(self, tag: str, x: jax.Array) -> None:
        if isinstance(x, jax.core.Tracer):
            raise RuntimeError(
                "Collector is host-side/eager and cannot run under jit; "
                "use StreamingCalibrator / streaming_calibrate for the "
                "device-side capture path")
        x2 = np.asarray(x, dtype=np.float64).reshape(-1, x.shape[-1])
        g = x2.T @ x2
        if tag in self.gram:
            self.gram[tag] += g
            self.absmean[tag] += np.abs(x2).sum(0)
            self.count[tag] += x2.shape[0]
        else:
            self.gram[tag] = g
            self.absmean[tag] = np.abs(x2).sum(0)
            self.count[tag] = x2.shape[0]

    def add_expert_batch(self, tag: str, xs: jax.Array) -> None:
        """xs: (E, capacity, d) dispatch buffers — one Gram per expert."""
        xs = np.asarray(xs, dtype=np.float64)
        for e in range(xs.shape[0]):
            self.add(f"{tag}/expert{e}", xs[e])

    def mean_abs(self, tag: str) -> np.ndarray:
        return self.absmean[tag] / max(1, self.count[tag])

    def __enter__(self):
        set_capture(self)
        return self

    def __exit__(self, *exc):
        set_capture(None)
        return False


# ---------------------------------------------------------------------------
# Streaming (jit/device) capture
# ---------------------------------------------------------------------------
class StreamingTape:
    """Trace-time capture target: collects per-tag fp32 partial statistics
    as jax values while a jit'd forward pass is being traced. The traced
    computation therefore CONTAINS the Gram reductions; the surrounding
    step function folds ``partials`` into the carried accumulators, so the
    side effect is confined to trace time and the result is functional.

    ``raw`` selects tags whose activation blocks are kept RAW (fp32 row
    blocks in ``xblocks``) instead of being reduced to a Gram at trace
    time: whitened tags feed a QR update, and — on a mesh — sharded-Gram
    tags feed the row-block fold, which needs the rows themselves
    (DESIGN.md §1.5/§1.6)."""

    def __init__(self, use_kernel: Optional[bool] = None,
                 whiten=None, raw=None):
        if use_kernel is None:
            use_kernel = jax.default_backend() == "tpu"
        self.use_kernel = use_kernel
        self.whiten = whiten            # True (all tags) or a set of tags
        self.raw = raw                  # additional raw-block tags
        self.partials: Dict[str, Dict[str, jax.Array]] = {}
        self.xblocks: Dict[str, list] = {}

    def _keep_raw(self, tag: str) -> bool:
        return (_tag_whitened(self.whiten, tag)
                or _tag_whitened(self.raw, tag))

    def _gram(self, x2: jax.Array) -> jax.Array:
        if self.use_kernel:
            from repro.kernels import ops as kops
            return kops.gram(x2)
        return jax.lax.dot_general(x2, x2, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def add(self, tag: str, x: jax.Array) -> None:
        x2 = x.astype(jnp.float32).reshape(-1, x.shape[-1])
        part = {
            "absx": jnp.abs(x2).sum(0),
            "count": jnp.full((), x2.shape[0], dtype=jnp.int32),
        }
        if self._keep_raw(tag):
            self.xblocks.setdefault(tag, []).append(x2)
        else:
            part["gram"] = self._gram(x2)
        if tag in self.partials:
            self.partials[tag] = jax.tree.map(jnp.add, self.partials[tag],
                                              part)
        else:
            self.partials[tag] = part

    def add_expert_batch(self, tag: str, xs: jax.Array) -> None:
        for e in range(xs.shape[0]):
            self.add(f"{tag}/expert{e}", xs[e])

    def __enter__(self):
        set_capture(self)
        return self

    def __exit__(self, *exc):
        set_capture(None)
        return False


def _tag_whitened(whiten, tag: str) -> bool:
    """Shared predicate: ``whiten`` is True (all tags), a collection of
    tags, or None/falsy (off)."""
    return whiten is True or (whiten is not None and tag in whiten)


def _split_weights(tagged: Params):
    """(array leaves of ``tagged``, rebuild). The capture steps take the
    weights as jit ARGUMENTS and rebuild the tagged tree at trace time:
    closed over, they would be baked into the executable as constants
    (gigabytes for a full-width model, and a compile to match)."""
    leaves, treedef = jax.tree.flatten(tagged)
    is_arr = [hasattr(x, "shape") for x in leaves]
    weights = [x for x, a in zip(leaves, is_arr) if a]

    def rebuild(ws):
        it = iter(ws)
        return treedef.unflatten([next(it) if a else x
                                  for x, a in zip(leaves, is_arr)])
    return weights, rebuild


def _spec_axes(spec) -> tuple:
    """First-dimension mesh axes of a PartitionSpec, as a flat tuple."""
    entry = spec[0] if len(spec) else None
    if entry is None:
        return ()
    return (entry,) if isinstance(entry, str) else tuple(entry)


def _zero_accs(dims: Dict[str, int], whiten=None
               ) -> Dict[str, Dict[str, jax.Array]]:
    def entry(tag, d):
        stat = ({"chol": jnp.zeros((d, d), jnp.float32)}
                if _tag_whitened(whiten, tag)
                else {"gram": jnp.zeros((d, d), jnp.float32)})
        return {**stat, "absx": jnp.zeros((d,), jnp.float32),
                "count": jnp.zeros((), jnp.int32)}

    return {tag: entry(tag, d) for tag, d in dims.items()}


class _ShapeProbe:
    """Abstract capture target for tag/dim discovery under eval_shape."""

    def __init__(self):
        self.dims: Dict[str, int] = {}

    def add(self, tag: str, x) -> None:
        self.dims[tag] = int(x.shape[-1])

    def add_expert_batch(self, tag: str, xs) -> None:
        for e in range(xs.shape[0]):
            self.dims[f"{tag}/expert{e}"] = int(xs.shape[-1])


def discover_capture_dims(tagged: Params, cfg: ModelConfig,
                          batch: Dict) -> Dict[str, int]:
    """Enumerate every capture tag and its feature dim without running the
    model (abstract eval of one forward pass)."""
    from repro.models import transformer as T
    probe = _ShapeProbe()
    set_capture(probe)
    try:
        jax.eval_shape(lambda b: T.forward(tagged, cfg, b), batch)
    finally:
        set_capture(None)
    return probe.dims


class StreamingCalibrator:
    """Jit-compiled, device-side calibration capture (DESIGN.md §1.3/§1.6).

    One jit'd step per batch shape: forward pass + on-device fp32 Gram
    partials per tag, folded into donated accumulators. Every
    ``flush_every`` batches the fp32 accumulators are pulled to host,
    added into fp64 sums and reset — bounding fp32 accumulation error
    while keeping the per-batch path free of host transfers.

    With ``mesh``, capture is a two-stage pipeline (DESIGN.md §1.6):
    stage 1 (``_capture``) runs the forward pass per data-parallel shard
    inside ``shard_map`` (batch rows split over ``data_axes``, weights
    replicated) and emits per-shard partials with NO
    collectives; stage 2 (``_fold``) reduces the PREVIOUS batch's
    partials into the donated accumulators. ``ingest`` dispatches stage 1
    of batch k+1 before stage 2 of batch k, so the per-batch
    psum/all-gather latency hides behind the next forward pass
    (double-buffered: the in-flight partials are the second buffer).

    Accumulator layout on a mesh is routed per tag:

      replicated  (D, D) fp32 Gram on every device; per-shard partial
                  Grams are ``lax.psum``'d in the fold. The default for
                  small D.
      sharded     tags with ``D >= shard_grams_above`` (and divisible)
                  keep the (D, D) accumulator SHARDED row-wise over the
                  data axes — each device owns a (D/n_shards, D) block
                  and folds its rows of XᵀX from all-gathered activation
                  rows, so no device ever materializes a full (D, D)
                  buffer. Flush reassembles the blocks on host in fp64.
      whiten      see below: one QR factor per shard, tree-reduced at
                  finalize.

    ``whiten_tags`` (True = every tag, or an explicit collection of tags)
    enables STREAMING WHITENING for those tags: instead of accumulating a
    Gram, the step maintains the upper-triangular Cholesky factor of the
    running Gram directly — ``R' = qr_r([R; X_batch])`` — as a QR update
    on the raw fp32 activation rows. The Gram of a whitened tag is never
    materialized, on device or host; ``finalize`` exposes the factor as
    ``Collector.chol[tag]`` and both the host whitener
    (``numerics.whitener_from_factor``) and the device decomposition
    (``numerics_jax.decompose(factor=...)``) consume it as is.
    QR-updating also sidesteps fp32 Gram-summation error (orthogonal
    transforms don't square the condition number), so no fp64 host flush
    is needed for these tags. On a mesh, each shard QR-updates its OWN
    factor over its slice of the data (QR updates don't commute with
    psum, so nothing is reduced per batch); ``finalize`` merges the
    per-shard factors with ``numerics_jax.tree_reduce_factors`` —
    pairwise ``R' = qr_r([R_a; R_b])`` rounds whose result is exact
    (``RᵀR = Σ_s R_sᵀR_s`` = the global Gram) for any reduction order.

    Example (single device; pass ``mesh=`` for the sharded path)::

        >>> import jax
        >>> from repro.configs import get_config
        >>> from repro.core.capture import (StreamingCalibrator,
        ...                                 to_list_params)
        >>> from repro.models import transformer as T
        >>> cfg = get_config("llama-mini").replace(
        ...     n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
        ...     head_dim=16, d_ff=64, vocab_size=128)
        >>> params, _ = T.init_model(cfg, jax.random.PRNGKey(0))
        >>> cal = StreamingCalibrator(to_list_params(params, cfg), cfg)
        >>> for i in range(2):
        ...     cal.ingest({"tokens": jax.random.randint(
        ...         jax.random.PRNGKey(i), (2, 16), 0, cfg.vocab_size)})
        >>> col = cal.finalize()
        >>> sorted(col.gram)[0], col.count[sorted(col.gram)[0]]
        ('decoder/run0/0/attn/wk', 64)
    """

    def __init__(self, list_params: Params, cfg: ModelConfig, *,
                 mesh=None, data_axes=("pod", "data"),
                 flush_every: int = 8, use_kernel: Optional[bool] = None,
                 whiten_tags=None, shard_grams_above: int = 4096):
        self.cfg = cfg
        self.tagged = tag_linears(list_params)
        self._weights, self._rebuild = _split_weights(self.tagged)
        self.mesh = mesh
        self.flush_every = max(1, flush_every)
        self.use_kernel = use_kernel
        self.shard_grams_above = shard_grams_above
        if whiten_tags is True:
            self.whiten = True
        elif whiten_tags:
            self.whiten = frozenset(whiten_tags)
        else:
            self.whiten = None
        self._dims: Optional[Dict[str, int]] = None
        self._routes: Dict[str, str] = {}
        self._accs = None
        self._step = None
        self._capture = None
        self._folds = ()
        self._make_zeros = None
        self._pending = None
        self._since_flush = 0
        self._host: Dict[str, Dict[str, np.ndarray]] = {}
        if mesh is not None:
            axes = tuple(a for a in data_axes if a in mesh.axis_names)
            if not axes:
                raise ValueError(
                    f"mesh axes {mesh.axis_names} share nothing with "
                    f"data_axes {data_axes}")
            self.data_axes = axes
            self.n_shards = axis_group_size(mesh, axes)
            # accumulator layouts resolve through the logical sharding
            # rules (dist.sharding): "gram_rows" for the row split of
            # sharded (D,D) accumulators, "calib_shard" for the
            # per-shard stack of whitening factors. The fold math rides
            # the batch split, so gram rows must shard a SUBSET of the
            # data axes and the factor stack must match them exactly.
            self.row_axes = tuple(
                a for a in _spec_axes(logical_spec(("gram_rows",), mesh))
                if a in axes)
            stack = _spec_axes(logical_spec(("calib_shard",), mesh))
            if tuple(a for a in stack if a in axes) != axes:
                raise ValueError(
                    f"calib_shard rule {stack} must cover the capture "
                    f"data axes {axes}: each data shard QR-updates its "
                    f"own factor over its slice of the batch")
        else:
            self.data_axes = ()
            self.n_shards = 1
            self.row_axes = ()

    # -- routing ------------------------------------------------------------
    def _route_of(self, tag: str, d: int) -> str:
        if _tag_whitened(self.whiten, tag):
            return "whiten"
        if (self.mesh is not None and self.shard_grams_above
                and self.row_axes
                and d >= self.shard_grams_above
                and d % axis_group_size(self.mesh, self.row_axes) == 0):
            return "sharded"
        return "replicated"

    @property
    def routes(self) -> Dict[str, str]:
        """tag -> accumulator route ('whiten' | 'sharded' | 'replicated');
        populated after the first ``ingest``."""
        return dict(self._routes)

    # -- step construction --------------------------------------------------
    def _tape_partials(self, weights, batch, raw=None):
        from repro.models import transformer as T
        tape = StreamingTape(self.use_kernel, whiten=self.whiten, raw=raw)
        with tape:
            T.forward(self._rebuild(weights), self.cfg, batch)
        return tape.partials, tape.xblocks

    def _build_step(self):
        """Single-device path: one fused jit (forward + fold)."""
        def step(accs, weights, batch):
            parts, xblocks = self._tape_partials(weights, batch)
            new = {}
            for tag, acc in accs.items():
                p = parts[tag]
                e = {"absx": acc["absx"] + p["absx"],
                     "count": acc["count"] + p["count"]}
                if "chol" in acc:
                    stacked = jnp.concatenate(
                        [acc["chol"], *xblocks[tag]], axis=0)
                    e["chol"] = jnp.linalg.qr(stacked, mode="r")
                else:
                    e["gram"] = acc["gram"] + p["gram"]
                new[tag] = e
            return new
        return jax.jit(step, donate_argnums=0)

    def _build_mesh_steps(self):
        """Mesh path: capture stage (per-shard partials, no collectives)
        plus per-route fold stages (all collectives + accumulator update).
        Folds are split so the whiten fold's LAPACK QR never shares an
        executable with the Gram folds' big GEMMs (XLA:CPU runs dots ~3×
        slower next to LAPACK custom calls; see numerics_jax)."""
        axes = self.data_axes
        mesh = self.mesh
        raw_tags = frozenset(t for t, r in self._routes.items()
                             if r in ("whiten", "sharded"))

        def part_spec(tag):
            key = "x" if tag in raw_tags else "gram"
            return {"absx": P(axes), "count": P(axes), key: P(axes)}

        def capture_body(weights, batch):
            parts, xblocks = self._tape_partials(weights, batch,
                                                 raw=raw_tags)
            out = {}
            for tag, p in parts.items():
                e = {"absx": p["absx"][None], "count": p["count"][None]}
                if tag in xblocks:
                    e["x"] = jnp.concatenate(xblocks[tag], axis=0)
                else:
                    e["gram"] = p["gram"][None]
                out[tag] = e
            return out

        capture = jax.jit(shard_map(
            capture_body, mesh=mesh, in_specs=(P(), P(axes)),
            out_specs={t: part_spec(t) for t in self._dims}))

        def stat_fold(acc, p):
            return {"absx": acc["absx"] + jax.lax.psum(p["absx"][0], axes),
                    "count": acc["count"]
                    + jax.lax.psum(p["count"][0], axes)}

        def fold_gram_body(accs, parts):
            new = {}
            for tag, acc in accs.items():
                p = parts[tag]
                e = stat_fold(acc, p)
                if "x" in p:        # sharded accumulator: row block of XᵀX
                    Xa = p["x"]
                    for a in reversed(axes):
                        Xa = jax.lax.all_gather(Xa, a, axis=0, tiled=True)
                    blk = acc["gram"].shape[0]      # local row-block size
                    off = combined_axis_index(self.row_axes, mesh) * blk
                    Xf = jax.lax.dynamic_slice_in_dim(Xa, off, blk, axis=1)
                    e["gram"] = acc["gram"] + jax.lax.dot_general(
                        Xf, Xa, (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                else:
                    e["gram"] = acc["gram"] + jax.lax.psum(p["gram"][0],
                                                           axes)
                new[tag] = e
            return new

        def fold_whiten_body(accs, parts):
            new = {}
            for tag, acc in accs.items():
                p = parts[tag]
                e = stat_fold(acc, p)
                stacked = jnp.concatenate([acc["chol"][0], p["x"]], axis=0)
                e["chol"] = jnp.linalg.qr(stacked, mode="r")[None]
                new[tag] = e
            return new

        def acc_spec(tag):
            route = self._routes[tag]
            stat = {"absx": P(), "count": P()}
            if route == "whiten":
                return {**stat, "chol": P(axes)}
            if route == "sharded":
                return {**stat, "gram": P(self.row_axes, None)}
            return {**stat, "gram": P()}

        folds = []
        for body, pred in ((fold_gram_body, ("replicated", "sharded")),
                           (fold_whiten_body, ("whiten",))):
            tags = sorted(t for t, r in self._routes.items() if r in pred)
            if not tags:
                continue
            sm = shard_map(
                body, mesh=mesh,
                in_specs=({t: acc_spec(t) for t in tags},
                          {t: part_spec(t) for t in tags}),
                out_specs={t: acc_spec(t) for t in tags})
            folds.append((tuple(tags), jax.jit(sm, donate_argnums=0)))
        return capture, tuple(folds)

    # -- accumulator construction -------------------------------------------
    def _fresh_accs(self):
        """Zeroed flushable accumulators (gram/absx/count) with the routed
        shardings. On a mesh the zeros are produced ON DEVICE by a jitted
        init with explicit ``out_shardings`` — flush resets every
        ``flush_every`` batches, and shipping host zero buffers (256 MB
        per sharded tag at D=16k) over H2D each time would serialize
        behind the pipelined capture/fold work. Whiten-route factors are
        NOT included: they are never reset (``_init_chol`` seeds them
        once; flush carries them over)."""
        if self.mesh is None:
            return _zero_accs(self._dims, self.whiten)
        if self._make_zeros is None:
            NS = jax.sharding.NamedSharding
            shapes: Dict[str, Dict] = {}
            shards: Dict[str, Dict] = {}
            for tag, d in self._dims.items():
                route = self._routes[tag]
                sh = {"absx": ((d,), jnp.float32),
                      "count": ((), jnp.int32)}
                sp = {"absx": NS(self.mesh, P()),
                      "count": NS(self.mesh, P())}
                if route == "sharded":
                    sh["gram"] = ((d, d), jnp.float32)
                    sp["gram"] = NS(self.mesh, P(self.row_axes, None))
                elif route == "replicated":
                    sh["gram"] = ((d, d), jnp.float32)
                    sp["gram"] = NS(self.mesh, P())
                shapes[tag], shards[tag] = sh, sp
            self._make_zeros = jax.jit(
                lambda: {t: {k: jnp.zeros(*s) for k, s in e.items()}
                         for t, e in shapes.items()},
                out_shardings=shards)
        return self._make_zeros()

    def _init_chol(self, accs) -> None:
        """Seed the per-shard whitening-factor stacks (first ingest only;
        a one-off H2D of zeros per whiten tag)."""
        NS = jax.sharding.NamedSharding
        for tag, d in self._dims.items():
            if self._routes[tag] == "whiten":
                accs[tag]["chol"] = jax.device_put(
                    np.zeros((self.n_shards, d, d), np.float32),
                    NS(self.mesh, P(self.data_axes)))

    # -- ingest / flush / finalize -----------------------------------------
    def ingest(self, batch: Dict) -> None:
        """Fold one calibration batch into the device accumulators."""
        with trace.span("calib_ingest", since_flush=self._since_flush):
            if self._accs is None:
                self._dims = discover_capture_dims(self.tagged, self.cfg,
                                                   batch)
                self._routes = {t: self._route_of(t, d)
                                for t, d in self._dims.items()}
                self._accs = self._fresh_accs()
                if self.mesh is None:
                    self._step = self._build_step()
                else:
                    self._init_chol(self._accs)
                    self._capture, self._folds = self._build_mesh_steps()
                    # replicated once, not re-sent with every batch
                    self._weights = jax.device_put(
                        self._weights,
                        jax.sharding.NamedSharding(self.mesh, P()))
            if self.mesh is None:
                self._accs = self._step(self._accs, self._weights, batch)
            else:
                # dispatch the next capture BEFORE reducing the previous
                # batch's partials: both are queued asynchronously, so the
                # fold's collectives overlap the new forward pass
                parts = self._capture(self._weights, batch)
                self._fold_pending()
                self._pending = parts
        self._since_flush += 1
        if self._since_flush >= self.flush_every:
            self.flush()

    def _fold_pending(self) -> None:
        if self._pending is None:
            return
        parts, self._pending = self._pending, None
        for tags, fold in self._folds:
            new = fold({t: self._accs[t] for t in tags},
                       {t: parts[t] for t in tags})
            self._accs.update(new)

    def flush(self) -> None:
        """Reduce pending partials, pull fp32 device accumulators to host,
        fold into fp64, reset. Sharded (D,D) accumulators reassemble on
        host (device_get gathers the row blocks); streaming-whitening
        factors stay resident on device (the QR chain is self-stabilizing;
        there is nothing to flush into fp64)."""
        if self._accs is None or self._since_flush == 0:
            return
        with trace.span("calib_flush", batches=self._since_flush):
            self._flush_inner()

    def _flush_inner(self) -> None:
        self._fold_pending()
        host = jax.device_get({
            tag: {k: v for k, v in acc.items() if k != "chol"}
            for tag, acc in self._accs.items()})
        for tag, acc in host.items():
            a = np.asarray(acc["absx"], dtype=np.float64)
            n = int(acc["count"])
            if tag in self._host:
                self._host[tag]["absx"] += a
                self._host[tag]["count"] += n
            else:
                self._host[tag] = {"absx": a, "count": n}
            if "gram" in acc:
                g = np.asarray(acc["gram"], dtype=np.float64)
                if "gram" in self._host[tag]:
                    self._host[tag]["gram"] += g
                else:
                    self._host[tag]["gram"] = g
        fresh = self._fresh_accs()
        for tag, acc in self._accs.items():
            if "chol" in acc:
                fresh[tag]["chol"] = acc["chol"]
        self._accs = fresh
        self._since_flush = 0

    def sync(self) -> None:
        """Drain the pending fold and block until in-flight device work is
        done (benchmarking / completion barrier)."""
        self._fold_pending()
        if self._accs is not None:
            jax.block_until_ready(self._accs)

    def finalize(self) -> Collector:
        """Return the fp64 host-side statistics as a Collector (drop-in for
        the compression driver). Whitened tags expose their running
        Cholesky factor as ``col.chol[tag]`` and have no Gram entry; on a
        mesh the per-shard factors are tree-reduced first (exact — see
        ``numerics_jax.tree_reduce_factors``)."""
        with trace.span("calib_finalize"):
            return self._finalize_inner()

    def _finalize_inner(self) -> Collector:
        self.flush()
        col = Collector()
        for tag, acc in self._host.items():
            if "gram" in acc:
                col.gram[tag] = acc["gram"]
            col.absmean[tag] = acc["absx"]
            col.count[tag] = acc["count"]
        if self._accs is not None:
            for tag, acc in self._accs.items():
                if "chol" in acc:
                    R = acc["chol"]
                    if self.mesh is not None:   # (n_shards, d, d) stack
                        from repro.core import numerics_jax as numj
                        R = numj.tree_reduce_factors(
                            jnp.asarray(jax.device_get(R)))
                    col.chol[tag] = np.asarray(
                        jax.device_get(R), dtype=np.float64)
        return col


def streaming_calibrate(list_params: Params, cfg: ModelConfig,
                        batches: Iterable[Dict], *, mesh=None,
                        flush_every: int = 8,
                        use_kernel: Optional[bool] = None,
                        whiten_tags=None,
                        shard_grams_above: int = 4096) -> Collector:
    """Run the device-side streaming capture over ``batches`` and return the
    finalized fp64 Collector (see ``StreamingCalibrator`` for the mesh,
    whitening and sharded-accumulator knobs)."""
    cal = StreamingCalibrator(list_params, cfg, mesh=mesh,
                              flush_every=flush_every, use_kernel=use_kernel,
                              whiten_tags=whiten_tags,
                              shard_grams_above=shard_grams_above)
    for batch in batches:
        cal.ingest(batch)
    return cal.finalize()


# ---------------------------------------------------------------------------
# List-form params + tagging
# ---------------------------------------------------------------------------
def _is_linear(d) -> bool:
    return isinstance(d, dict) and ("w" in d or ("B" in d and "C" in d))


def to_list_params(params: Params, cfg: ModelConfig) -> Params:
    """Stacked layer runs -> lists of per-layer trees (deep copy of refs).
    Already-list runs pass through. Non-run subtrees are kept as-is."""
    out = dict(params)

    def split_runs(stack: Dict, runs) -> Dict:
        new = dict(stack)
        for r, (_kind, n) in enumerate(runs):
            rp = stack[f"run{r}"]
            if isinstance(rp, list):
                new[f"run{r}"] = rp
            else:
                new[f"run{r}"] = [
                    jax.tree.map(lambda a: a[i], rp) for i in range(n)]
        return new

    out["decoder"] = split_runs(params["decoder"], cfg.layer_runs())
    if cfg.is_encoder_decoder:
        enc_cfg = cfg.replace(n_layers=cfg.n_encoder_layers,
                              sliding_window=0, local_global_pattern=(0, 0))
        out["encoder"] = split_runs(params["encoder"], enc_cfg.layer_runs())
    return out


def to_stacked_params(list_params: Params, cfg: ModelConfig) -> Params:
    """Inverse of ``to_list_params`` (only valid if per-layer trees have
    identical leaf shapes — i.e. uncompressed or rank-padded)."""
    out = dict(list_params)

    def join_runs(stack: Dict, runs) -> Dict:
        new = dict(stack)
        for r, (_kind, n) in enumerate(runs):
            rp = stack[f"run{r}"]
            if isinstance(rp, list):
                new[f"run{r}"] = jax.tree.map(lambda *a: jnp.stack(a), *rp)
        return new

    out["decoder"] = join_runs(list_params["decoder"], cfg.layer_runs())
    if cfg.is_encoder_decoder:
        enc_cfg = cfg.replace(n_layers=cfg.n_encoder_layers,
                              sliding_window=0, local_global_pattern=(0, 0))
        out["encoder"] = join_runs(list_params["encoder"],
                                   enc_cfg.layer_runs())
    return out


def tag_linears(list_params: Params) -> Params:
    """Returns a shallow-copied tree where every linear dict carries its
    path as ``"_tag"`` (and MoE subtrees carry a dispatch tag)."""

    def walk(node, path):
        if _is_linear(node):
            d = dict(node)
            d["_tag"] = "/".join(map(str, path))
            return d
        if isinstance(node, dict):
            d = {}
            for k, v in node.items():
                d[k] = walk(v, path + (k,))
            if "w_gate" in node and "router" in node:   # routed-expert subtree
                d["_tag"] = "/".join(map(str, path))
            return d
        if isinstance(node, list):
            return [walk(v, path + (i,)) for i, v in enumerate(node)]
        return node

    return walk(list_params, ())


def strip_tags(params: Params) -> Params:
    def walk(node):
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items() if k != "_tag"}
        if isinstance(node, list):
            return [walk(v) for v in node]
        return node

    return walk(params)
