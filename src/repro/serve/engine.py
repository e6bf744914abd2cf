"""Serving: prefill + single-token decode steps and a slot-based
continuous-batching driver, with a resilience layer (DESIGN.md §5).
All jit dispatch goes through an executable registry (``serve/aot.py``):
lazily traced by default, AOT-compiled from the persistent cache when
booted through ``repro.serve.api`` with ``aot=True`` (DESIGN.md §5.6).

The engine keeps a fixed pool of `batch` decode slots. Requests are admitted
into free slots (their prompt prefilled into that slot's cache region) and
retired when they emit `n_new` tokens; every decode step advances ALL active
slots at once (per-sequence positions — the cache layer supports (B,)
position vectors). Works identically for dense, compressed (factorized),
full-KV, sliding-window, SSM-state and enc-dec models.

Resilience (all opt-in via ``AdmissionConfig`` / constructor kwargs, the
default construction behaves exactly like the pre-resilience engine):

* **admission control** — bounded queue with explicit backpressure
  (``submit`` returns accept/reject), per-request deadlines shed overdue
  work before it wastes a prefill (``serve.admission``).
* **poison quarantine** — every prefill/decode emits through a finite
  guard; non-finite logits rows are attributed (bisected when ambiguous),
  their slots purged (cache row zeroed so later tenants of the slot can
  never attend into poisoned state), and the requests re-queued under a
  bounded retry budget, then failed with a typed error. Healthy slots
  never see a poisoned token.
* **elastic-rank degradation** — with ``elastic=True`` and factorized
  params, the batcher holds a pow2 rank-bucket ladder
  (``compress.slice_rank_ladder``) and drops decode rank under queue
  pressure instead of shedding, restoring it as the queue drains.
  Retrace-free beyond one compile per rung: the KV cache layout is
  rank-independent, so switching rungs just swaps the weight pytree.
* **liveness** — ``run_until_drained`` returns a ``DrainResult`` whose
  ``status`` distinguishes drained / timeout / stalled (watchdog on
  forward progress), and the step loop beats a ``dist.ft.Heartbeat``.
* **fault injection** — a ``dist.faultinject.FaultPlan`` drives
  seed-deterministic NaN/latency/heartbeat faults through the exact same
  code paths production faults would take (chaos suite:
  tests/test_resilience.py).
* **observability** — every stage is traced (``obs.trace`` spans:
  ``engine_step`` and, inside it, admit/prefill/decode_step/
  logits_wait/sample/emit/purge, each child with the ``step`` of its
  engine step; poison_probe instants, per-request async spans,
  queue-depth and rung counter tracks) and a flight recorder
  (``obs.flightrec``) rings recent events, auto-dumping an artifact on a
  typed request failure or a non-``drained`` drain (DESIGN.md §6).
"""
from __future__ import annotations

import dataclasses
import json
import time
import warnings
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.models import transformer as T
from repro.models.params import Params
from repro.obs import flightrec as frec
from repro.obs import trace
from repro.serve import admission as adm
from repro.serve import aot as aotlib


@dataclass(frozen=True)
class ServeConfig:
    batch: int = 8                # decode slot count
    max_len: int = 512            # cache capacity (prompt + generated)
    temperature: float = 0.0      # 0 => greedy
    seed: int = 0
    # --- paged KV pool (DESIGN.md §5.7) -----------------------------------
    kv_block: int = 0             # KV block size in tokens; 0 = contiguous
    #                               per-slot pool (the historical layout)
    prefix_cache: bool = False    # share identical prompt-prefix blocks
    #                               across requests (requires kv_block > 0)


@dataclass
class Request:
    rid: int
    tokens: np.ndarray            # prompt (S,)
    n_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    t_submit: float = 0.0
    t_done: float = 0.0
    # --- resilience fields (serve.admission / quarantine) -----------------
    deadline_s: Optional[float] = None   # relative to submit; None = none
    status: str = adm.QUEUED
    retries: int = 0              # poison-quarantine attempts consumed
    t_admit: float = 0.0
    t_first: float = 0.0          # first token emitted (TTFT anchor)
    error: Optional[str] = None   # set on typed failure
    truncated: bool = False       # prompt lost its oldest tokens at
    #                               admission (over max_len - 1)


class DrainResult(list):
    """``run_until_drained`` result: a list of completed requests (so the
    historical ``done = cb.run_until_drained()`` callers keep working)
    plus the drain verdict.

    ``status`` is ``"drained"`` (queue empty, all slots free),
    ``"timeout"`` (``max_steps`` exhausted with work still pending) or
    ``"stalled"`` (the watchdog saw no forward progress — tokens, shed or
    terminal transitions — for ``watchdog_s``). ``undrained`` lists the
    requests still queued or running; ``shed``/``rejected``/``failed``
    surface the terminal non-success populations."""

    def __init__(self, done: List[Request], status: str,
                 undrained: List[Request], shed: List[Request],
                 rejected: List[Request], failed: List[Request]):
        super().__init__(done)
        self.status = status
        self.undrained = undrained
        self.shed = shed
        self.rejected = rejected
        self.failed = failed


def _normalize_load_retries(retries, load_retries: int) -> int:
    """Fold the pre-API ``retries=`` spelling into ``load_retries=`` (the
    ``repro.serve.api`` name) with a deprecation warning."""
    if retries is not None:
        warnings.warn(
            "from_compressed(retries=...) is deprecated; use "
            "load_retries=... (repro.serve.api spelling)",
            DeprecationWarning, stacklevel=3)
        return int(retries)
    return load_retries


def from_compressed(ckpt_dir: str, cfg: ModelConfig,
                    scfg: Optional[ServeConfig] = None, *,
                    batcher: bool = True, verify: bool = False,
                    load_retries: int = 0,
                    quarantine: Optional[bool] = None,
                    **kwargs):
    """THE loading path for booting serve engines from a
    ``compress.save_plan`` artifact — ``Engine.from_compressed`` and
    ``ContinuousBatcher.from_compressed`` both delegate here (they used
    to carry diverged copies of the manifest handling), and
    ``repro.serve.api`` re-exports it.

    ``verify=True`` re-hashes the stored arrays against the manifest
    content hashes before booting; ``load_retries > 0`` retries a
    transiently failing load with backoff and (with ``quarantine``,
    default: on whenever retries are) moves a persistently failing
    artifact aside before raising a typed ``store.IntegrityError``.
    ``batcher=False`` returns the fixed-batch :class:`Engine` instead of
    the :class:`ContinuousBatcher`; extra kwargs (``admission``,
    ``faults``, ``heartbeat``, ``executables``) pass through to the
    batcher constructor.
    """
    from repro.core import compress as CC
    if quarantine is None:
        quarantine = load_retries > 0
    params, plan = CC.load_plan(ckpt_dir, cfg=cfg, verify=verify,
                                retries=load_retries, quarantine=quarantine)
    scfg = scfg if scfg is not None else ServeConfig()
    cls = ContinuousBatcher if batcher else Engine
    eng = cls(params, cfg, scfg, **kwargs)
    eng.plan = plan
    return eng


class Engine:
    def __init__(self, params: Params, cfg: ModelConfig, scfg: ServeConfig):
        self.params = params
        self.cfg = cfg
        self.scfg = scfg
        self.plan = None              # set when booted from a compressed ckpt
        self.stats: Dict[str, int] = {"prefill_retraces": 0,
                                      "decode_retraces": 0}

        def _decode_fn(p, c, t):
            self.stats["decode_retraces"] += 1
            return T.decode_step(p, cfg, c, t)

        # decode consumes the cache: its pool is updated in place
        self._decode = jax.jit(_decode_fn, donate_argnums=(1,))
        self._prefill_cache: Dict[int, object] = {}
        self.key = jax.random.PRNGKey(scfg.seed)

    def _prefill_fn(self, max_len: int):
        """Memoized jitted prefill per cache capacity. ``generate`` /
        ``measure_decode_throughput`` used to build a fresh ``jax.jit``
        closure every call, so every invocation retraced (and recompiled)
        the whole prefill even at identical shapes; the cache keys on
        ``max_len`` — the only trace-relevant closure capture — and the
        retrace counter makes the bound assertable."""
        fn = self._prefill_cache.get(max_len)
        if fn is None:
            cfg = self.cfg

            def _p(p, b):
                self.stats["prefill_retraces"] += 1
                return T.prefill(p, cfg, b, max_len=max_len)

            fn = jax.jit(_p)
            self._prefill_cache[max_len] = fn
        return fn

    @classmethod
    def from_compressed(cls, ckpt_dir: str, cfg: ModelConfig,
                        scfg: ServeConfig, verify: bool = False,
                        retries: Optional[int] = None,
                        load_retries: int = 0,
                        quarantine: Optional[bool] = None) -> "Engine":
        """Boot directly from a ``compress.save_plan`` artifact — no
        calibration or SVD at serve time; the factorized list-form params
        drop straight into the model code. Delegates to the unified
        module-level :func:`from_compressed` (one loading path for both
        engine flavors, re-exported from ``repro.serve.api``).
        ``verify=True`` re-hashes the stored arrays against the manifest
        content hashes first (``launch/serve.py --verify``).
        ``load_retries``/``quarantine`` retry-with-backoff a transiently
        failing load and move a persistently sha256-failing artifact
        aside before raising a typed ``store.IntegrityError``
        (``--load-retries``); ``retries=`` is the deprecated pre-API
        spelling of ``load_retries=``.

        Example (boot from an artifact and generate; continues the
        ``compress.save_plan`` example)::

            >>> import tempfile, jax, numpy as np
            >>> from repro.configs import get_config
            >>> from repro.core import compress as CC
            >>> from repro.models import transformer as T
            >>> from repro.serve.engine import Engine, ServeConfig
            >>> cfg = get_config("llama-mini").replace(
            ...     n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
            ...     head_dim=16, d_ff=64, vocab_size=128, rank_multiple=1)
            >>> params, _ = T.init_model(cfg, jax.random.PRNGKey(0))
            >>> calib = [{"tokens": jax.random.randint(
            ...     jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)}]
            >>> comp, plan = CC.build_plan_and_params(
            ...     params, cfg, CC.CompressionConfig(ratio=0.3), calib)
            >>> d = tempfile.mkdtemp()
            >>> _ = CC.save_plan(d, comp, plan, cfg)
            >>> eng = Engine.from_compressed(d, cfg, ServeConfig(),
            ...                              verify=True)
            >>> prompts = np.arange(8, dtype=np.int32).reshape(2, 4)
            >>> eng.generate(prompts, n_new=3).shape
            (2, 3)
        """
        return from_compressed(
            ckpt_dir, cfg, scfg, batcher=False, verify=verify,
            load_retries=_normalize_load_retries(retries, load_retries),
            quarantine=quarantine)

    # ---- batch generation (simple API, fixed same-length prompts) --------
    def generate(self, prompts: np.ndarray, n_new: int,
                 enc_embeds: Optional[np.ndarray] = None) -> np.ndarray:
        """prompts: (B, S) int32. Returns (B, n_new)."""
        batch = {"tokens": jnp.asarray(prompts)}
        if enc_embeds is not None:
            batch["enc_embeds"] = jnp.asarray(enc_embeds)
        max_len = prompts.shape[1] + n_new + 1
        logits, cache = self._prefill_fn(max_len)(self.params, batch)
        outs = []
        tok = self._sample(logits)
        for _ in range(n_new):
            outs.append(tok)
            logits, cache = self._decode(self.params, cache, tok)
            tok = self._sample(logits)
        return np.concatenate([np.asarray(t) for t in outs], axis=1)

    def _sample(self, logits: jax.Array) -> jax.Array:
        if self.scfg.temperature <= 0:
            return jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        self.key, sub = jax.random.split(self.key)
        return jax.random.categorical(
            sub, logits[:, -1] / self.scfg.temperature)[:, None].astype(
                jnp.int32)

    # ---- throughput measurement (Fig. 4 benchmark) ------------------------
    def measure_decode_throughput(self, batch: int, prompt_len: int,
                                  n_new: int, warmup: int = 3
                                  ) -> Dict[str, float]:
        prompts = np.random.default_rng(0).integers(
            0, self.cfg.vocab_size, size=(batch, prompt_len),
            dtype=np.int32)
        b = {"tokens": jnp.asarray(prompts)}
        if self.cfg.is_encoder_decoder:
            b["enc_embeds"] = jnp.zeros(
                (batch, prompt_len, self.cfg.d_model), dtype=jnp.float32)
        logits, cache = self._prefill_fn(
            prompt_len + warmup + n_new + 1)(self.params, b)
        tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        # warmup advances the cache (each step decodes a fresh position,
        # like the timed loop) and is safely skippable with warmup=0
        for _ in range(warmup):
            logits, cache = self._decode(self.params, cache, tok)
            tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        jax.block_until_ready(tok)
        t0 = time.perf_counter()
        for _ in range(n_new):
            logits, cache = self._decode(self.params, cache, tok)
            tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        jax.block_until_ready(logits)
        dt = time.perf_counter() - t0
        return {"tokens_per_s": batch * n_new / dt,
                "ms_per_step": dt / n_new * 1000.0}


def _put_shared(tree, device: jax.Device):
    """``jax.device_put`` leaf by leaf, keeping aliased leaves (a group's
    shared basis B) aliased on the device instead of copying each use."""
    memo: Dict[int, jax.Array] = {}

    def one(a):
        if id(a) not in memo:
            memo[id(a)] = jax.device_put(a, device)
        return memo[id(a)]
    return jax.tree.map(one, tree)


def _bucket_len(n: int, max_len: int) -> int:
    """Next power of two ≥ n (floor 2), capped at max_len. Bucketing prompt
    pads means `_prefill1` compiles once per bucket — at most
    ⌈log2(max_len)⌉ shapes — instead of once per distinct prompt length."""
    b = 2
    while b < n:
        b *= 2
    return min(b, max_len)


class ContinuousBatcher:
    """Slot-based continuous batching on top of per-slot caches.

    Every slot owns one row of a persistent batched cache; decode advances
    all live slots each step. Admission is BATCHED: all waiting requests
    that fit into free slots are prefilled together in one fixed-batch
    call, with prompts right-padded to a power-of-two bucket (per-row
    `lengths` keep ragged rows exact — padded cache slots are zeroed and
    masked). The freshly built rows then land in the pool via a single
    donated multi-row scatter. Retraces of the jitted prefill/decode/
    scatter steps are counted in `stats` — the bucketing invariant
    (≤ ⌈log2(max_len)⌉ prefill traces, 1 decode trace) is load-bearing for
    serving latency and asserted in tests.

    Architectures with recurrent state (ssm/lstm/enc-dec) can't right-pad
    a prompt without corrupting the state, so they take the exact-length
    admission path (one prefill trace per distinct prompt length).
    """

    @classmethod
    def from_compressed(cls, ckpt_dir: str, cfg: ModelConfig,
                        scfg: ServeConfig, verify: bool = False,
                        retries: Optional[int] = None,
                        load_retries: int = 0,
                        quarantine: Optional[bool] = None,
                        **kwargs) -> "ContinuousBatcher":
        """Boot the batcher from a saved compressed checkpoint. Delegates
        to the unified module-level :func:`from_compressed` (one loading
        path shared with ``Engine``; ``verify`` checks content hashes,
        ``load_retries``/``quarantine`` make the load resilient;
        ``retries=`` is the deprecated pre-API spelling). Extra kwargs
        (``admission``, ``faults``, ``heartbeat``, ``executables``) pass
        through to the constructor."""
        return from_compressed(
            ckpt_dir, cfg, scfg, batcher=True, verify=verify,
            load_retries=_normalize_load_retries(retries, load_retries),
            quarantine=quarantine, **kwargs)

    def __init__(self, params: Params, cfg: ModelConfig, scfg: ServeConfig,
                 admission: Optional[adm.AdmissionConfig] = None,
                 faults=None, heartbeat=None, executables=None,
                 flight: Optional[frec.FlightRecorder] = None,
                 device: Optional[jax.Device] = None):
        # ``device`` pins the weights and the KV pool to one local device
        # (a replica per chip); every other input is uncommitted and
        # follows them there
        if device is not None:
            params = _put_shared(params, device)
        self.params, self.cfg, self.scfg = params, cfg, scfg
        self.plan = None
        self.acfg = admission or adm.AdmissionConfig()
        self.faults = faults          # dist.faultinject.FaultPlan or None
        self.heartbeat = heartbeat    # dist.ft.Heartbeat or None
        # always-on event ring; only writes when flight.dump_dir is set
        self.flight = flight if flight is not None else frec.FlightRecorder()
        kinds = {k for k, _ in cfg.layer_runs()}
        self.bucketed = (kinds <= {"attn", "swa"}
                         and not cfg.is_encoder_decoder)
        # --- paged KV pool (DESIGN.md §5.7) -------------------------------
        self.paged = scfg.kv_block > 0
        if scfg.prefix_cache and not self.paged:
            raise ValueError("prefix_cache requires kv_block > 0")
        if self.paged:
            if scfg.max_len % scfg.kv_block:
                raise ValueError(
                    f"kv_block={scfg.kv_block} must divide "
                    f"max_len={scfg.max_len}")
            if kinds != {"attn"} or cfg.is_encoder_decoder:
                raise ValueError(
                    "paged KV cache requires a pure-attention decoder "
                    f"(got layer kinds {sorted(kinds)})")
            from repro.serve import paged as pglib
            self.nb = scfg.max_len // scfg.kv_block
            # worst case every slot holds a full-length row, +1 for the
            # reserved null block — without prefix sharing allocation can
            # never fail; sharing only frees headroom
            self.n_blocks = scfg.batch * self.nb + 1
            self.cache = T.init_cache_paged(cfg, scfg.batch,
                                            self.n_blocks, scfg.kv_block)
            self.pool = pglib.BlockPool(self.n_blocks)
            self.prefix = (pglib.PrefixCache(scfg.kv_block)
                           if scfg.prefix_cache else None)
            self.table = np.zeros((scfg.batch, self.nb), dtype=np.int32)
            self._table_dev = None          # cached device copy
            self._req_blocks: Dict[int, tuple] = {}  # rid -> (held, nshared)
        else:
            self.cache = T.init_cache(cfg, scfg.batch, scfg.max_len)
        if device is not None:
            self.cache = jax.device_put(self.cache, device)
        self.slots: List[Optional[Request]] = [None] * scfg.batch
        self.tokens = jnp.zeros((scfg.batch, 1), dtype=jnp.int32)
        self.done: List[Request] = []
        self.failed: List[Request] = []
        self._metrics = adm.ServeMetrics()
        self.admission = adm.AdmissionController(self.acfg, self._metrics)
        self._step_idx = 0
        self._progress = 0            # bumps on any forward progress
        # streaming hooks (serve/frontdoor.py): called on the engine
        # thread as tokens are emitted / requests reach terminal states /
        # a quarantine rewinds a request's output
        self.on_token: Optional[Callable[[Request, int], None]] = None
        self.on_terminal: Optional[Callable[[Request], None]] = None
        self.on_rewind: Optional[Callable[[Request], None]] = None
        # elastic-rank ladder: rung 0 is self.params ITSELF (token-identical
        # to the pre-ladder engine); rung ℓ slices the singular-value-
        # ordered factors to the pow2 bucket pow2_ceil(k) >> ℓ. Dense
        # params have no factors to slice — the ladder stays length 1.
        self.level = 0
        if self.acfg.elastic:
            from repro.core.compress import slice_rank_ladder
            self.ladder = slice_rank_ladder(params,
                                            levels=self.acfg.elastic_levels)
            if len(self.ladder) > 1 and self.ladder[1] is params:
                self.ladder = [params]
        else:
            self.ladder = [params]
        self.stats: Dict[str, int] = {
            "prefill_retraces": 0, "decode_retraces": 0,
            "scatter_retraces": 0, "admissions": 0, "admitted": 0,
        }
        # executable registry: all prefill/decode/scatter/purge dispatch
        # goes through one object (serve/aot.py). The default traced
        # registry reproduces the historical lazy-jit behavior (and its
        # retrace counters) exactly; an AotRegistry swaps every entry
        # point for an ahead-of-time compiled executable backed by the
        # persistent cache.
        self.exec = executables if executables is not None \
            else aotlib.TracedRegistry(cfg, scfg)
        self.exec.bind_stats(self.stats)

    def warm_executables(self) -> None:
        """Precompile (or cache-load) the full serving surface for this
        batcher's ladder — a no-op for the traced registry; for an
        ``AotRegistry`` this is the boot step that makes the steady-state
        loop trace-free (see ``repro.serve.api.load_engine``)."""
        self.exec.warm(self.ladder, self.bucketed, paged=self.paged)

    # ---- streaming emission (frontdoor hooks) ----------------------------
    def _emit_token(self, req: Request, tok: int) -> None:
        if self.on_token is not None:
            self.on_token(req, tok)

    def _emit_terminal(self, req: Request) -> None:
        trace.async_end("request", req.rid, status=req.status)
        if self.on_terminal is not None:
            self.on_terminal(req)

    def _emit_rewind(self, req: Request) -> None:
        if self.on_rewind is not None:
            self.on_rewind(req)

    # ---- intake ----------------------------------------------------------
    @property
    def queue(self) -> List[Request]:
        return self.admission.queue

    def submit(self, req: Request) -> bool:
        """Offer a request. Returns True iff admitted to the wait queue;
        False means backpressure (queue at ``max_queue`` — the request is
        marked ``shed_queue_full`` and kept in ``admission.rejected``)."""
        trace.async_begin("request", req.rid, n_new=req.n_new,
                          prompt_len=len(req.tokens))
        ok = self.admission.offer(req, time.perf_counter())
        if not ok:
            trace.async_end("request", req.rid, status=req.status)
            self.flight.note("reject", rid=req.rid, status=req.status)
        return ok

    def _params_now(self) -> Params:
        return self.ladder[self.level]

    def _adjust_rank_level(self) -> None:
        depth = len(self.queue)
        prev = self.level
        if (depth >= self.acfg.degrade_above
                and self.level < len(self.ladder) - 1):
            self.level += 1
        elif depth <= self.acfg.restore_below and self.level > 0:
            self.level -= 1
        if self.level != prev:
            trace.instant("rung_transition", frm=prev, to=self.level,
                          queue_depth=depth)
            self.flight.note("rung", frm=prev, to=self.level,
                             queue_depth=depth, step=self._step_idx)

    # ---- admission -------------------------------------------------------
    def _admit(self, step: int) -> None:
        free = [i for i, r in enumerate(self.slots) if r is None]
        admit, shed = self.admission.take(len(free), time.perf_counter())
        for req in shed:
            self.flight.note("shed", rid=req.rid, status=req.status)
            self._emit_terminal(req)
        admit = [r for r in admit if self._check_length(r)]
        if not admit:
            return
        with trace.span("admit", step=step, n=len(admit), level=self.level):
            self.flight.note("admit", rids=[r.rid for r in admit],
                             level=self.level)
            if self.paged:
                n_adm = self._admit_paged(admit, free[:len(admit)], step)
            elif self.bucketed:
                self._admit_batched(admit, free[:len(admit)], step)
                n_adm = len(admit)
            else:
                for req, slot in zip(admit, free):
                    self._admit_exact(req, slot, step)
                n_adm = len(admit)
        self.stats["admissions"] += 1
        self.stats["admitted"] += n_adm

    def _check_length(self, req: Request) -> bool:
        """Over-long prompt policy at admission. Cache rows hold prompt +
        generated tokens, so a prompt can keep at most ``max_len - 1``
        tokens. Default: keep the NEWEST tokens (degrade, not crash) —
        but counted, flight-recorded and flagged on the request's
        terminal result instead of silent. With
        ``AdmissionConfig.reject_overlong`` the request is shed typed
        (``shed_overlong``) before it wastes a prefill."""
        keep = self.scfg.max_len - 1
        n = len(req.tokens)
        if n <= keep:
            return True
        if self.acfg.reject_overlong:
            req.status = adm.SHED_OVERLONG
            self._metrics.bump("shed_overlong")
            self.admission.shed.append(req)
            self.flight.note("shed", rid=req.rid, status=req.status,
                             prompt_len=n, max_len=self.scfg.max_len)
            self._emit_terminal(req)
            self._progress += 1          # terminal transition
            return False
        req.tokens = req.tokens[-keep:]
        req.truncated = True
        self._metrics.bump("prompt_truncations")
        self.flight.note("truncate", rid=req.rid, kept=keep,
                         dropped=n - keep)
        return True

    def _poison_rid_rows(self, reqs: Sequence[Request],
                         last: np.ndarray) -> None:
        """Persistent content-poison injection (FaultPlan.poison_rids):
        corrupt the host-side logits row of marked requests."""
        if self.faults is None:
            return
        for j, req in enumerate(reqs):
            if req is not None and self.faults.rid_is_poison(req.rid):
                last[j] = np.nan

    def _admit_batched(self, admit: List[Request], free: List[int],
                       step: int) -> None:
        """All admitted prompts in ONE fixed-batch bucketed prefill,
        emitted through the finite guard."""
        B = self.scfg.batch
        Sb = _bucket_len(max(len(r.tokens) for r in admit),
                         self.scfg.max_len)
        toks = np.zeros((B, Sb), dtype=np.int32)
        lens = np.ones((B,), dtype=np.int32)
        slots = np.full((B,), B, dtype=np.int32)       # B = dropped row
        for j, (req, slot) in enumerate(zip(admit, free)):
            toks[j, :len(req.tokens)] = req.tokens
            lens[j] = len(req.tokens)
            slots[j] = slot
        with trace.span("prefill", step=step, bucket=Sb, n=len(admit),
                        level=self.level,
                        real_tokens=int(lens[:len(admit)].sum())):
            logits, c1 = self.exec.prefill(
                self._params_now(), {"tokens": jnp.asarray(toks),
                                     "lengths": jnp.asarray(lens)},
                level=self.level, bucket=Sb)
            self.cache = self.exec.scatter(self.cache, c1,
                                           jnp.asarray(slots))
        with trace.span("logits_wait", step=step):
            last = np.array(logits[:, -1])     # (B, V) writable host copy
        with trace.span("sample", step=step):
            if self.faults is not None:
                for j in self.faults.prefill_rows_to_poison(
                        self.stats["admissions"], len(admit)):
                    last[j] = np.nan
            self._poison_rid_rows(admit + [None] * (B - len(admit)), last)
            finite = np.isfinite(last).all(axis=-1)
            tok = last.argmax(-1).astype(np.int32)
            tok[~finite] = 0
            self.tokens = self.tokens.at[jnp.asarray(slots), 0].set(
                jnp.asarray(tok), mode="drop")
        bad: List[int] = []
        with trace.span("emit", step=step):
            now = time.perf_counter()
            for j, (req, slot) in enumerate(zip(admit, free)):
                if finite[j]:
                    req.out.append(int(tok[j]))
                    self._emit_token(req, int(tok[j]))
                    req.t_first = req.t_first or now
                    self._metrics.observe_ttft(now - req.t_submit)
                    self.slots[slot] = req
                    self._progress += 1
                else:
                    bad.append(j)
        if bad:
            ambiguous = len(bad) == len(admit) and len(admit) > 1
            self._purge_slots([free[j] for j in bad])
            self._quarantine([admit[j] for j in bad], ambiguous)

    def _admit_exact(self, req: Request, slot: int, step: int) -> None:
        """Exact-length single-row admission (recurrent-state archs)."""
        with trace.span("prefill", step=step, exact=len(req.tokens),
                        level=self.level, real_tokens=len(req.tokens)):
            logits, c1 = self.exec.prefill(
                self._params_now(),
                {"tokens": jnp.asarray(req.tokens[None, :])},
                level=self.level)
            self.cache = self.exec.scatter(
                self.cache, c1, jnp.asarray([slot], dtype=np.int32))
        with trace.span("logits_wait", step=step):
            last = np.array(logits[:, -1])
        with trace.span("sample", step=step):
            self._poison_rid_rows([req], last)
            finite = bool(np.isfinite(last[0]).all())
            if finite:
                t = int(last[0].argmax())
                self.tokens = self.tokens.at[slot, 0].set(t)
        if not finite:
            self._purge_slots([slot])
            self._quarantine([req], ambiguous=False)
            return
        with trace.span("emit", step=step):
            req.out.append(t)
            self._emit_token(req, t)
            now = time.perf_counter()
            req.t_first = req.t_first or now
            self._metrics.observe_ttft(now - req.t_submit)
            self.slots[slot] = req
            self._progress += 1

    # ---- paged admission (DESIGN.md §5.7) --------------------------------
    def _table_jnp(self) -> jax.Array:
        if self._table_dev is None:
            self._table_dev = jnp.asarray(self.table)
        return self._table_dev

    def _kv_gauges(self) -> None:
        r = self._metrics.registry
        r.gauge("kv_blocks_in_use").set(self.pool.in_use)
        r.gauge("kv_blocks_peak").set(self.pool.peak_in_use)

    def _admit_paged(self, admit: List[Request], free: List[int],
                     step: int) -> int:
        """Paged admission: plan each request against the prefix cache,
        allocate/refcount its blocks into a table row, COW-fork partial
        matches, then prefill in (at most) two fixed-batch groups —
        fresh rows through the plain bucketed prefill, prefix-extending
        rows through ``prefill_ext`` — and route both results into the
        arena with the table-indirected scatter. Requests the pool can't
        hold (only possible with prefix sharing pinning blocks) requeue
        at the front. Returns the number actually admitted."""
        B = self.scfg.batch
        bk = self.scfg.kv_block
        plans: List[tuple] = []           # (req, slot, start)
        cow_src: List[int] = []
        cow_dst: List[int] = []
        deferred: List[Request] = []
        for req, slot in zip(admit, free):
            if deferred:                  # keep FIFO: defer the rest too
                deferred.append(req)
                continue
            n = len(req.tokens)
            need = -(-min(n + req.n_new, self.scfg.max_len) // bk)
            plan = (self.prefix.plan(req.tokens)
                    if self.prefix is not None else None)
            shared = plan.shared if plan is not None else []
            n_alloc = need - len(shared)
            if self.prefix is not None:
                while not self.pool.can_alloc(n_alloc):
                    if not self.prefix.evict_lru(self.pool):
                        break
                    self._metrics.bump("prefix_evictions")
            fresh = self.pool.alloc(n_alloc)
            if fresh is None:
                deferred.append(req)
                continue
            held = [e.block for e in shared]
            for b in held:
                self.pool.incref(b)
            held.extend(fresh)
            row = np.zeros((self.nb,), dtype=np.int32)
            row[:len(held)] = held
            self.table[slot] = row
            start = 0
            if plan is not None:
                start = plan.start
                if plan.cow_src:
                    cow_src.append(plan.cow_src)
                    cow_dst.append(fresh[0])
                    self._metrics.bump("cow_forks")
                self._metrics.bump(
                    "prefix_hits" if start > 0 else "prefix_misses")
            self._req_blocks[req.rid] = (held, len(shared))
            plans.append((req, slot, start))
        for req in reversed(deferred):
            self.admission.requeue(req)
        if not plans:
            return 0
        self._table_dev = None
        self._kv_gauges()
        if cow_src:
            src = np.full((B,), self.n_blocks, dtype=np.int32)
            dst = np.full((B,), self.n_blocks, dtype=np.int32)
            src[:len(cow_src)] = cow_src
            dst[:len(cow_dst)] = cow_dst
            self.cache = self.exec.copy_blocks(
                self.cache, jnp.asarray(src), jnp.asarray(dst))
        tbl = self._table_jnp()
        g0 = [j for j, p in enumerate(plans) if p[2] == 0]
        g1 = [j for j, p in enumerate(plans) if p[2] > 0]
        last_rows: List[Optional[np.ndarray]] = [None] * len(plans)
        for grp, ext in ((g0, False), (g1, True)):
            if not grp:
                continue
            Sg = _bucket_len(
                max(len(plans[j][0].tokens) - plans[j][2] for j in grp),
                self.scfg.max_len)
            toks = np.zeros((B, Sg), dtype=np.int32)
            lens = np.ones((B,), dtype=np.int32)
            starts = np.zeros((B,), dtype=np.int32)
            slots = np.full((B,), B, dtype=np.int32)    # B = dropped row
            for row, j in enumerate(grp):
                req, slot, start = plans[j]
                t = np.asarray(req.tokens[start:], dtype=np.int32)
                toks[row, :len(t)] = t
                lens[row] = len(t)
                starts[row] = start
                slots[row] = slot
            with trace.span("prefill", step=step, bucket=Sg, n=len(grp),
                            level=self.level, ext=ext,
                            real_tokens=int(lens[:len(grp)].sum())):
                if ext:
                    # arena gather wants the table row of each BATCH row
                    rtbl = jnp.asarray(
                        self.table[np.minimum(slots, B - 1)])
                    logits, c1 = self.exec.prefill_ext(
                        self._params_now(),
                        {"tokens": jnp.asarray(toks),
                         "lengths": jnp.asarray(lens),
                         "starts": jnp.asarray(starts)},
                        self.cache, rtbl, level=self.level, bucket=Sg)
                else:
                    logits, c1 = self.exec.prefill(
                        self._params_now(),
                        {"tokens": jnp.asarray(toks),
                         "lengths": jnp.asarray(lens)},
                        level=self.level, bucket=Sg)
                self.cache = self.exec.scatter_paged(
                    self.cache, c1, jnp.asarray(slots), tbl,
                    jnp.asarray(starts))
            with trace.span("logits_wait", step=step):
                gl = np.array(logits[:, -1])
            for row, j in enumerate(grp):
                last_rows[j] = gl[row]
        reqs = [p[0] for p in plans]
        with trace.span("sample", step=step):
            last = np.stack(last_rows)                 # (n_plans, V)
            if self.faults is not None:
                for j in self.faults.prefill_rows_to_poison(
                        self.stats["admissions"], len(plans)):
                    last[j] = np.nan
            self._poison_rid_rows(reqs, last)
            finite = np.isfinite(last).all(axis=-1)
            tok = last.argmax(-1).astype(np.int32)
            tok[~finite] = 0
            tokj = np.zeros((B,), dtype=np.int32)
            slotj = np.full((B,), B, dtype=np.int32)
            for j, (req, slot, start) in enumerate(plans):
                tokj[j] = tok[j]
                slotj[j] = slot
            self.tokens = self.tokens.at[jnp.asarray(slotj), 0].set(
                jnp.asarray(tokj), mode="drop")
        bad: List[int] = []
        with trace.span("emit", step=step):
            now = time.perf_counter()
            for j, (req, slot, start) in enumerate(plans):
                if finite[j]:
                    req.out.append(int(tok[j]))
                    self._emit_token(req, int(tok[j]))
                    req.t_first = req.t_first or now
                    self._metrics.observe_ttft(now - req.t_submit)
                    self.slots[slot] = req
                    self._progress += 1
                    if self.prefix is not None:
                        self.prefix.register(np.asarray(req.tokens),
                                             self.table[slot], self.pool)
                else:
                    bad.append(j)
        if bad:
            ambiguous = len(bad) == len(plans) and len(plans) > 1
            self._purge_slots([plans[j][1] for j in bad],
                              [plans[j][0] for j in bad])
            self._quarantine([plans[j][0] for j in bad], ambiguous)
        return len(plans)

    def _host_release(self, rows: List[int], reqs: List[Request],
                      contaminated: bool) -> List[int]:
        """Drop each request's block references and clear its table row.
        ``contaminated`` (poison purge): prefix-cache entries built on
        the request's own (fresh) blocks are evicted first, and every
        block whose refcount hits zero is returned for device zeroing —
        while shared prefix blocks another holder still references
        survive untouched. Clean retirement frees without zeroing (a
        freed block is unreachable: no table row points at it, and
        masked positions contribute exact zeros)."""
        zero: List[int] = []
        for slot, req in zip(rows, reqs):
            held, nshared = self._req_blocks.pop(req.rid, ([], 0))
            if contaminated and self.prefix is not None:
                fresh = held[nshared:]
                if fresh:
                    n = self.prefix.evict_blocks(fresh, self.pool)
                    if n:
                        self._metrics.bump("prefix_evictions", n)
            for b in held:
                if self.pool.decref(b) and contaminated:
                    zero.append(b)
            self.table[slot] = 0
        self._table_dev = None
        self._kv_gauges()
        return zero

    def _release_retired(self, rows: List[int],
                         reqs: List[Request]) -> None:
        """Return a retired request's blocks to the pool (no zeroing) and
        mark its slot row dead (pos = -1) so later decode steps neither
        write through the cleared table row nor emit junk."""
        self._host_release(rows, reqs, contaminated=False)
        B = self.scfg.batch
        pad = np.full((B,), B, dtype=np.int32)
        pad[:len(rows)] = rows
        blk = np.full((B * self.nb,), self.n_blocks, dtype=np.int32)
        self.cache = self.exec.purge_paged(self.cache, jnp.asarray(pad),
                                           jnp.asarray(blk))

    # ---- poison quarantine -----------------------------------------------
    def _purge_slots(self, rows: List[int],
                     reqs: Optional[List[Request]] = None) -> None:
        """Quarantine slot cleanup. Contiguous pool: zero the cache rows
        + next-token entries. Paged pool (``reqs`` required — the block
        bookkeeping is per-request): release the requests' blocks, zero
        exactly the blocks whose refcount hit zero (shared prefix blocks
        another request or the cache still holds are never zeroed — the
        other holders' content is untouched by the poisoned row), and
        mark the rows dead."""
        with trace.span("purge", step=self._step_idx - 1, rows=list(rows)):
            B = self.scfg.batch
            pad = np.full((B,), B, dtype=np.int32)
            pad[:len(rows)] = rows
            jrows = jnp.asarray(pad)
            if self.paged:
                zero = self._host_release(rows, list(reqs or []),
                                          contaminated=True)
                blk = np.full((B * self.nb,), self.n_blocks,
                              dtype=np.int32)
                blk[:len(zero)] = zero
                self.cache = self.exec.purge_paged(self.cache, jrows,
                                                   jnp.asarray(blk))
            else:
                self.cache = self.exec.purge(self.cache, jrows)
            self.tokens = self.tokens.at[jrows, 0].set(0, mode="drop")
        self._metrics.bump("slot_purges", len(rows))

    def _probe(self, reqs: List[Request]) -> np.ndarray:
        """Replay each suspect's (prompt + emitted tokens) in isolation —
        one bucketed prefill, no cache writes — and report per-row
        finiteness. Reuses the admission prefill executables, so probing
        adds no new traces."""
        self._metrics.bump("poison_probes")
        trace.instant("poison_probe", rids=[r.rid for r in reqs])
        seqs = []
        keep = self.scfg.max_len - 1
        for r in reqs:
            s = np.concatenate([np.asarray(r.tokens, dtype=np.int32),
                                np.asarray(r.out, dtype=np.int32)])
            seqs.append(s[-keep:])
        if self.bucketed:
            B = self.scfg.batch
            Sb = _bucket_len(max(len(s) for s in seqs), self.scfg.max_len)
            toks = np.zeros((B, Sb), dtype=np.int32)
            lens = np.ones((B,), dtype=np.int32)
            for j, s in enumerate(seqs):
                toks[j, :len(s)] = s
                lens[j] = len(s)
            logits, _ = self.exec.prefill(
                self._params_now(), {"tokens": jnp.asarray(toks),
                                     "lengths": jnp.asarray(lens)},
                level=self.level, bucket=Sb)
            last = np.array(logits[:, -1])
            self._poison_rid_rows(reqs + [None] * (B - len(reqs)), last)
            return np.isfinite(last).all(axis=-1)[:len(reqs)]
        verdict = np.zeros((len(reqs),), dtype=bool)
        for j, s in enumerate(seqs):
            logits, _ = self.exec.prefill(
                self._params_now(), {"tokens": jnp.asarray(s[None, :])},
                level=self.level)
            last = np.array(logits[:, -1])
            self._poison_rid_rows([reqs[j]], last)
            verdict[j] = bool(np.isfinite(last[0]).all())
        return verdict

    def _bisect_poison(self, reqs: List[Request]
                       ) -> tuple[List[Request], List[Request]]:
        """Attribute an ambiguous (every-live-row non-finite) poison event
        to the offending request(s) by bisection: replay suspects in
        isolation; a subset that still comes back all-bad splits in half
        until single offenders remain. Returns (offenders, collateral)."""
        verdict = self._probe(reqs)
        if verdict.all():
            return [], list(reqs)
        if not verdict.any() and len(reqs) > 1:
            mid = len(reqs) // 2
            o1, c1 = self._bisect_poison(reqs[:mid])
            o2, c2 = self._bisect_poison(reqs[mid:])
            return o1 + o2, c1 + c2
        offenders = [r for r, ok in zip(reqs, verdict) if not ok]
        collateral = [r for r, ok in zip(reqs, verdict) if ok]
        return offenders, collateral

    def _quarantine(self, reqs: List[Request], ambiguous: bool) -> None:
        """Evict poisoned requests: re-queue (front, retry budget) or fail
        typed. ``ambiguous=True`` means every live row was non-finite at
        once — bisect to the offender(s) first; proven-healthy collateral
        re-queues without consuming its retry budget, but only when an
        actual offender was identified (otherwise the event was a
        transient engine fault and everyone pays one retry, so a
        persistently faulty engine still terminates typed instead of
        looping forever)."""
        self._metrics.bump("poison_events")
        self.flight.note("poison", rids=[r.rid for r in reqs],
                         ambiguous=ambiguous, level=self.level,
                         step=self._step_idx)
        offenders, collateral = (self._bisect_poison(reqs) if ambiguous
                                 else (list(reqs), []))
        if not offenders:       # transient: no culprit to exonerate against
            charge, collateral = collateral, []
        else:
            charge = offenders
        for req in collateral:
            req.out = []
            req.t_first = 0.0
            self._emit_rewind(req)
            self.admission.requeue(req)
        for req in charge:
            req.retries += 1
            self._metrics.bump("poison_retries")
            if req.retries > self.acfg.max_retries:
                req.status = adm.FAILED_POISON
                req.error = (f"non-finite logits after {req.retries} "
                             f"attempts (retry budget "
                             f"{self.acfg.max_retries})")
                req.t_done = time.perf_counter()
                self.failed.append(req)
                self._metrics.bump("poison_failures")
                self._progress += 1          # terminal transition
                self.flight.note("fail", rid=req.rid, level=self.level,
                                 retries=req.retries, error=req.error)
                self.dump_flight("failed_poison",
                                 {"rid": req.rid, "error": req.error})
                self._emit_terminal(req)
            else:
                req.out = []
                req.t_first = 0.0
                self._emit_rewind(req)
                self.admission.requeue(req)

    # ---- step loop -------------------------------------------------------
    def step(self) -> int:
        """One engine iteration: beat liveness, shed overdue work, admit,
        one decode step for all live slots through the finite guard.
        Returns the number of healthy live slots stepped."""
        t0 = time.perf_counter()
        with trace.span("engine_step", step=self._step_idx):
            n = self._step_inner()
        wall_ms = (time.perf_counter() - t0) * 1e3
        self._metrics.observe_step_ms(wall_ms)
        self.flight.step_timing(self._step_idx - 1, wall_ms, n)
        return n

    def _step_inner(self) -> int:
        idx = self._step_idx
        self._step_idx += 1
        if self.heartbeat is not None:
            self.heartbeat.beat(idx)
        if self.faults is not None:
            if self.faults.wedged(idx):
                return 0                     # hung engine: no progress
            stall = self.faults.stall_for(idx)
            if stall:
                time.sleep(stall)
        self._adjust_rank_level()
        self._metrics.step_at_level(self.level)
        self._metrics.observe_queue_depth(len(self.queue))
        trace.counter("serve", queue_depth=len(self.queue),
                      rank_level=self.level)
        self._admit(idx)
        live = [i for i, r in enumerate(self.slots) if r is not None]
        if not live:
            return 0
        # the context each live slot attends this step (prompt and tokens
        # so far), counted only for the span
        ctx = (sum(len(self.slots[i].tokens) + len(self.slots[i].out)
                   for i in live) if trace.enabled() else 0)
        with trace.span("decode_step", step=idx, live=len(live),
                        level=self.level, ctx_tokens=ctx):
            if self.paged:
                logits, self.cache = self.exec.decode_paged(
                    self._params_now(), self.cache, self.tokens,
                    self._table_jnp(), level=self.level)
            else:
                logits, self.cache = self.exec.decode(
                    self._params_now(), self.cache, self.tokens,
                    level=self.level)
        with trace.span("logits_wait", step=idx):
            last = np.array(logits[:, -1])     # (B, V) writable host copy
        with trace.span("sample", step=idx):
            if self.faults is not None:
                for row in self.faults.decode_rows_to_poison(idx, live):
                    last[row] = np.nan
            self._poison_rid_rows(self.slots, last)
            finite = np.isfinite(last).all(axis=-1)
            nxt = last.argmax(-1).astype(np.int32)
            good = [i for i in live if finite[i]]
            bad = [i for i in live if not finite[i]]
            nxt[~finite] = 0                 # poisoned tokens never emitted
            self.tokens = jnp.asarray(nxt[:, None])
        with trace.span("emit", step=idx, n=len(good)):
            retired_rows: List[int] = []
            retired_reqs: List[Request] = []
            for i in good:
                req = self.slots[i]
                req.out.append(int(nxt[i]))
                self._emit_token(req, int(nxt[i]))
                self._progress += 1
                if len(req.out) >= req.n_new:
                    req.t_done = time.perf_counter()
                    req.status = adm.DONE
                    self._metrics.bump("completed")
                    self.done.append(req)
                    self.slots[i] = None
                    if self.paged:
                        retired_rows.append(i)
                        retired_reqs.append(req)
                    self._emit_terminal(req)
            if retired_rows:
                self._release_retired(retired_rows, retired_reqs)
        if bad:
            ambiguous = len(bad) == len(live) and len(live) > 1
            reqs = [self.slots[i] for i in bad]
            for i in bad:
                self.slots[i] = None
            self._purge_slots(bad, reqs)
            self._quarantine(reqs, ambiguous)
        return len(good)

    def run_until_drained(self, max_steps: int = 100000,
                          watchdog_s: Optional[float] = None
                          ) -> DrainResult:
        """Step until the queue and slots drain. Returns a ``DrainResult``
        (list of completed requests + ``status``): ``"drained"`` on a
        clean drain, ``"timeout"`` when ``max_steps`` is exhausted with
        work still pending (the old silent-return failure mode), and
        ``"stalled"`` when ``watchdog_s`` elapses with no forward
        progress (no token emitted, nothing shed or failed) — a wedged
        engine is reported, not spun on."""
        status = "drained"
        last_progress = time.perf_counter()
        for _ in range(max_steps):
            if not self.queue and all(s is None for s in self.slots):
                break
            before = (self._progress
                      + self._metrics.count("shed_deadline"))
            self.step()
            now = time.perf_counter()
            if (self._progress
                    + self._metrics.count("shed_deadline")) > before:
                last_progress = now
            elif (watchdog_s is not None
                    and now - last_progress > watchdog_s):
                status = "stalled"
                break
        else:
            status = "timeout"
        undrained = ([r for r in self.slots if r is not None]
                     + list(self.queue))
        if status == "timeout" and not undrained:
            status = "drained"     # last permitted step finished the work
        if status != "drained":
            self.dump_flight(status,
                             {"undrained_rids": [r.rid for r in undrained]})
        return DrainResult(self.done, status, undrained,
                           shed=list(self.admission.shed),
                           rejected=list(self.admission.rejected),
                           failed=list(self.failed))

    # ---- observability ---------------------------------------------------
    def metrics(self) -> Dict:
        """The structured serve-metrics snapshot (v2 schema + deprecated
        legacy aliases: queue depth, shed counts, retries, rank-bucket
        residency, TTFT/queue-wait percentiles, jit retrace + AOT
        counters) — the one surface shared by operators
        (``serve.py --stats-json``), the degradation benchmark and the
        chaos tests."""
        return self._metrics.snapshot(len(self.queue), self.level,
                                      engine_stats=self.stats)

    def dump_flight(self, reason: str,
                    extra: Optional[Dict] = None) -> Optional[str]:
        """Dump the flight-recorder ring with full engine context (armed
        ``FaultPlan`` incl. seed, queue/slot state, elastic rung, step
        index). Returns the artifact path, or ``None`` when no dump dir
        is configured. Called automatically on a typed poison failure and
        a non-``drained`` drain; the front door calls it on its own
        triggers too."""
        ctx: Dict = {
            "step": self._step_idx,
            "rank_level": self.level,
            "ladder_len": len(self.ladder),
            "queue_depth": len(self.queue),
            "queued_rids": [r.rid for r in self.queue],
            "slot_rids": [r.rid if r is not None else None
                          for r in self.slots],
            "failed_rids": [r.rid for r in self.failed],
            "fault_plan": (json.loads(self.faults.to_json())
                           if self.faults is not None else None),
        }
        if extra:
            ctx.update(extra)
        return self.flight.dump(reason, ctx)
