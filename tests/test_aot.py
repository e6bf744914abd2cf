"""ISSUE 7 AOT coverage: the persistent-executable registry must only
ever change COST, never results. Oracle: the traced registry's token
stream. Asserts the boot contract (second boot performs zero compiles),
fingerprint isolation (a different artifact never replays a cached
executable), and the corruption fallback ladder.
"""
import dataclasses
import os

import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.core import compress as CC
from repro.models import transformer as T
from repro.serve import aot as aotlib
from repro.serve.engine import ContinuousBatcher, Request, ServeConfig

CFG = get_config("llama-mini").replace(
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=256, rank_multiple=1)
SCFG = ServeConfig(batch=2, max_len=32)


@pytest.fixture(scope="module")
def comp():
    params, _ = T.init_model(CFG, jax.random.PRNGKey(0))
    calib = [{"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (2, 16), 0, CFG.vocab_size)}]
    c, _ = CC.build_plan_and_params(
        params, CFG, CC.CompressionConfig(ratio=0.4), calib)
    return c


def _workload(n=4, n_new=5, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, n_new=n_new,
                    tokens=rng.integers(0, CFG.vocab_size, size=(7,),
                                        dtype=np.int32))
            for i in range(n)]


def _drain(params, registry=None):
    cb = ContinuousBatcher(params, CFG, SCFG, executables=registry)
    cb.warm_executables()
    reqs = _workload()
    for r in reqs:
        cb.submit(r)
    res = cb.run_until_drained()
    assert res.status == "drained"
    return {r.rid: list(r.out) for r in res}, cb.stats


def _registry(comp, cache_dir, fingerprint=None):
    return aotlib.AotRegistry(
        CFG, SCFG,
        fingerprint or aotlib.live_fingerprint(comp, CFG),
        cache_dir=str(cache_dir))


def test_aot_boot_token_identical_and_second_boot_compile_free(
        tmp_path, comp):
    oracle, tstats = _drain(comp)                       # traced reference
    assert tstats["decode_retraces"] == 1

    cold, s1 = _drain(comp, _registry(comp, tmp_path))  # boot 1: compiles
    assert cold == oracle
    assert s1["aot_compiles"] > 0 and s1["aot_cache_hits"] == 0
    assert s1["decode_retraces"] == 0                   # nothing traced lazily

    warm, s2 = _drain(comp, _registry(comp, tmp_path))  # boot 2: cache only
    assert warm == oracle
    assert s2["aot_compiles"] == 0, s2
    assert s2["aot_cache_hits"] > 0
    assert s2["aot_fallbacks"] == 0 and s2["aot_deser_failures"] == 0


def test_fingerprint_mismatch_recompiles_not_replays(tmp_path, comp):
    _drain(comp, _registry(comp, tmp_path))             # populate cache
    # same shapes, different artifact identity: the cache must MISS —
    # replaying another artifact's executable would be silently wrong
    # if shapes ever coincided across incompatible artifacts
    other, s = _drain(comp, _registry(comp, tmp_path,
                                      fingerprint="sha256:deadbeef"))
    assert s["aot_compiles"] > 0
    assert s["aot_cache_hits"] == 0
    oracle, _ = _drain(comp)
    assert other == oracle


def test_corrupt_cache_entry_falls_back_to_compile(tmp_path, comp):
    reg = _registry(comp, tmp_path)
    _drain(comp, reg)                                   # populate cache
    for key in reg.cache.keys():                        # torch every entry
        with open(reg.cache.path(key), "wb") as f:
            f.write(b"not an executable")
    redo, s = _drain(comp, _registry(comp, tmp_path))
    assert s["aot_deser_failures"] > 0
    assert s["aot_compiles"] == s["aot_deser_failures"]  # each re-made once
    oracle, _ = _drain(comp)
    assert redo == oracle


def test_cache_key_separates_roles_variants_and_config(comp):
    fp = aotlib.live_fingerprint(comp, CFG)
    sig = "sig"
    k = aotlib.cache_key(fp, "decode", (0,), sig, SCFG, CFG)
    assert k != aotlib.cache_key(fp, "prefill", (0,), sig, SCFG, CFG)
    assert k != aotlib.cache_key(fp, "decode", (1,), sig, SCFG, CFG)
    assert k != aotlib.cache_key(fp, "decode", (0,), sig,
                                 ServeConfig(batch=4, max_len=32), CFG)
    assert k != aotlib.cache_key("sha256:other", "decode", (0,), sig,
                                 SCFG, CFG)
    assert k == aotlib.cache_key(fp, "decode", (0,), sig, SCFG, CFG)


def test_unserializable_executable_is_counted_not_silent(tmp_path, comp,
                                                         monkeypatch):
    import jax.experimental.serialize_executable as se

    def refuse(compiled):
        raise RuntimeError("backend cannot serialize")
    monkeypatch.setattr(se, "serialize", refuse)
    out, s = _drain(comp, _registry(comp, tmp_path))
    assert s["aot_compiles"] > 0
    assert s["aot_store_failures"] == s["aot_compiles"]
    assert aotlib.AotCache(str(tmp_path)).keys() == []
    oracle, _ = _drain(comp)
    assert out == oracle


def test_cache_key_tracks_the_installed_jaxlib(comp, monkeypatch):
    import jaxlib
    fp = aotlib.live_fingerprint(comp, CFG)
    k = aotlib.cache_key(fp, "decode", (0,), "sig", SCFG, CFG)
    monkeypatch.setattr(jaxlib, "__version__", "0.0.0-other")
    assert aotlib.cache_key(fp, "decode", (0,), "sig", SCFG, CFG) != k


def test_default_cache_dir_lives_under_the_compile_cache_root(
        tmp_path, monkeypatch):
    from repro import compile_cache
    monkeypatch.delenv("REPRO_AOT_CACHE", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert aotlib.default_cache_dir() == str(tmp_path / "repro-aot")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert aotlib.default_cache_dir() == os.path.join(
        compile_cache.REPO_ROOT, ".cache", "jax", "repro-aot")
    monkeypatch.setenv("REPRO_AOT_CACHE", str(tmp_path / "explicit"))
    assert aotlib.default_cache_dir() == str(tmp_path / "explicit")


def test_compile_cache_enable_sets_only_the_unset_default(tmp_path,
                                                          monkeypatch):
    from repro import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None   # JAX reads env
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        root = compile_cache.enable()
        assert root.endswith("/.cache/jax")
        assert jax.config.jax_compilation_cache_dir == root
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_replica_engines_are_placed_on_local_devices(comp):
    from repro.serve import api
    devices = jax.local_devices()
    opts = api.ServeOptions(arch="llama-mini", replicas=3)
    assert api._replica_device(dataclasses.replace(opts, replicas=1),
                               0) is None
    for i in range(3):
        assert api._replica_device(opts, i) == devices[i % len(devices)]
    dev = devices[-1]
    cb = ContinuousBatcher(comp, CFG, SCFG, device=dev)
    placed = {d for leaf in jax.tree.leaves((cb.params, cb.cache))
              for d in leaf.devices()}
    assert placed == {dev}


def test_compiled_executables_are_named_for_their_role(tmp_path, comp):
    """The role functions carry their role's name, so XLA names each
    executable for it (``jit_decode``, not ``jit__lambda_``) — compiled
    now, and deserialized from the cache on the next boot."""
    cache = aotlib.AotRegistry(CFG, SCFG, "x")._cache_aval()
    tok = jax.ShapeDtypeStruct((SCFG.batch, 1), np.int32)
    batch = {"tokens": jax.ShapeDtypeStruct((SCFG.batch, 8), np.int32),
             "lengths": jax.ShapeDtypeStruct((SCFG.batch,), np.int32)}
    entries = ((aotlib.ROLE_DECODE, (0,),
                (comp, cache["runs"], cache["pos"], tok)),
               (aotlib.ROLE_PREFILL, (0, 8), (comp, batch)))
    for boot in range(2):
        reg = _registry(comp, tmp_path)
        for role, variant, args in entries:
            head = reg._resolve(role, variant, args).as_text().split(",")[0]
            assert head == f"HloModule jit_{role}"
        assert reg.stats["aot_cache_hits"] == 2 * boot


# ---------------------------------------------------------------------------
# decode updates the donated KV pool in place
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def dense():
    params, _ = T.init_model(CFG, jax.random.PRNGKey(0))
    return params


def _decode_memory(cb):
    """(alias, temp) bytes of the batcher's full-rank decode executable
    (the AOT registry's boot counters, or the traced registry's jitted
    decode compiled for the batcher's own arguments), and the temp bytes
    of the same step compiled without donation."""
    table = cb._table_jnp() if cb.paged else None
    plain = jax.jit(lambda p, c, t, tbl: T.decode_step(p, CFG, c, t,
                                                       table=tbl))
    base = plain.lower(cb.params, cb.cache, cb.tokens,
                       table).compile().memory_analysis()
    if cb.exec.kind == "aot":
        cb.warm_executables()
        return (cb.stats["decode_alias_bytes"], cb.stats["decode_temp_bytes"],
                base.temp_size_in_bytes)
    jitted = cb.exec._decode_paged if cb.paged else cb.exec._decode
    args = (cb.params, cb.cache["runs"], cb.cache["pos"], cb.tokens)
    ma = jitted.lower(*args, *([table] if cb.paged else [])).compile(
        ).memory_analysis()
    return (ma.alias_size_in_bytes, ma.temp_size_in_bytes,
            base.temp_size_in_bytes)


@pytest.mark.parametrize("registry", ["aot", "traced"])
@pytest.mark.parametrize("layers", ["scan", "list", "paged"])
def test_decode_updates_the_donated_pool_in_place(tmp_path, comp, dense,
                                                  registry, layers):
    """Every decode executable aliases the whole KV pool (scanned dense
    layers, the compressed model's list of layers, the paged arena) and
    adds less than a pool's worth of scratch to the undonated step, so it
    keeps no copy of the pool; and a batcher step consumes the pool it was
    given: the pre-step leaves are deleted, and the pre-step cache dict
    points at the updated pool."""
    params = comp if layers == "list" else dense
    scfg = (dataclasses.replace(SCFG, kv_block=8) if layers == "paged"
            else SCFG)
    reg = (aotlib.AotRegistry(CFG, scfg, aotlib.live_fingerprint(params,
                                                                 CFG),
                              cache_dir=str(tmp_path))
           if registry == "aot" else None)
    cb = ContinuousBatcher(params, CFG, scfg, executables=reg)
    pool = sum(leaf.nbytes for leaf in jax.tree.leaves(cb.cache["runs"]))
    alias, temp, undonated_temp = _decode_memory(cb)
    assert alias >= pool, (alias, pool)
    assert temp < undonated_temp + pool, (temp, undonated_temp)  # no copy
    for r in _workload(n=2, n_new=8):
        cb.submit(r)
    cb.step()                      # admits both requests, decodes once
    for _ in range(3):             # decode only: nothing to admit or retire
        old = cb.cache
        leaves = jax.tree.leaves(old["runs"])
        assert cb.step() == 2
        assert all(leaf.is_deleted() for leaf in leaves)
        assert old["runs"] is cb.cache["runs"]
        assert not any(leaf.is_deleted()
                       for leaf in jax.tree.leaves(cb.cache))
