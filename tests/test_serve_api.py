"""ISSUE 7 API-redesign coverage: the typed ``repro.serve.api`` surface,
the CLI↔ServeOptions golden round trip (every legacy flag maps; the
deprecated spellings warn), and the unified ``from_compressed`` factory.
"""
import dataclasses
import warnings

import jax
import pytest

from repro.configs import get_config
from repro.core import compress as CC
from repro.models import transformer as T
from repro.serve import api
from repro.launch.serve import build_parser, parse_serve_options

CFG = get_config("llama-mini").replace(
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=256)


# ---------------------------------------------------------------------------
# API surface snapshot
# ---------------------------------------------------------------------------

API_SURFACE = [
    "AotCache", "AotRegistry", "ContinuousBatcher", "DrainResult",
    "Engine", "FrontDoor", "Request", "Router", "ServeConfig",
    "ServeOptions", "TokenStream", "TracedRegistry", "from_compressed",
    "load_engine", "serve",
]


def test_api_surface_snapshot():
    """The public surface is a contract: additions mean updating this
    snapshot deliberately; removals/renames are breaking changes."""
    assert sorted(api.__all__) == API_SURFACE
    for name in api.__all__:
        assert getattr(api, name) is not None


# ---------------------------------------------------------------------------
# CLI <-> ServeOptions golden round trip
# ---------------------------------------------------------------------------

# every flag the CLI accepts, with a non-default value, and the
# ServeOptions field it must land in — the golden map. A flag missing
# here (or a field missing a flag) fails the completeness checks below.
GOLDEN = [
    # (argv fragment, field, expected value)
    (["--arch", "llama-mini"], "arch", "llama-mini"),
    (["--ckpt", "runs/x"], "ckpt", "runs/x"),
    (["--compress", "drank"], "compress", "drank"),
    (["--ratio", "0.4"], "ratio", 0.4),
    (["--group-size", "4"], "group_size", 4),
    (["--beta", "0.7"], "beta", 0.7),
    (["--save-compressed", "runs/cc"], "save_compressed", "runs/cc"),
    (["--verify"], "verify", True),
    (["--calib-mesh-shards", "2"], "calib_mesh_shards", 2),
    (["--shard-grams-above", "128"], "shard_grams_above", 128),
    (["--calib-samples", "32"], "calib_samples", 32),
    (["--calib-seq", "64"], "calib_seq", 64),
    (["--device-compress"], "device_compress", True),
    (["--rsvd-threshold", "96"], "rsvd_threshold", 96),
    (["--batch", "3"], "batch", 3),
    (["--max-len", "128"], "max_len", 128),
    (["--kv-block", "16"], "kv_block", 16),
    (["--prefix-cache"], "prefix_cache", True),
    (["--requests", "5"], "requests", 5),
    (["--prompt-len", "9"], "prompt_len", 9),
    (["--n-new", "11"], "n_new", 11),
    (["--seed", "7"], "seed", 7),
    (["--max-queue", "6"], "max_queue", 6),
    (["--deadline-s", "12.5"], "deadline_s", 12.5),
    (["--max-retries", "3"], "max_retries", 3),
    (["--reject-overlong"], "reject_overlong", True),
    (["--elastic"], "elastic", True),
    (["--elastic-levels", "1"], "elastic_levels", 1),
    (["--watchdog-s", "45"], "watchdog_s", 45.0),
    (["--heartbeat-dir", "runs/hb"], "heartbeat_dir", "runs/hb"),
    (["--fault-plan", '{"nan_decode_step": 3}'], "fault_plan",
     '{"nan_decode_step": 3}'),
    (["--load-retries", "2"], "load_retries", 2),
    (["--stats-json", "runs/s.json"], "stats_json", "runs/s.json"),
    (["--aot"], "aot", True),
    (["--aot-cache-dir", "/tmp/aot"], "aot_cache_dir", "/tmp/aot"),
    (["--replicas", "2"], "replicas", 2),
    (["--stream"], "stream", True),
    (["--trace-out", "runs/t.json"], "trace_out", "runs/t.json"),
    (["--device-trace-dir", "runs/prof"], "device_trace_dir",
     "runs/prof"),
    (["--metrics-json", "runs/m.json"], "metrics_json", "runs/m.json"),
    (["--metrics-interval-s", "0.5"], "metrics_interval_s", 0.5),
    (["--metrics-port", "0"], "metrics_port", 0),
    (["--flightrec-dir", "runs/frec"], "flightrec_dir", "runs/frec"),
]
# flags that exist but map through translation, or cannot combine with
# the all-at-once argv below
SPECIAL = {
    "--slots": "batch",                    # deprecated alias
    "--whiten-stream": "whiten_stream",    # conflicts with --eager-capture
    "--eager-capture": "eager_capture",    # conflicts with mesh shards
    "--compressed-ckpt": "compressed_ckpt",  # conflicts with --compress
}


def test_every_flag_round_trips_together():
    argv = [tok for frag, _, _ in GOLDEN for tok in frag]
    opts = parse_serve_options(argv)
    for _, field, want in GOLDEN:
        assert getattr(opts, field) == want, field


def test_conflicting_flags_round_trip_individually():
    opts = parse_serve_options(["--arch", "llama-mini", "--whiten-stream"])
    assert opts.whiten_stream is True
    opts = parse_serve_options(["--arch", "llama-mini", "--eager-capture"])
    assert opts.eager_capture is True
    opts = parse_serve_options(["--arch", "llama-mini",
                                "--compressed-ckpt", "runs/cc"])
    assert opts.compressed_ckpt == "runs/cc"


def test_golden_map_is_complete_both_ways():
    """No CLI flag and no ServeOptions field outside the golden map."""
    parser_flags = {a.option_strings[0] for a in build_parser()._actions
                    if a.option_strings and a.option_strings[0] != "-h"}
    golden_flags = {frag[0] for frag, _, _ in GOLDEN} | set(SPECIAL)
    assert parser_flags == golden_flags
    fields = set(api.ServeOptions.__dataclass_fields__)
    golden_fields = {f for _, f, _ in GOLDEN} | set(SPECIAL.values())
    assert fields == golden_fields


def test_slots_is_a_deprecated_alias_of_batch():
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        opts = parse_serve_options(["--arch", "llama-mini", "--slots", "3"])
    assert opts.batch == 3
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    # explicit --batch wins over the alias
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        opts = parse_serve_options(["--arch", "llama-mini",
                                    "--slots", "3", "--batch", "5"])
    assert opts.batch == 5


def test_cli_rejects_bad_combinations_as_parse_errors():
    with pytest.raises(SystemExit):
        parse_serve_options(["--arch", "llama-mini", "--whiten-stream",
                             "--eager-capture"])


# ---------------------------------------------------------------------------
# ServeOptions validation
# ---------------------------------------------------------------------------

def test_options_validate_at_construction():
    ok = api.ServeOptions(arch="llama-mini")
    assert ok.serve_config().batch == ok.batch
    assert ok.admission_config().max_retries == ok.max_retries
    with pytest.raises(ValueError, match="unknown compression"):
        api.ServeOptions(arch="llama-mini", compress="zip")
    with pytest.raises(ValueError, match="conflict"):
        api.ServeOptions(arch="llama-mini", compress="drank",
                         compressed_ckpt="runs/cc")
    with pytest.raises(ValueError, match="save_compressed"):
        api.ServeOptions(arch="llama-mini", save_compressed="runs/cc")
    with pytest.raises(ValueError, match="streaming capture"):
        api.ServeOptions(arch="llama-mini", whiten_stream=True,
                         eager_capture=True)
    with pytest.raises(ValueError, match="must divide"):
        api.ServeOptions(arch="llama-mini", calib_mesh_shards=3)
    with pytest.raises(ValueError, match="multiple"):
        api.ServeOptions(arch="llama-mini", calib_mesh_shards=2,
                         calib_samples=12)
    with pytest.raises(ValueError, match="replicas"):
        api.ServeOptions(arch="llama-mini", replicas=0)
    with pytest.raises(ValueError, match="metrics_port"):
        api.ServeOptions(arch="llama-mini", metrics_port=70000)
    with pytest.raises(ValueError, match="metrics_interval_s"):
        api.ServeOptions(arch="llama-mini", metrics_interval_s=0.0)
    with pytest.raises(dataclasses.FrozenInstanceError):
        ok.batch = 9


# ---------------------------------------------------------------------------
# unified from_compressed factory
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("api_artifact"))
    params, _ = T.init_model(CFG, jax.random.PRNGKey(0))
    calib = [{"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (2, 16), 0, CFG.vocab_size)}]
    cfg = CFG.replace(rank_multiple=1)
    comp, plan = CC.build_plan_and_params(
        params, cfg, CC.CompressionConfig(ratio=0.4), calib)
    CC.save_plan(d, comp, plan, cfg)
    return d, cfg


def test_unified_factory_returns_both_kinds(artifact):
    d, cfg = artifact
    scfg = api.ServeConfig(batch=2, max_len=32)
    cb = api.from_compressed(d, cfg, scfg)
    eng = api.from_compressed(d, cfg, scfg, batcher=False)
    assert isinstance(cb, api.ContinuousBatcher)
    assert isinstance(eng, api.Engine)
    assert not isinstance(eng, api.ContinuousBatcher)
    # the shared loading path attaches the plan on both
    assert cb.plan.summary == eng.plan.summary
    # and the classmethods stay as thin delegates of the same factory
    cb2 = api.ContinuousBatcher.from_compressed(d, cfg, scfg)
    eng2 = api.Engine.from_compressed(d, cfg, scfg)
    assert isinstance(cb2, api.ContinuousBatcher)
    assert type(eng2) is api.Engine


def test_factory_retries_kwarg_is_deprecated(artifact):
    d, cfg = artifact
    scfg = api.ServeConfig(batch=2, max_len=32)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        eng = api.Engine.from_compressed(d, cfg, scfg, retries=0)
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    assert isinstance(eng, api.Engine)


def test_registered_config_serves_through_the_api():
    """``configs.register`` makes a cut variant an arch id that every
    entry point accepts."""
    from repro import configs
    configs.register("llama-mini-cut2", CFG)
    assert get_config("llama_mini_cut2") is CFG
    opts = api.ServeOptions(arch="llama-mini-cut2", batch=2, max_len=32,
                            requests=2, prompt_len=4, n_new=3)
    assert api.load_engine(opts).cfg is CFG
    res = api.serve(opts)
    assert res.status == "drained" and len(res) == 2
    assert all(len(r.out) == 3 for r in res)
