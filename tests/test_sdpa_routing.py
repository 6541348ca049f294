"""SDPA routing: env overrides > measured table > analytic default.

The reference always runs fused SDPA (modules/pp/attn.py:153); our backend
choice is a checked-in measured table (ops/sdpa_routing.py) with env vars
demoted to operator overrides. These tests pin the resolution order and the
log -> table updater round trip."""

import json
import os
import sys

import jax
import pytest

import importlib

attention = importlib.import_module("distrifuser_tpu.ops.attention")
from distrifuser_tpu.ops import sdpa_routing
from distrifuser_tpu.ops.sdpa_routing import Route

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts"))


# the autouse fixture below empties the shipped override table for the
# tests of the lookup rules; the tests of what ships get it back from here
SHIPPED_OVERRIDES = dict(sdpa_routing.MODEL_VALIDATED_OVERRIDES)


class _Dev:
    def __init__(self, platform):
        self.platform = platform


@pytest.fixture(autouse=True)
def _clean_flash_env(monkeypatch):
    """Isolate routing tests from env leaked by other test files —
    __graft_entry__ setdefaults DISTRIFUSER_TPU_FLASH=0 process-wide when
    test_graft_entry runs earlier in the session.  Runs before each test
    body, so tests that set these vars intentionally still win."""
    for var in ("DISTRIFUSER_TPU_FLASH", "DISTRIFUSER_TPU_FLASH_IMPL",
                "DISTRIFUSER_TPU_FLASH_BQ", "DISTRIFUSER_TPU_FLASH_BK"):
        monkeypatch.delenv(var, raising=False)
    # the shipped model-validated override would shadow every monkeypatched
    # MEASURED_ROUTES below; tests that exercise overrides set their own
    monkeypatch.setattr(sdpa_routing, "MODEL_VALIDATED_OVERRIDES", {})


def _route(monkeypatch, platform="tpu", lq=4096, lk=4096, c=640, heads=10):
    import jax.numpy as jnp

    monkeypatch.setattr(jax, "devices", lambda: [_Dev(platform)])
    q = jax.ShapeDtypeStruct((2, lq, c), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2, lk, c), jnp.bfloat16)
    return attention._resolve_route(q, k, heads)


def test_env_off_wins_over_everything(monkeypatch):
    monkeypatch.setenv("DISTRIFUSER_TPU_FLASH", "0")
    monkeypatch.setattr(sdpa_routing, "MEASURED_ROUTES",
                        {(64, 12): Route("inrepo", 256, 512)})
    assert _route(monkeypatch) == Route("xla")


def test_unaligned_always_xla(monkeypatch):
    assert _route(monkeypatch, lq=4095, lk=4095) == Route("xla")


def test_cpu_defaults_to_xla(monkeypatch):
    assert _route(monkeypatch, platform="cpu") == Route("xla")


def test_force_on_cpu_is_inrepo_interpret_path(monkeypatch):
    monkeypatch.setenv("DISTRIFUSER_TPU_FLASH", "1")
    assert _route(monkeypatch, platform="cpu").impl == "inrepo"


def test_measured_table_drives_default_route(monkeypatch):
    monkeypatch.setattr(sdpa_routing, "MEASURED_ROUTES",
                        {(64, 12): Route("inrepo", 256, 512),
                         (64, 16): Route("xla")})
    # L=4096 -> bucket 12 -> measured inrepo with tuned tiles
    assert _route(monkeypatch) == Route("inrepo", 256, 512)
    # L=57600 -> bucket ~15.8 -> nearest measured is 16 -> xla beats flash
    assert _route(monkeypatch, lq=57600 // 8 * 8, lk=57344) == Route("xla")


def test_env_tiles_override_measured_tiles(monkeypatch):
    monkeypatch.setattr(sdpa_routing, "MEASURED_ROUTES",
                        {(64, 12): Route("inrepo", 256, 512)})
    monkeypatch.setenv("DISTRIFUSER_TPU_FLASH_BQ", "128")
    assert _route(monkeypatch) == Route("inrepo", 128, 512)


def test_explicit_impl_wins_over_table(monkeypatch):
    monkeypatch.setattr(sdpa_routing, "MEASURED_ROUTES",
                        {(64, 12): Route("xla")})
    monkeypatch.setenv("DISTRIFUSER_TPU_FLASH_IMPL", "upstream")
    assert _route(monkeypatch).impl == "upstream"


def test_unmeasured_falls_to_analytic_default(monkeypatch):
    monkeypatch.setattr(sdpa_routing, "MEASURED_ROUTES", {})
    assert _route(monkeypatch).impl == "upstream"  # long seq on TPU
    assert _route(monkeypatch, lq=512, lk=512).impl == "xla"  # short


def test_lookup_requires_matching_head_dim():
    # shipped table contents change with every campaign re-bake; pin only
    # the lookup semantics against a controlled table
    table = {(64, 12): Route("upstream")}
    old = sdpa_routing.MEASURED_ROUTES
    sdpa_routing.MEASURED_ROUTES = table
    try:
        assert sdpa_routing.lookup(5000, 64) == Route("upstream")
        assert sdpa_routing.lookup(5000, 160) is None
    finally:
        sdpa_routing.MEASURED_ROUTES = old


def test_lookup_distance_cap():
    """A lone long-L measurement must not govern short sequences:
    beyond MAX_BUCKET_DISTANCE log2 steps lookup falls through to the
    analytic default."""
    table = {(64, 14): Route("inrepo", 256, 512)}  # L=16384 only
    old = sdpa_routing.MEASURED_ROUTES
    sdpa_routing.MEASURED_ROUTES = table
    try:
        assert sdpa_routing.lookup(16384, 64) == Route("inrepo", 256, 512)
        assert sdpa_routing.lookup(8192, 64) is not None   # 1 step away
        assert sdpa_routing.lookup(1024, 64) is None       # 4 steps away
        assert sdpa_routing.lookup(2**20, 64) is None      # far the other way
    finally:
        sdpa_routing.MEASURED_ROUTES = old


def test_updater_tiles_keyed_by_head_dim(tmp_path):
    """Tuned tiles for one head_dim must not leak onto another head_dim's
    route at the same L."""
    import json as _json

    import update_sdpa_table as upd

    log = tmp_path / "campaign.log"
    lines = [
        {"phase": "attn", "L": 4096, "heads": 10, "head_dim": 64,
         "ms": {"xla": 2.0, "inrepo": 1.5}},
        {"phase": "attn", "L": 4096, "heads": 16, "head_dim": 72,
         "ms": {"xla": 2.2, "inrepo": 1.8}},
        {"phase": "tune", "L": 4096, "heads": 10, "head_dim": 64,
         "ms": {"256x512": 1.2}},
        {"phase": "tune", "L": 4096, "heads": 16, "head_dim": 72,
         "ms": {"128x128": 1.6}},
    ]
    log.write_text("\n".join(_json.dumps(rec) for rec in lines) + "\n")
    attn, tune = upd.parse_log(str(log))
    routes = upd.build_routes(attn, tune)
    assert routes[(64, 12)][:3] == ("inrepo", 256, 512)
    assert routes[(72, 12)][:3] == ("inrepo", 128, 128)


def test_updater_upstream_tune_can_win(tmp_path):
    """A tuned upstream sweep that beats the default-tile attn comparison
    flips the route to upstream and carries its tiles."""
    import json as _json

    import update_sdpa_table as upd

    log = tmp_path / "campaign.log"
    lines = [
        {"phase": "attn", "L": 4096, "heads": 10, "head_dim": 64,
         "ms": {"xla": 2.0, "inrepo": 1.5, "upstream": 1.8}},
        {"phase": "tune", "L": 4096, "heads": 10, "head_dim": 64,
         "ms": {"256x512": 1.4}},
        {"phase": "tune_upstream", "L": 4096, "heads": 10, "head_dim": 64,
         "ms": {"512x1024": 1.1, "256x512": 1.3}},
    ]
    log.write_text("\n".join(_json.dumps(rec) for rec in lines) + "\n")
    attn, tune = upd.parse_log(str(log))
    routes = upd.build_routes(attn, tune)
    assert routes[(64, 12)][:3] == ("upstream", 512, 1024)


def test_updater_round_trip(tmp_path):
    import update_sdpa_table as upd

    log = tmp_path / "campaign.log"
    lines = [
        {"phase": "attn", "L": 4096, "heads": 10, "head_dim": 64,
         "ms": {"xla": 2.0, "inrepo": 1.5, "upstream": 1.0}},
        {"phase": "attn", "L": 16384, "heads": 10, "head_dim": 64,
         "ms": {"xla": 9.0, "inrepo": 8.0, "upstream": "failed:XlaError"}},
        # 7.5 ms sits just above the L=16384 roofline floor (~6.98 ms at
        # 100% bf16 peak) — the sanity guard must keep it
        {"phase": "tune", "L": 16384, "heads": 10, "head_dim": 64,
         "ms": {"128x128": 8.0, "256x512": 7.5}},
        {"phase": "b1024", "size": 1024, "s": 7.0},  # ignored: no ms dict
    ]
    log.write_text("non-json noise\n"
                   + "\n".join(json.dumps(rec) for rec in lines) + "\n")

    attn, tune = upd.parse_log(str(log))
    assert len(attn) == 2 and len(tune) == 1
    routes = upd.build_routes(attn, tune)
    assert routes[(64, 12)][0] == "upstream"
    impl, bq, bk, _comment = routes[(64, 14)]
    assert (impl, bq, bk) == ("inrepo", 256, 512)  # tuned tiles attached

    block = upd.render_block(routes, "unit-test")
    ns = {"Route": Route}
    exec(block.replace(upd.BEGIN, "").replace(upd.END, ""), ns)
    assert ns["MEASURED_ROUTES"][(64, 14)] == Route("inrepo", 256, 512)
    assert ns["MEASURED_PROVENANCE"] == "unit-test"


def test_updater_drops_subroofline_timings(tmp_path):
    """Campaign r5 regression: upstream-flash tune entries of ~0.02 ms at
    L=16384 (350x above bf16 peak — the kernel degenerates at those tiles
    instead of failing) must not reach the table; the sane sub-peak tiles
    of the same sweep still win."""
    import json as _json

    import update_sdpa_table as upd

    log = tmp_path / "campaign.log"
    lines = [
        {"phase": "attn", "L": 16384, "heads": 10, "head_dim": 64,
         "ms": {"xla": "failed:JaxRuntimeError", "inrepo": 184.9,
                "upstream": 161.8}},
        {"phase": "tune", "L": 16384, "heads": 10, "head_dim": 64,
         "ms": {"512x1024": 25.9}},
        {"phase": "tune_upstream", "L": 16384, "heads": 10, "head_dim": 64,
         "ms": {"256x2048": 23.2, "512x512": 0.022, "1024x512": 0.019}},
    ]
    log.write_text("\n".join(_json.dumps(rec) for rec in lines) + "\n")
    attn, tune = upd.parse_log(str(log))
    routes = upd.build_routes(attn, tune)
    impl, bq, bk, _comment = routes[(64, 14)]
    assert (impl, bq, bk) == ("upstream", 256, 2048)  # not the 0.02ms tiles
    # an attn record that is ENTIRELY sub-floor contributes nothing
    attn2 = [{"phase": "attn", "L": 16384, "heads": 10, "head_dim": 64,
              "ms": {"xla": 0.01, "upstream": 0.02}}]
    assert upd.build_routes(attn2, []) == {}


def test_updater_tiles_require_matching_head_count(tmp_path):
    """Campaign r5 regression: an h=10 tuned sweep must not fold into an
    h=24 attn record at the same (L, head_dim) — mixed-head comparison
    flipped the route to a kernel that loses at both head counts.  A
    heads-less record (pre-r5 logs) still matches any sweep (wildcard)."""
    import json as _json

    import update_sdpa_table as upd

    log = tmp_path / "campaign.log"
    lines = [
        # h=10 record first, h=24 record last (owns the route slot)
        {"phase": "attn", "L": 4096, "heads": 10, "head_dim": 64,
         "ms": {"xla": 7.1, "inrepo": 13.8, "upstream": 12.2}},
        {"phase": "attn", "L": 4096, "heads": 24, "head_dim": 64,
         "ms": {"xla": 12.2, "inrepo": 29.4, "upstream": 26.3}},
        {"phase": "tune", "L": 4096, "heads": 10, "head_dim": 64,
         "ms": {"512x1024": 8.2}},
    ]
    log.write_text("\n".join(_json.dumps(rec) for rec in lines) + "\n")
    attn, tune = upd.parse_log(str(log))
    routes = upd.build_routes(attn, tune)
    # the h=10 sweep (8.2ms) must NOT beat the h=24 record's xla (12.2ms)
    assert routes[(64, 12)][:3] == ("xla", None, None)

    # wildcard: heads-less attn record accepts the sweep
    lines2 = [
        {"phase": "attn", "L": 4096, "head_dim": 64,
         "ms": {"xla": 12.2, "inrepo": 13.8}},
        {"phase": "tune", "L": 4096, "heads": 10, "head_dim": 64,
         "ms": {"512x1024": 8.2}},
    ]
    log.write_text("\n".join(_json.dumps(rec) for rec in lines2) + "\n")
    attn, tune = upd.parse_log(str(log))
    routes = upd.build_routes(attn, tune)
    assert routes[(64, 12)][:3] == ("inrepo", 512, 1024)


def test_model_validated_override_wins_and_scopes():
    """MODEL_VALIDATED_OVERRIDES outranks MEASURED_ROUTES at its bucket but
    obeys the same bucket-distance discipline elsewhere."""
    old_m = sdpa_routing.MEASURED_ROUTES
    old_o = sdpa_routing.MODEL_VALIDATED_OVERRIDES
    sdpa_routing.MEASURED_ROUTES = {(64, 12): Route("xla")}
    sdpa_routing.MODEL_VALIDATED_OVERRIDES = {
        (64, 12): Route("upstream", 256, 1024)}
    try:
        assert sdpa_routing.lookup(4096, 64) == Route("upstream", 256, 1024)
        # far buckets fall through the override to the measured table rules
        assert sdpa_routing.lookup(2**20, 64) is None
        # other head_dims see neither
        assert sdpa_routing.lookup(4096, 160) is None
        # a STRICTLY CLOSER measured entry beats the override: the override
        # is model-validated at ITS bucket only, not at lengths a nearer
        # measurement covers (L=1536 is 0.58 buckets from the (64,10) XLA
        # entry, 1.42 from the (64,12) override)
        sdpa_routing.MEASURED_ROUTES = {(64, 10): Route("xla"),
                                        (64, 12): Route("xla")}
        assert sdpa_routing.lookup(1536, 64) == Route("xla")
        assert sdpa_routing.lookup(4096, 64) == Route("upstream", 256, 1024)
    finally:
        sdpa_routing.MEASURED_ROUTES = old_m
        sdpa_routing.MODEL_VALIDATED_OVERRIDES = old_o


def test_updater_skips_tiles_slower_than_default(tmp_path):
    """A tuned sweep whose best time LOSES to the winner's default-tile
    time must not pin its tiles onto the route (the comment would claim a
    time those tiles never achieved)."""
    import json as _json

    import update_sdpa_table as upd

    log = tmp_path / "campaign.log"
    lines = [
        {"phase": "attn", "L": 4096, "heads": 10, "head_dim": 64,
         "ms": {"xla": 9.0, "upstream": 7.0}},
        {"phase": "tune_upstream", "L": 4096, "heads": 10, "head_dim": 64,
         "ms": {"512x1024": 8.5}},  # tuned WORSE than default-tile 7.0
    ]
    log.write_text("\n".join(_json.dumps(rec) for rec in lines) + "\n")
    attn, tune = upd.parse_log(str(log))
    routes = upd.build_routes(attn, tune)
    assert routes[(64, 12)][:3] == ("upstream", None, None)


def test_sdpa_still_computes_on_cpu(monkeypatch):
    """End to end: routing lands on a working path whatever the table says."""
    import jax.numpy as jnp
    import numpy as np

    monkeypatch.setattr(sdpa_routing, "MEASURED_ROUTES",
                        {(64, 7): Route("inrepo", 64, 64)})
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 128, 128), jnp.float32)
    out = attention.sdpa(q, q, q, heads=2)
    assert out.shape == (1, 128, 128)
    assert np.isfinite(np.asarray(out)).all()


def test_updater_accepts_bench_attention_lines(tmp_path):
    import update_sdpa_table as upd

    log = tmp_path / "bench_attention.log"
    lines = [
        {"impl": "xla", "L": 4096, "heads": 10, "ms": 2.0},
        {"impl": "pallas_inrepo", "L": 4096, "heads": 10, "ms": 1.4},
        {"impl": "pallas_upstream", "L": 4096, "heads": 10,
         "ms": "failed: XlaRuntimeError"},
    ]
    log.write_text("\n".join(json.dumps(rec) for rec in lines) + "\n")
    attn, tune = upd.parse_log(str(log))
    assert len(attn) == 1 and not tune
    routes = upd.build_routes(attn, tune)
    assert routes[(64, 12)][0] == "inrepo"  # failed upstream excluded


def test_updater_accepts_batch2_campaign_records(tmp_path):
    """chip_campaign.py emits ``batch=2`` (the CFG pair) in attn/tune
    records: the updater must carry them end to end — the roofline floor
    doubles (4*B*h*L^2*d flops), the batch lands in the table comment, and
    the rendered block round-trips."""
    import json as _json

    import update_sdpa_table as upd

    # b=2 floor at L=16384 h=10 d=64: 4*2*10*16384^2*64/197e12 ~= 6.98 ms
    floor_b2 = upd._roofline_floor_ms(
        {"L": 16384, "heads": 10, "head_dim": 64, "batch": 2})
    floor_b1 = upd._roofline_floor_ms(
        {"L": 16384, "heads": 10, "head_dim": 64})
    assert floor_b2 == pytest.approx(2 * floor_b1)

    log = tmp_path / "campaign.log"
    lines = [
        {"phase": "attn", "L": 16384, "heads": 10, "head_dim": 64,
         "batch": 2, "ms": {"xla": 30.0, "inrepo": 20.0, "upstream": 12.0}},
        # 5 ms sits ABOVE the b=1 floor (~3.5 ms) but BELOW the b=2 floor
        # (~6.98 ms): a b=2 record must drop it as a timing escape
        {"phase": "tune_upstream", "L": 16384, "heads": 10, "head_dim": 64,
         "batch": 2, "ms": {"512x512": 5.0, "256x1024": 10.0}},
    ]
    log.write_text("\n".join(_json.dumps(rec) for rec in lines) + "\n")
    attn, tune = upd.parse_log(str(log))
    assert attn[0]["batch"] == 2 and tune[0]["batch"] == 2
    routes = upd.build_routes(attn, tune)
    impl, bq, bk, comment = routes[(64, 14)]
    assert (impl, bq, bk) == ("upstream", 256, 1024)  # not the 5 ms escape
    assert "b=2" in comment
    block = upd.render_block(routes, "unit-test-b2")
    ns = {"Route": Route}
    exec(block.replace(upd.BEGIN, "").replace(upd.END, ""), ns)
    assert ns["MEASURED_ROUTES"][(64, 14)] == Route("upstream", 256, 1024)


def test_lookup_nearest_shape_fallback_for_missing_key():
    """The table is keyed by (head_dim, log2 L) — a query whose exact
    (batch, seq, heads) combination was never measured still routes via
    the NEAREST measured bucket at its head_dim (within
    MAX_BUCKET_DISTANCE), and falls through to the analytic default
    beyond it.  Batch and head count deliberately do not partition the
    table: the campaign measures the CFG pair at the model's head counts,
    and the latency ordering tracks sequence-length scale."""
    table = {(64, 12): Route("upstream", 256, 1024),
             (64, 14): Route("inrepo", 512, 512)}
    old = sdpa_routing.MEASURED_ROUTES
    sdpa_routing.MEASURED_ROUTES = table
    try:
        # L=6000 (bucket ~12.55) was never measured: nearest is 12
        assert sdpa_routing.lookup(6000, 64) == Route("upstream", 256, 1024)
        # L=11585 (bucket ~13.5): ties resolve to a measured neighbor,
        # never to None, as long as one is in range
        assert sdpa_routing.lookup(11585, 64) in table.values()
        # L=23000 (bucket ~14.5): nearest is 14
        assert sdpa_routing.lookup(23000, 64) == Route("inrepo", 512, 512)
        # missing head_dim: no fallback across head_dims
        assert sdpa_routing.lookup(6000, 128) is None
        # far outside every measured bucket: analytic default decides
        assert sdpa_routing.lookup(240, 64) is None
    finally:
        sdpa_routing.MEASURED_ROUTES = old


def test_largest_dividing_tile():
    """Tile fitting for the upstream kernel: a tuned tile that
    does not divide the call's length is halved to the largest power-of-2
    divisor instead of being dropped (which would mix in the kernel's
    hardcoded 512/1024 defaults — themselves non-dividing for shapes like
    Lk=57600)."""
    fit = attention._largest_dividing_tile
    assert fit(512, 4096) == 512          # already divides
    assert fit(1024, 57600) == 256        # 1024, 512 fail; 256 divides
    assert fit(512, 57600) == 256
    assert fit(1024, 77) is None          # below the 128 lane minimum
    assert fit(128, 384) == 128
    assert fit(1024, 1000) is None        # no pow2 >=128 divides 1000


# the benchmark's cells: (head_dim, heads, L) of every self-attention shape
# that `sdxl-1024-solo` and `pixart-1024-solo` run through the table
CELL_SHAPES = [(72, 16, 4096), (64, 10, 4096), (64, 20, 1024)]


@pytest.mark.parametrize("d,heads,l", CELL_SHAPES)
def test_cell_shapes_resolve_to_the_seq_minor_kernel(monkeypatch, d, heads, l):
    """PR 25: the three shapes of the benchmark's cells route to the in-repo
    (sequence-minor) kernel, with tiles that divide the cell's length and
    the local Q lengths of the patch path (L/2, L/4 rows, KV gathered)."""
    monkeypatch.setattr(sdpa_routing, "MODEL_VALIDATED_OVERRIDES",
                        SHIPPED_OVERRIDES)
    route = _route(monkeypatch, lq=l, lk=l, c=heads * d, heads=heads)
    assert route.impl == "inrepo", route
    for tile in (route.block_q, route.block_k):
        assert tile and tile >= 128 and tile & (tile - 1) == 0
    assert l % route.block_q == 0 and l % route.block_k == 0
    # the patch path keys on kv_len, so it inherits the entry
    for n in (2, 4):
        assert _route(monkeypatch, lq=l // n, lk=l, c=heads * d,
                      heads=heads) == route


def test_inrepo_route_tiles_are_fitted_to_the_call(monkeypatch):
    """`sdpa` cuts a route's tiles down to what divides this call's lengths
    (a bucket holds lengths its tiles do not divide; the patch path's local
    Lq is a fraction of the cell's) and hands them to `flash_sdpa`."""
    import jax.numpy as jnp

    fa = importlib.import_module("distrifuser_tpu.ops.flash_attention")
    monkeypatch.setattr(sdpa_routing, "MODEL_VALIDATED_OVERRIDES",
                        {(64, 12): Route("inrepo", 1024, 512)})
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("tpu")])
    seen = []

    def spy(q, k, v, **kw):
        seen.append(kw)
        return q

    monkeypatch.setattr(fa, "flash_sdpa", spy)
    for lq, lk in [(4096, 4096), (512, 4096), (3840, 3840)]:
        attention.sdpa(jnp.zeros((1, lq, 128)), jnp.zeros((1, lk, 128)),
                       jnp.zeros((1, lk, 128)), heads=2)
    assert [(kw["block_q"], kw["block_k"]) for kw in seen] == [
        (1024, 512), (512, 512), (256, 256)]
    assert all(kw["interpret"] is False for kw in seen)


def test_env_overrides_govern_the_moved_entries(monkeypatch):
    """The documented hatches against the shipped table: FLASH=0 and
    IMPL=xla pin XLA, IMPL=upstream pins the upstream kernel, and BQ / BK
    replace the entry's tiles one axis at a time."""
    monkeypatch.setattr(sdpa_routing, "MODEL_VALIDATED_OVERRIDES",
                        SHIPPED_OVERRIDES)
    shipped = _route(monkeypatch, c=16 * 72, heads=16)
    assert shipped.impl == "inrepo"
    monkeypatch.setenv("DISTRIFUSER_TPU_FLASH_BK", "256")
    assert _route(monkeypatch, c=16 * 72, heads=16) == Route(
        "inrepo", shipped.block_q, 256)
    monkeypatch.setenv("DISTRIFUSER_TPU_FLASH_BQ", "128")
    assert _route(monkeypatch, c=16 * 72, heads=16) == Route(
        "inrepo", 128, 256)
    monkeypatch.delenv("DISTRIFUSER_TPU_FLASH_BQ")
    monkeypatch.delenv("DISTRIFUSER_TPU_FLASH_BK")
    monkeypatch.setenv("DISTRIFUSER_TPU_FLASH_IMPL", "upstream")
    assert _route(monkeypatch, c=16 * 72, heads=16).impl == "upstream"
    monkeypatch.setenv("DISTRIFUSER_TPU_FLASH_IMPL", "xla")
    assert _route(monkeypatch, c=16 * 72, heads=16) == Route("xla")
    monkeypatch.delenv("DISTRIFUSER_TPU_FLASH_IMPL")
    monkeypatch.setenv("DISTRIFUSER_TPU_FLASH", "0")
    assert _route(monkeypatch, c=16 * 72, heads=16) == Route("xla")


def test_unmoved_entries_stay_where_they_were(monkeypatch):
    """(64, 14), (64, 16) keep the upstream kernel; a length between the
    buckets of a moved entry and an unmoved one goes to the nearer."""
    monkeypatch.setattr(sdpa_routing, "MODEL_VALIDATED_OVERRIDES",
                        SHIPPED_OVERRIDES)
    assert _route(monkeypatch, lq=16384, lk=16384).impl == "upstream"
    assert _route(monkeypatch, lq=57600 // 128 * 128,
                  lk=57600 // 128 * 128).impl == "upstream"
