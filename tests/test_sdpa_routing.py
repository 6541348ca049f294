"""SDPA routing: one function (`ops/sdpa_routing.py route`) decides, one table.

The reference always runs fused SDPA (modules/pp/attn.py:153); here the
kernel is chosen per shape.  `NAMED_SHAPES` and `RANGE_EDGES` were recorded
at the parent of PR 29 (commit f8bee82: two tables competing in `lookup()`,
four environment variables, the padded gate inside `sdpa`) with the harness
below, platform patched to "tpu", the shipped tables in place: what
`_resolve_route` returned and which kernel `sdpa` then entered with which
tiles.  The one-table `route()` has to reproduce every line of it."""

import ast
import importlib
import os
import re

import jax
import jax.numpy as jnp
import pytest

from distrifuser_tpu.ops import sdpa_routing
from distrifuser_tpu.ops.sdpa_routing import Route, Row

attention = importlib.import_module("distrifuser_tpu.ops.attention")
fa = importlib.import_module("distrifuser_tpu.ops.flash_attention")

PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(attention.__file__)))


class _Dev:
    def __init__(self, platform):
        self.platform = platform


@pytest.fixture(autouse=True)
def _clean_flash_env(monkeypatch):
    """Isolate routing tests from env leaked by other test files —
    __graft_entry__ setdefaults DISTRIFUSER_TPU_FLASH=0 process-wide when
    test_graft_entry runs earlier in the session.  Runs before each test
    body, so tests that set the variable intentionally still win."""
    monkeypatch.delenv("DISTRIFUSER_TPU_FLASH", raising=False)


def _route(platform="tpu", lq=4096, lk=4096, c=640, heads=10):
    return sdpa_routing.route(lq, lk, c, heads, platform)


def _runs(monkeypatch, platform, d, heads, lq, lk):
    """What `sdpa` enters for one call, traced abstractly: (kernel, block_q,
    block_k, interpret, padded) — the tiles as fitted to the call."""
    monkeypatch.setattr(jax, "devices", lambda: [_Dev(platform)])
    seen, padded = [], []

    def spy(name):
        def kernel(q, k, v, *args, **kw):
            seen.append((name, kw.get("block_q"), kw.get("block_k"),
                         kw.get("interpret", False)))
            return q
        return kernel

    real_padded = fa.padded_flash_sdpa

    def padded_spy(*args, **kw):
        padded.append(True)
        return real_padded(*args, **kw)

    monkeypatch.setattr(fa, "flash_sdpa", spy("inrepo"))
    monkeypatch.setattr(fa, "upstream_flash_sdpa", spy("upstream"))
    monkeypatch.setattr(fa, "padded_flash_sdpa", padded_spy)
    monkeypatch.setattr(
        attention, "_sdpa_xla",
        lambda q, k, v, scale: (seen.append(("xla", None, None, False)), q)[1])
    shape = lambda l: jax.ShapeDtypeStruct((2, l, d * heads), jnp.bfloat16)  # noqa: E731
    jax.eval_shape(lambda q, k, v: attention.sdpa(q, k, v, heads=heads),
                   shape(lq), shape(lk), shape(lk))
    assert len(set(seen)) == 1, seen  # the chunked XLA path enters it n times
    return seen[0] + (bool(padded),)


def _assert_as_recorded(monkeypatch, platform, shape, resolved, ran):
    d, heads, lq, lk = shape
    route = sdpa_routing.route(lq, lk, d * heads, heads, platform)
    if ran[-1]:
        # the parent's resolver said xla here and sdpa took the padded route
        # on its own; route() now says so itself
        assert resolved == ("xla", None, None)
        assert route == Route("padded", kernel=ran[0])
    else:
        assert (route.impl, route.block_q, route.block_k) == resolved
        assert route.kernel is None
    assert _runs(monkeypatch, platform, d, heads, lq, lk) == ran


# id, (head dim, heads, lq, lk), platform, DISTRIFUSER_TPU_FLASH, the
# parent's _resolve_route, and what its sdpa then ran: (kernel, block_q,
# block_k, interpret, padded)
NAMED_SHAPES = [
    ("sdxl-1024-self-64x64", (64, 10, 4096, 4096), "tpu", None,
     ("inrepo", 1024, 512), ("inrepo", 1024, 512, False, False)),
    ("sdxl-1024-self-32x32", (64, 20, 1024, 1024), "tpu", None,
     ("inrepo", 1024, 1024), ("inrepo", 1024, 1024, False, False)),
    ("sdxl-1024-cross-64x64", (64, 10, 4096, 77), "tpu", None,
     ("xla", None, None), ("xla", None, None, False, False)),
    ("sdxl-1024-cross-32x32", (64, 20, 1024, 77), "tpu", None,
     ("xla", None, None), ("xla", None, None, False, False)),
    ("sdxl-512-self-32x32", (64, 10, 1024, 1024), "tpu", None,
     ("inrepo", 1024, 1024), ("inrepo", 1024, 1024, False, False)),
    ("sdxl-512-self-16x16", (64, 20, 256, 256), "tpu", None,
     ("xla", None, None), ("xla", None, None, False, False)),
    ("sdxl-768-self-48x48", (64, 10, 2304, 2304), "tpu", None,
     ("xla", None, None), ("xla", None, None, False, False)),
    ("sdxl-768-self-24x24", (64, 20, 576, 576), "tpu", None,
     ("xla", None, None), ("xla", None, None, False, False)),
    ("sdxl-2048-self-128x128", (64, 10, 16384, 16384), "tpu", None,
     ("upstream", 512, 1024), ("upstream", 512, 1024, False, False)),
    ("sdxl-2048-self-64x64", (64, 20, 4096, 4096), "tpu", None,
     ("inrepo", 1024, 512), ("inrepo", 1024, 512, False, False)),
    ("sdxl-3840-self-240x240", (64, 10, 57600, 57600), "tpu", None,
     ("upstream", 256, 256), ("upstream", 256, 256, False, False)),
    ("sdxl-3840-self-120x120-unaligned", (64, 20, 14400, 14400), "tpu", None,
     ("xla", None, None), ("upstream", 128, 128, False, True)),
    ("sdxl-1024-patch4-64x64", (64, 10, 1024, 4096), "tpu", None,
     ("inrepo", 1024, 512), ("inrepo", 1024, 512, False, False)),
    ("sdxl-1024-patch4-32x32", (64, 20, 256, 1024), "tpu", None,
     ("inrepo", 1024, 1024), ("inrepo", 256, 1024, False, False)),
    ("sd15-512-d40", (40, 8, 4096, 4096), "tpu", None,
     ("upstream", None, None), ("upstream", None, None, False, False)),
    ("sd15-512-d80", (80, 8, 1024, 1024), "tpu", None,
     ("upstream", None, None), ("upstream", None, None, False, False)),
    ("sd15-512-d160", (160, 8, 256, 256), "tpu", None,
     ("xla", None, None), ("xla", None, None, False, False)),
    ("pixart-512", (72, 16, 1024, 1024), "tpu", None,
     ("upstream", None, None), ("upstream", None, None, False, False)),
    ("pixart-1024", (72, 16, 4096, 4096), "tpu", None,
     ("inrepo", 1024, 512), ("inrepo", 1024, 512, False, False)),
    ("pixart-2048", (72, 16, 16384, 16384), "tpu", None,
     ("upstream", None, None), ("upstream", None, None, False, False)),
    ("sd3-1024-joint-unaligned", (64, 24, 4250, 4250), "tpu", None,
     ("xla", None, None), ("upstream", 256, 256, False, True)),
    ("vae-mid-512", (512, 1, 4096, 4096), "tpu", None,
     ("upstream", None, None), ("upstream", None, None, False, False)),
    ("vae-mid-1024", (512, 1, 16384, 16384), "tpu", None,
     ("upstream", None, None), ("upstream", None, None, False, False)),
    ("vae-mid-2048", (512, 1, 65536, 65536), "tpu", None,
     ("upstream", None, None), ("upstream", None, None, False, False)),
    ("sdxl-1024-tp-5heads-64x64", (64, 5, 4096, 4096), "tpu", None,
     ("inrepo", 1024, 512), ("inrepo", 1024, 512, False, False)),
    ("sdxl-1024-tp-10heads-32x32", (64, 10, 1024, 1024), "tpu", None,
     ("inrepo", 1024, 1024), ("inrepo", 1024, 1024, False, False)),
    ("flash0-aligned", (64, 10, 4096, 4096), "tpu", "0",
     ("xla", None, None), ("xla", None, None, False, False)),
    ("flash0-unaligned", (64, 24, 4250, 4250), "tpu", "0",
     ("xla", None, None), ("xla", None, None, False, False)),
    ("flash1-aligned", (64, 10, 4096, 4096), "tpu", "1",
     ("upstream", None, None), ("upstream", None, None, False, False)),
    ("flash1-aligned-short", (64, 20, 256, 256), "tpu", "1",
     ("upstream", None, None), ("upstream", None, None, False, False)),
    ("flash1-unaligned", (64, 24, 4250, 4250), "tpu", "1",
     ("xla", None, None), ("upstream", 256, 256, False, True)),
    ("cpu-aligned", (64, 10, 4096, 4096), "cpu", None,
     ("xla", None, None), ("xla", None, None, False, False)),
    ("cpu-unaligned", (64, 24, 4250, 4250), "cpu", None,
     ("xla", None, None), ("xla", None, None, False, False)),
    ("cpu-flash0-aligned", (64, 10, 4096, 4096), "cpu", "0",
     ("xla", None, None), ("xla", None, None, False, False)),
    ("cpu-flash1-aligned", (64, 10, 4096, 4096), "cpu", "1",
     ("inrepo", None, None), ("inrepo", 128, 128, True, False)),
    ("cpu-flash1-unaligned", (64, 24, 4250, 4250), "cpu", "1",
     ("xla", None, None), ("xla", None, None, False, False)),
]

# both edges of every range of ISSUE 29"s table, lq = lk = kv_len:
# (head dim, kv_len, the parent"s _resolve_route, what its sdpa ran)
RANGE_EDGES = [
    (64, 128, ("xla", None, None),
     ("xla", None, None, False, False)),
    (64, 640, ("xla", None, None),
     ("xla", None, None, False, False)),
    (64, 768, ("inrepo", 1024, 1024),
     ("inrepo", 256, 256, False, False)),
    (64, 1408, ("inrepo", 1024, 1024),
     ("inrepo", 128, 128, False, False)),
    (64, 1536, ("xla", None, None),
     ("xla", None, None, False, False)),
    (64, 2816, ("xla", None, None),
     ("xla", None, None, False, False)),
    (64, 2944, ("inrepo", 1024, 512),
     ("inrepo", 128, 128, False, False)),
    (64, 5760, ("inrepo", 1024, 512),
     ("inrepo", 128, 128, False, False)),
    (64, 5888, ("xla", None, None),
     ("xla", None, None, False, False)),
    (64, 8192, ("xla", None, None),
     ("xla", None, None, False, False)),
    (64, 8320, ("upstream", 512, 1024),
     ("upstream", 128, 128, False, False)),
    (64, 32768, ("upstream", 512, 1024),
     ("upstream", 512, 1024, False, False)),
    (64, 32896, ("upstream", 256, 256),
     ("upstream", 128, 128, False, False)),
    (64, 185344, ("upstream", 256, 256),
     ("upstream", 256, 256, False, False)),
    (64, 185472, ("upstream", None, None),
     ("upstream", None, None, False, False)),
    (64, 1048576, ("upstream", None, None),
     ("upstream", None, None, False, False)),
    (72, 128, ("xla", None, None),
     ("xla", None, None, False, False)),
    (72, 896, ("xla", None, None),
     ("xla", None, None, False, False)),
    (72, 1024, ("upstream", None, None),
     ("upstream", None, None, False, False)),
    (72, 1408, ("upstream", None, None),
     ("upstream", None, None, False, False)),
    (72, 1536, ("xla", None, None),
     ("xla", None, None, False, False)),
    (72, 2816, ("xla", None, None),
     ("xla", None, None, False, False)),
    (72, 2944, ("inrepo", 1024, 512),
     ("inrepo", 128, 128, False, False)),
    (72, 5760, ("inrepo", 1024, 512),
     ("inrepo", 128, 128, False, False)),
    (72, 5888, ("xla", None, None),
     ("xla", None, None, False, False)),
    (72, 11520, ("xla", None, None),
     ("xla", None, None, False, False)),
    (72, 11648, ("upstream", None, None),
     ("upstream", None, None, False, False)),
    (72, 1048576, ("upstream", None, None),
     ("upstream", None, None, False, False)),
    (128, 128, ("xla", None, None),
     ("xla", None, None, False, False)),
    (128, 896, ("xla", None, None),
     ("xla", None, None, False, False)),
    (128, 1024, ("upstream", None, None),
     ("upstream", None, None, False, False)),
    (128, 1048576, ("upstream", None, None),
     ("upstream", None, None, False, False)),
]

@pytest.mark.parametrize(
    "shape,platform,flash_env,resolved,ran",
    [case[1:] for case in NAMED_SHAPES], ids=[case[0] for case in NAMED_SHAPES])
def test_named_shape_routes(monkeypatch, shape, platform, flash_env, resolved,
                            ran):
    if flash_env is not None:
        monkeypatch.setenv("DISTRIFUSER_TPU_FLASH", flash_env)
    _assert_as_recorded(monkeypatch, platform, shape, resolved, ran)


@pytest.mark.parametrize(
    "d,kv_len,resolved,ran", RANGE_EDGES,
    ids=[f"{d}-{kv_len}" for d, kv_len, _, _ in RANGE_EDGES])
def test_range_edges_route_as_before(monkeypatch, d, kv_len, resolved, ran):
    heads = 16 if d == 72 else 10
    _assert_as_recorded(monkeypatch, "tpu", (d, heads, kv_len, kv_len),
                        resolved, ran)


def test_env_off_wins_over_everything(monkeypatch):
    monkeypatch.setenv("DISTRIFUSER_TPU_FLASH", "0")
    monkeypatch.setattr(
        sdpa_routing, "TABLE",
        {64: (Row(128, 8192, Route("inrepo", 256, 512), "test"),)})
    assert _route() == Route("xla")
    assert _route(lq=4250, lk=4250) == Route("xla")


def test_unaligned_is_padded_on_the_chip_and_xla_elsewhere():
    """The gate that stood in `sdpa` behind the resolver's back: an unaligned
    length takes the padded upstream kernel from 1024 keys on the chip, head
    dim a multiple of 8 up to 256; everything else unaligned is XLA."""
    padded = Route("padded", kernel="upstream")
    assert _route(lq=4095, lk=4095) == padded
    assert _route(lq=4095, lk=4096) == padded
    assert _route(lq=4096, lk=1000) == Route("xla")
    assert _route(lq=4095, lk=4095, c=512, heads=1) == Route("xla")
    assert _route(lq=4095, lk=4095, c=60, heads=10) == Route("xla")
    assert _route(lq=4096, lk=4096, c=60, heads=10) == Route("xla")
    assert _route(platform="cpu", lq=4095, lk=4095) == Route("xla")


def test_cpu_defaults_to_xla():
    assert _route(platform="cpu") == Route("xla")


def test_force_on_cpu_is_inrepo_interpret_path(monkeypatch):
    monkeypatch.setenv("DISTRIFUSER_TPU_FLASH", "1")
    assert _route(platform="cpu").impl == "inrepo"


def test_table_rows_are_inclusive_and_keyed_by_head_dim(monkeypatch):
    """A row governs its own head dim from kv_lo to kv_hi inclusive, whatever
    lq is; outside it the default decides (xla under 1024 keys, upstream
    with its own tiles from there)."""
    row = Route("inrepo", 256, 512)
    monkeypatch.setattr(sdpa_routing, "TABLE",
                        {64: (Row(512, 2048, row, "test"),)})
    for lk in (512, 1280, 2048):
        assert _route(lq=128, lk=lk) == row
    assert _route(lq=384, lk=384) == Route("xla")
    assert _route(lq=2176, lk=2176) == Route("upstream")
    assert _route(lq=1280, lk=1280, c=1280) == Route("upstream")
    assert _route(lq=512, lk=512, c=1280) == Route("xla")


def test_sdpa_still_computes_on_cpu(monkeypatch):
    """End to end: routing lands on a working path whatever the table says."""
    import numpy as np

    monkeypatch.setattr(
        sdpa_routing, "TABLE",
        {64: (Row(128, 128, Route("inrepo", 64, 64), "test"),)})
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 128, 128), jnp.float32)
    out = attention.sdpa(q, q, q, heads=2)
    assert out.shape == (1, 128, 128)
    assert np.isfinite(np.asarray(out)).all()


def test_largest_dividing_tile():
    """Tile fitting for the upstream kernel: a tuned tile that
    does not divide the call's length is halved to the largest power-of-2
    divisor instead of being dropped (which would mix in the kernel's
    hardcoded 512/1024 defaults — themselves non-dividing for shapes like
    Lk=57600)."""
    fit = fa.largest_dividing_tile
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
def test_cell_shapes_resolve_to_the_seq_minor_kernel(d, heads, l):
    """PR 25: the three shapes of the benchmark's cells route to the in-repo
    (sequence-minor) kernel, with tiles that divide the cell's length and
    the local Q lengths of the patch path (L/2, L/4 rows, KV gathered)."""
    route = _route(lq=l, lk=l, c=heads * d, heads=heads)
    assert route.impl == "inrepo", route
    for tile in (route.block_q, route.block_k):
        assert tile and tile >= 128 and tile & (tile - 1) == 0
    assert l % route.block_q == 0 and l % route.block_k == 0
    # the patch path keys on kv_len, so it inherits the row
    for n in (2, 4):
        assert _route(lq=l // n, lk=l, c=heads * d,
                      heads=heads) == route


def test_inrepo_route_tiles_are_fitted_to_the_call(monkeypatch):
    """`sdpa` cuts a route's tiles down to what divides this call's lengths
    (a row holds lengths its tiles do not divide; the patch path's local
    Lq is a fraction of the cell's) and hands them to `flash_sdpa`."""
    monkeypatch.setattr(
        sdpa_routing, "TABLE",
        {64: (Row(2944, 5760, Route("inrepo", 1024, 512), "test"),)})
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


def test_unmoved_entries_stay_where_they_were():
    """d=64 at 16384 and 57600 keys keeps the upstream kernel."""
    assert _route(lq=16384, lk=16384).impl == "upstream"
    assert _route(lq=57600 // 128 * 128,
                  lk=57600 // 128 * 128).impl == "upstream"


def test_kernels_and_table_import_nothing_from_attention():
    """The arrows point one way: `attention.py` imports the kernels and the
    table; neither reaches back up into its caller, lazily or otherwise."""
    for fname in ("flash_attention.py", "sdpa_routing.py"):
        with open(os.path.join(PACKAGE, "ops", fname)) as f:
            tree = ast.parse(f.read())
        names = set()
        for node in ast.walk(tree):  # function bodies included
            if isinstance(node, ast.Import):
                names.update(a.name.rsplit(".", 1)[-1] for a in node.names)
            elif isinstance(node, ast.ImportFrom):
                names.add((node.module or "").rsplit(".", 1)[-1])
                names.update(a.name for a in node.names)
        assert "attention" not in names, (fname, sorted(names))


def test_one_env_hatch_read_in_one_module():
    """Under distrifuser_tpu/ the environment is asked for one
    DISTRIFUSER_TPU_ name, in one module; no other such name is so much as
    mentioned."""
    reads, mentioned = set(), set()
    for root, _dirs, files in os.walk(PACKAGE):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            with open(path) as f:
                src = f.read()
            rel = os.path.relpath(path, PACKAGE)
            mentioned.update(re.findall(r"DISTRIFUSER_TPU_[A-Z0-9_]*", src))
            for node in ast.walk(ast.parse(src)):
                # os.environ.get(X) / os.getenv(X) / environ.setdefault(X, ..)
                # / os.environ[X] / X in os.environ
                if isinstance(node, ast.Call) and node.args:
                    target, arg = ast.unparse(node.func), node.args[0]
                elif isinstance(node, ast.Subscript):
                    target, arg = ast.unparse(node.value), node.slice
                elif isinstance(node, ast.Compare) and node.comparators:
                    target, arg = ast.unparse(node.comparators[0]), node.left
                else:
                    continue
                if "environ" in target or "getenv" in target:
                    name = ast.unparse(arg)
                    if "DISTRIFUSER_TPU_" in name:
                        reads.add((rel, name.strip("'\"")))
    assert reads == {(os.path.join("ops", "sdpa_routing.py"),
                      "DISTRIFUSER_TPU_FLASH")}, reads
    assert mentioned == {"DISTRIFUSER_TPU_FLASH"}, mentioned
