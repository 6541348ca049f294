"""distrilint framework: every checker fails on its seeded violation,
the baseline round-trips with provenance enforcement, fingerprints are
stable across unrelated edits, and the jaxpr overlap gate agrees with
the slow HLO tests' classification on the tiny config — fast enough to
run un-slow-marked on the 2-core tier-1 runner (trace, never compile).
"""

import ast
import dataclasses
import os
import subprocess
import sys
import textwrap

import pytest

from distrifuser_tpu.analysis import (
    Baseline,
    BaselineError,
    CheckContext,
    Finding,
    apply_baseline,
    render_baseline,
    run_checkers,
)
from distrifuser_tpu.analysis.checkers import (
    collective_containment,
    compile_identity,
    lock_discipline,
    overlap_gate,
    route_tables,
    typed_raises,
)
from distrifuser_tpu.analysis.checkers.lock_discipline import guard
from distrifuser_tpu.analysis.jaxpr_overlap import (
    analyze_jaxpr_collectives,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def real_ctx():
    return CheckContext(REPO)


# ---------------------------------------------------------------------------
# acceptance: the shipped tree is clean under the checked-in baseline


def test_shipped_tree_strict_clean():
    """`--strict` semantics in-process: zero non-baselined findings and
    zero stale baseline entries on the tree as shipped."""
    results = run_checkers(real_ctx())
    findings = [f for fs in results.values() for f in fs]
    baseline = Baseline.load(os.path.join(
        REPO, "distrifuser_tpu", "analysis", "baseline.txt"))
    applied = apply_baseline(findings, baseline)
    assert not applied.new, [f.render() for f in applied.new]
    assert not applied.stale, [e.fingerprint for e in applied.stale]
    # all seven checkers actually ran (a crashed checker emits findings)
    assert set(results) == {
        "typed-raises", "collective-containment", "sync-containment",
        "lock-discipline", "compile-identity", "route-tables",
        "jaxpr-overlap",
    }


def test_cli_runs_fast_checkers(tmp_path):
    """The module entry point works as a subprocess (the CI invocation
    shape), restricted to AST checkers so the test stays cheap."""
    out = tmp_path / "findings.json"
    proc = subprocess.run(
        [sys.executable, "-m", "distrifuser_tpu.analysis", "--strict",
         "--checker", "typed-raises", "--checker", "lock-discipline",
         "--json", str(out)],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr
    assert out.exists()
    assert "distrilint ok" in proc.stdout


# ---------------------------------------------------------------------------
# compile-identity: removing any single wiring station fails the gate


def _model():
    return compile_identity.build_model(real_ctx())


def test_compile_identity_clean_on_real_tree():
    assert compile_identity.check_model(_model()) == []


@pytest.mark.parametrize("field", [
    f.name for f in __import__(
        "dataclasses").fields(__import__(
            "distrifuser_tpu.serve.cache",
            fromlist=["ExecKey"]).ExecKey)])
def test_removing_any_exec_key_field_fails(field):
    """ISSUE 13 acceptance: drop any single ExecKey field and the gate
    fails — via the ServeConfig mirror rule, a dangling short()/policy
    reference, or a dangling _exec_key_for kwarg."""
    m = _model()
    mutated = dataclasses.replace(
        m, exec_key_fields=tuple(f for f in m.exec_key_fields
                                 if f != field))
    findings = compile_identity.check_model(mutated)
    assert findings, f"removing ExecKey.{field} went undetected"


@pytest.mark.parametrize("station,attr_field", [
    ("short_attrs", "short"),
    ("policy_attrs", "policy"),
    ("key_call_kwargs", "key-for"),
])
def test_removing_handling_fails(station, attr_field):
    """Dropping a field's handling from short()/apply_key_policy/
    _exec_key_for (modelled by removing it from the extracted attr set)
    fails the gate for every non-allowlisted field."""
    m = _model()
    for field in m.exec_key_fields:
        if station == "policy_attrs" and (
                field in compile_identity.STRUCTURAL_FIELDS):
            continue
        if station == "key_call_kwargs" and (
                field in compile_identity.LADDER_ONLY_ALLOWLIST):
            continue
        attrs = frozenset(getattr(m, station) - {field})
        mutated = dataclasses.replace(m, **{station: attrs})
        findings = compile_identity.check_model(mutated)
        idents = {f.identity for f in findings}
        assert f"{attr_field}:{field}" in idents, (
            f"dropping {field} from {station} went undetected")


def test_unmirrored_serve_knob_fails():
    """The seeded violation the checker exists for: a new ServeConfig
    knob with no ExecKey field and no allowlist entry."""
    m = _model()
    mutated = dataclasses.replace(
        m, serve_config_fields=m.serve_config_fields + ("new_knob",))
    findings = compile_identity.check_model(mutated)
    assert any(f.identity == "mirror:new_knob" for f in findings)


def test_stale_allowlist_entry_fails(monkeypatch):
    monkeypatch.setitem(compile_identity.SERVE_RUNTIME_ALLOWLIST,
                        "ghost_knob", "no longer exists")
    findings = compile_identity.check_model(_model())
    assert any(f.identity == "allowlist-stale:ghost_knob"
               for f in findings)


# ---------------------------------------------------------------------------
# collective containment: seeded raw collective


RAW_COLLECTIVE_SRC = textwrap.dedent("""\
    from jax import lax

    def leak(x, axis):
        g = lax.all_gather(x, axis)
        return g.sum()

    def leak_twice(x, axis):
        a = lax.ppermute(x, axis, perm=[(0, 1)])
        b = lax.ppermute(a, axis, perm=[(1, 0)])
        return a + b
""")


def test_raw_collective_fixture_flagged():
    tree = ast.parse(RAW_COLLECTIVE_SRC)
    findings = collective_containment.scan_module(
        tree, "distrifuser_tpu/models/fixture.py")
    idents = {f.identity for f in findings}
    assert idents == {"leak:all_gather:0", "leak_twice:ppermute:0",
                      "leak_twice:ppermute:1"}


def test_blessed_module_not_flagged():
    tree = ast.parse(RAW_COLLECTIVE_SRC)
    assert collective_containment.scan_module(
        tree, "distrifuser_tpu/parallel/collectives.py") == []


def test_wrapper_calls_not_flagged():
    src = textwrap.dedent("""\
        from ..parallel.collectives import all_gather, psum

        def fine(x, axis):
            return psum(all_gather(x, axis), axis)
    """)
    assert collective_containment.scan_module(
        ast.parse(src), "distrifuser_tpu/models/fixture.py") == []


def test_unaliased_jax_lax_import_flagged():
    """`import jax.lax; jax.lax.psum(...)` must not evade the gate."""
    for imp in ("import jax.lax",
                "import jax.lax as L",
                "import jax"):
        base = {"import jax.lax": "jax.lax",
                "import jax.lax as L": "L",
                "import jax": "jax.lax"}[imp]
        src = f"{imp}\n\ndef leak(x, axis):\n    return {base}.psum(x, axis)\n"
        findings = collective_containment.scan_module(
            ast.parse(src), "distrifuser_tpu/models/fixture.py")
        assert [f.identity for f in findings] == ["leak:psum:0"], imp


def test_from_import_collective_flagged():
    src = textwrap.dedent("""\
        from jax.lax import all_gather as ag

        def leak(x, axis):
            return ag(x, axis)
    """)
    findings = collective_containment.scan_module(
        ast.parse(src), "distrifuser_tpu/ops/fixture.py")
    assert [f.identity for f in findings] == ["leak:all_gather:0"]


# ---------------------------------------------------------------------------
# lock discipline: seeded unguarded mutation


LOCK_FIXTURE_SRC = textwrap.dedent("""\
    class Cacheish:
        def __init__(self):
            self._entries = {}
            self._lock = object()
            self.hits = 0

        def good(self, k, v):
            with self._lock:
                self._entries[k] = v
                self.hits += 1

        def bad_assign(self, k, v):
            self._entries[k] = v

        def bad_augassign(self):
            self.hits += 1

        def bad_method(self, k):
            self._entries.pop(k, None)

        def _evict_locked(self, k):
            del self._entries[k]

        def bad_closure(self):
            with self._lock:
                def worker():
                    self.hits += 1
                return worker
""")


def _lock_findings(src=LOCK_FIXTURE_SRC):
    cls = ast.parse(src).body[0]
    spec = guard("_lock", ["_entries", "hits"])
    return lock_discipline.scan_class(cls, spec, "serve/fixture.py")


def test_lock_fixture_flags_unguarded_mutations():
    idents = {f.identity for f in _lock_findings()}
    assert idents == {
        "Cacheish.bad_assign:_entries:0",
        "Cacheish.bad_augassign:hits:0",
        "Cacheish.bad_method:_entries:0",
        # the closure runs on another thread: the enclosing with-block
        # does not protect it
        "Cacheish.worker:hits:0",
    }


def test_lock_registry_names_live_classes():
    findings = lock_discipline.run(real_ctx())
    assert not [f for f in findings
                if f.identity.startswith("registry-missing")], (
        [f.render() for f in findings])


# ---------------------------------------------------------------------------
# typed raises: seeded bare raise


def test_bare_raise_fixture_flagged():
    src = textwrap.dedent("""\
        class S:
            def hot(self):
                raise RuntimeError("boom")

            def validate(self, x):
                if x < 0:
                    raise ValueError("fine")

            def typed(self):
                raise ServerClosedError("fine")

        def reraise(exc):
            raise Exception
    """)
    findings = typed_raises.scan_module(
        ast.parse(src), "distrifuser_tpu/serve/fixture.py")
    assert {f.identity for f in findings} == {
        "S.hot:RuntimeError:0", "reraise:Exception:0"}


# ---------------------------------------------------------------------------
# route table: seeded violations (live-module monkeypatch)


def test_route_tables_clean_then_seeded(monkeypatch):
    assert route_tables.check_tables() == []
    from distrifuser_tpu.ops import sdpa_routing
    from distrifuser_tpu.ops.sdpa_routing import Route, Row

    monkeypatch.setattr(
        sdpa_routing, "TABLE",
        {64: (Row(2944, 5760, Route("inrepo", 1024, 512), "  "),)})
    findings = route_tables.check_tables()
    assert [f.identity for f in findings] == ["sdpa:origin:64:(2944, 5760)"]


def test_route_tables_overlapping_ranges(monkeypatch):
    from distrifuser_tpu.ops import sdpa_routing
    from distrifuser_tpu.ops.sdpa_routing import Route, Row

    for second in (Row(2816, 4096, Route("xla"), "test"),   # shares an edge
                   Row(128, 640, Route("xla"), "test")):    # descends
        monkeypatch.setattr(
            sdpa_routing, "TABLE",
            {72: (Row(1536, 2816, Route("xla"), "test"), second)})
        findings = route_tables.check_tables()
        assert [f.identity.rsplit(":", 2)[0] for f in findings] == [
            "sdpa:order"], findings


# ---------------------------------------------------------------------------
# baseline: round-trip, provenance enforcement, stale detection


def _finding(ident="f:x:0", path="a/b.py", checker="typed-raises"):
    return Finding(checker=checker, path=path, line=7,
                   message="seeded", identity=ident)


def test_baseline_round_trip():
    f1, f2 = _finding("one"), _finding("two")
    text = render_baseline([f1, f2])
    # machine-written entries carry the UNREVIEWED placeholder: parsing
    # must REJECT them until a human writes the reason
    with pytest.raises(BaselineError, match="UNREVIEWED"):
        Baseline.parse(text)
    text = text.replace(
        "UNREVIEWED — justify this suppression or fix the finding",
        "deliberate: seeded fixture")
    baseline = Baseline.parse(text)
    assert len(baseline.entries) == 2
    applied = apply_baseline([f1, f2], baseline)
    assert not applied.new and not applied.stale
    assert len(applied.suppressed) == 2
    # reasons survive a re-render (the add/expire cycle)
    again = Baseline.parse(render_baseline([f1, f2], previous=baseline))
    assert all(e.reason == "deliberate: seeded fixture"
               for e in again.entries)


def test_baseline_stale_entry_detected():
    f1, f2 = _finding("one"), _finding("two")
    text = render_baseline([f1, f2], previous=None).replace(
        "UNREVIEWED — justify this suppression or fix the finding", "ok")
    baseline = Baseline.parse(text)
    applied = apply_baseline([f1], baseline)  # f2 healed
    assert len(applied.stale) == 1
    assert applied.stale[0].fingerprint == f2.fingerprint


def test_baseline_requires_provenance():
    f = _finding("one")
    entry = f"{f.fingerprint} {f.checker} {f.path} seeded\n"
    with pytest.raises(BaselineError, match="provenance"):
        Baseline.parse(entry)
    # a blank line detaches a reason from a later entry
    with pytest.raises(BaselineError, match="provenance"):
        Baseline.parse(f"# provenance: ok\n\n{entry}")
    # attached reason parses
    assert len(Baseline.parse(
        f"# provenance: ok\n{entry}").entries) == 1


def test_baseline_rejects_malformed_lines():
    with pytest.raises(BaselineError, match="unparseable"):
        Baseline.parse("# provenance: ok\nnot-a-fingerprint\n")
    with pytest.raises(BaselineError, match="fingerprint"):
        Baseline.parse("# provenance: ok\nZZZZZZZZZZZZ c p note\n")


def test_shipped_baseline_parses_with_reasons():
    baseline = Baseline.load(os.path.join(
        REPO, "distrifuser_tpu", "analysis", "baseline.txt"))
    assert baseline.entries, "shipped baseline expected to be non-empty"
    assert all(e.reason for e in baseline.entries)


# ---------------------------------------------------------------------------
# fingerprints: stable across unrelated edits, distinct per violation


def test_fingerprint_stable_across_unrelated_edits():
    before = collective_containment.scan_module(
        ast.parse(RAW_COLLECTIVE_SRC), "distrifuser_tpu/x.py")
    shifted = ("# comment\n" * 40) + RAW_COLLECTIVE_SRC
    after = collective_containment.scan_module(
        ast.parse(shifted), "distrifuser_tpu/x.py")
    assert [f.fingerprint for f in before] == [
        f.fingerprint for f in after]
    assert [f.line for f in before] != [f.line for f in after]


def test_fingerprint_distinguishes_path_and_checker():
    a = _finding("one", path="a.py")
    b = _finding("one", path="b.py")
    c = _finding("one", path="a.py", checker="lock-discipline")
    assert len({a.fingerprint, b.fingerprint, c.fingerprint}) == 3


# ---------------------------------------------------------------------------
# jaxpr overlap: synthetic fixtures + agreement with the HLO tests


def _scan_reports(body_fn, n_carry_args, devices8):
    """Trace a shard_map'd scan over the 8-device mesh and analyze it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    mesh = Mesh(devices8, ("sp",))

    def device_fn(*carry):
        def body(c, _):
            return body_fn(*c), None

        out, _ = jax.lax.scan(body, carry, jnp.arange(4))
        return out

    fn = shard_map(device_fn, mesh=mesh,
                   in_specs=tuple(P("sp") for _ in range(n_carry_args)),
                   out_specs=tuple(P("sp") for _ in range(n_carry_args)))
    args = [jnp.ones((8, 4)) for _ in range(n_carry_args)]
    cj = jax.make_jaxpr(fn)(*args)
    return analyze_jaxpr_collectives(cj)


PERM = [(i, (i + 1) % 8) for i in range(8)]


def test_jaxpr_deferred_fixture(devices8):
    """Seeded deferred collective: ppermute straight to the carry."""
    import jax.numpy as jnp
    from jax import lax

    def body(x, stale):
        y = x * 1.5 + stale  # consume LAST step's exchange
        fresh = lax.ppermute(y, "sp", PERM)  # this step's: carry-only
        return y, fresh

    reports = _scan_reports(body, 2, devices8)
    (report,) = [r for r in reports if r.n_collectives]
    assert report.deferred and not report.inline, report
    assert list(report.deferred.values()) == ["ppermute"]
    del jnp  # silence linters


def test_jaxpr_inline_fixture(devices8):
    """Seeded inlined collective: the ppermute output feeds a matmul in
    the same iteration — must classify inline."""
    import jax.numpy as jnp
    from jax import lax

    def body(x, stale):
        g = lax.ppermute(x, "sp", PERM)
        y = x @ g.T + stale * 0.5  # same-step compute on the exchange
        return y, g

    reports = _scan_reports(body, 2, devices8)
    (report,) = [r for r in reports if r.n_collectives]
    assert report.inline and not report.deferred, report
    del jnp


def test_jaxpr_deferred_compute_fixture(devices8):
    """Elementwise-only consumers en route to the carry classify
    deferred_compute (the dequant-chain carve-out), never deferred."""
    from jax import lax

    def body(x, stale):
        y = x * 1.5 + stale
        fresh = lax.ppermute(y, "sp", PERM) * 0.25 + 1.0  # dequant-ish
        return y, fresh

    reports = _scan_reports(body, 2, devices8)
    (report,) = [r for r in reports if r.n_collectives]
    assert report.deferred_compute and not report.inline, report
    assert not report.deferred


def test_overlap_gate_fails_on_seeded_inline_report():
    """Seeded violation for the gate itself: a stale scan whose refresh
    ppermutes turned inline must produce findings (inline-count,
    inline-kind, halo-missing all fire)."""
    from distrifuser_tpu.analysis.jaxpr_overlap import JaxprLoopReport

    bad = JaxprLoopReport(
        kind="scan",
        deferred={f"all_gather#{i}": "all_gather" for i in range(12)},
        inline={"ppermute#0": "ppermute", "ppermute#1": "ppermute",
                "ppermute#2": "ppermute"},
        deferred_compute={},
    )
    findings = overlap_gate._gate_stale([bad], "stale")
    idents = {f.identity for f in findings}
    assert "stale:inline-count" in idents
    assert "stale:inline-kind" in idents
    assert "stale:halo-missing" in idents
    # and an empty program is itself a finding, never a silent pass
    assert overlap_gate._gate_stale([], "stale")[0].identity == (
        "stale:no-loops")


@pytest.fixture(scope="module")
def stale_reports(devices8):
    del devices8  # ensures the 8-device mesh exists before tracing
    return analyze_jaxpr_collectives(
        overlap_gate._trace_tiny("corrected_async_gn", 4))


def test_jaxpr_agrees_with_hlo_on_tiny_config(stale_reports):
    """The fast gate agrees with the slow HLO tests
    (tests/test_overlap.py) on the tiny config: every refresh collective
    of the stale scan is carry-only (halo ppermutes + KV gathers), and
    the only same-step consumers are the <=2 output/CFG gathers."""
    stale = max(stale_reports,
                key=lambda r: r.n_deferred + r.n_deferred_compute)
    hidden = {**stale.deferred, **stale.deferred_compute}
    assert stale.n_inline <= 2, stale.inline
    assert all(p == "all_gather" for p in stale.inline.values()), (
        stale.inline)
    assert "collective-permute" not in hidden  # jaxpr names, not HLO
    assert "ppermute" in hidden.values(), "halo refreshes missing"
    assert any(p == "all_gather" for p in hidden.values()), (
        "KV refreshes missing")
    assert len(hidden) >= 10
    # warmup/sync body: the analyzer must see its gathers as inline
    # (discrimination — the HLO negative control, full_sync, costs
    # another trace; the warmup scan body proves the same property)
    sync = min(stale_reports,
               key=lambda r: r.n_deferred + r.n_deferred_compute)
    assert sync.n_inline > 0


def test_overlap_gate_checker_clean(stale_reports):
    """The packaged checker itself passes on the shipped tree (it
    re-traces internally; the fixture just guarantees mesh setup)."""
    del stale_reports
    findings = overlap_gate.run(real_ctx())
    assert findings == [], [f.render() for f in findings]
