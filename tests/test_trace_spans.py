"""The program's own spans (utils/trace.py `span`): every layer boundary of
the request path in the profiler's `.xplane.pb`, the stage clocks on every
`ServeResult`, the per-request entry budget, and the named scopes on the
device side.  One profiler session per server kind serves the span, clock
and `Tracer` assertions alike."""

import glob
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from distrifuser_tpu.serve import ExecKey, InferenceServer, ServeConfig
from distrifuser_tpu.serve.executors import pipeline_executor_factory
from distrifuser_tpu.serve.testing import FakeExecutorFactory
from distrifuser_tpu.utils import trace as trace_mod
from distrifuser_tpu.utils.config import ObservabilityConfig, StepBatchConfig
from distrifuser_tpu.utils.trace import Scope, Tracer, phases, span

from test_observability import FakeClock
from test_pipelines import build_sd_pipeline

STEPS = 2
SERVER_KINDS = {
    "whole": {},
    "staged": {"pipeline_stages": True},
    "step": {"step_batching": StepBatchConfig(enabled=True, slots=2)},
}
STAGE_KEYS = {
    "whole": ("dispatch", "device_wait", "to_host", "post", "rewrite"),
    "staged": ("encode", "denoise", "decode"),
    "step": ("begin", "steps", "finish"),
}
PIPE_PHASES = ["distri.pipe.dispatch", "distri.pipe.wait_device",
               "distri.pipe.to_host", "distri.pipe.post"]
PIPE_ENQUEUES = ["distri.pipe.tokenize", "distri.pipe.latents",
                 "distri.pipe.encode", "distri.pipe.denoise",
                 "distri.pipe.decode"]
# span -> the span that holds it, per request, as the table of
# docs/OBSERVABILITY.md has them
WHOLE_BATCH_NESTING = {
    "distri.serve.get_executor": "distri.serve.batch",
    "distri.serve.handoff": "distri.serve.batch",
    "distri.serve.complete": "distri.serve.batch",
    "distri.exec.run": "distri.serve.handoff",
    **{name: "distri.exec.run" for name in PIPE_PHASES},
    **{name: "distri.pipe.dispatch" for name in PIPE_ENQUEUES},
}


def tiny_factory(devices8):
    def build(key: ExecKey):
        pipe, _ = build_sd_pipeline(
            devices8, 1, height=key.height, width=key.width, batch_size=2,
            do_classifier_free_guidance=key.cfg)
        return pipe

    return pipeline_executor_factory(build)


def tiny_server(devices8, kind, steps=STEPS, **kw):
    config = ServeConfig(
        max_batch_size=1, batch_window_s=0.0, buckets=((128, 128),),
        default_steps=steps, warmup_buckets=((128, 128, steps),),
        **SERVER_KINDS[kind], **kw.pop("config", {}))
    return InferenceServer(tiny_factory(devices8), config, model_id="tiny-sd",
                           scheduler="ddim", mesh_plan="dp1.cfg1.sp1", **kw)


def program_spans(trace_dir):
    """The `distri.` host events of the session's .xplane.pb, by start."""
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    spans, device_ops = [], 0
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for thread, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("distri."):
                    spans.append({"name": e.name, "start": e.start_ns,
                                  "end": e.start_ns + e.duration_ns,
                                  "thread": thread, "stats": dict(e.stats)})
                elif "hlo_op" in dict(e.stats):
                    device_ops += 1
    return sorted(spans, key=lambda s: (s["start"], -s["end"])), device_ops


@pytest.fixture(scope="module", params=list(SERVER_KINDS))
def traced(request, devices8, tmp_path_factory):
    """Two requests, one after the other, through a tiny real pipeline
    behind each kind of server, under one profiler session, an injected
    clock and a `Tracer`."""
    kind = request.param
    trace_dir = str(tmp_path_factory.mktemp(f"trace_{kind}"))
    server = tiny_server(
        devices8, kind, clock=FakeClock(),
        config={"observability": ObservabilityConfig(trace=True)})
    with server:
        jax.profiler.start_trace(trace_dir)
        try:
            results = [server.submit(f"a cat {i}", height=128, width=128,
                                     seed=i).result(timeout=600)
                       for i in range(2)]
        finally:
            jax.profiler.stop_trace()
    spans, device_ops = program_spans(trace_dir)
    return {"kind": kind, "results": results, "spans": spans,
            "device_ops": device_ops,
            "tracer": server.tracer.export()["traceEvents"]}


def of_request(spans, request_id):
    return [s for s in spans if s["stats"].get("request_id") == request_id]


def holder(spans, child):
    """The innermost other span that holds `child` in time (any thread)."""
    holds = [s for s in spans if s is not child
             and s["start"] <= child["start"] and child["end"] <= s["end"]]
    return max(holds, key=lambda s: (s["start"], -s["end"]), default=None)


def test_every_span_of_a_request_is_in_the_device_trace_file(traced):
    """Section 2 of ISSUE 24, per server kind: each span once per request
    (`distri.step.run` / `.wait` once per step), nested as the table says,
    sharing `request_id`, in the file that holds the device ops."""
    spans = traced["spans"]
    assert traced["device_ops"] > 0  # the CPU's XLA thunks: same file
    for result in traced["results"]:
        mine = of_request(spans, result.request_id)
        names = [s["name"] for s in mine]
        count = {n: names.count(n) for n in set(names)}
        if traced["kind"] == "whole":
            want = {"distri.serve.batch", *WHOLE_BATCH_NESTING}
            assert count == dict.fromkeys(want, 1)
            for s in mine:
                if s["name"] != "distri.serve.batch":
                    assert holder(mine, s)["name"] == \
                        WHOLE_BATCH_NESTING[s["name"]], s["name"]
            hit = next(s for s in mine
                       if s["name"] == "distri.serve.get_executor")
            assert hit["stats"]["hit"] == 1  # the warm bucket
            run = next(s for s in mine if s["name"] == "distri.exec.run")
            batch = next(s for s in mine if s["name"] == "distri.serve.batch")
            assert run["stats"]["rows"] == 1 and batch["stats"]["n"] == 1
            assert run["thread"] != batch["thread"]  # the watchdog's worker
        elif traced["kind"] == "staged":
            stages = [f"distri.stage.{k}" for k in STAGE_KEYS["staged"]]
            for name in ["distri.serve.batch", "distri.serve.complete",
                         *stages, *PIPE_PHASES, *PIPE_ENQUEUES]:
                assert count[name] == 1, name
            by = {s["name"]: s for s in mine}
            inside = {"distri.pipe.tokenize": "encode",
                      "distri.pipe.latents": "encode",
                      "distri.pipe.denoise": "denoise",
                      "distri.pipe.wait_device": "decode",
                      "distri.pipe.post": "decode"}
            for child, stage in inside.items():
                st = by[f"distri.stage.{stage}"]
                assert st["start"] <= by[child]["start"] \
                    and by[child]["end"] <= st["end"], child
        else:
            assert count["distri.step.begin"] == 1
            assert count["distri.step.finish"] == 1
            # one request at a time: every step is one solo dispatch
            runs = [s for s in mine if s["name"] == "distri.step.run"]
            assert len(runs) == STEPS == count["distri.step.wait"]
            assert all(s["stats"]["rows"] == 1
                       and s["stats"]["signature"] == "solo" for s in runs)
            finish = next(s for s in mine
                          if s["name"] == "distri.step.finish")
            decode = next(s for s in mine
                          if s["name"] == "distri.pipe.wait_device")
            assert finish["start"] <= decode["start"] \
                and decode["end"] <= finish["end"]


def test_stage_clocks_have_fixed_keys_and_fit_inside_execute(traced):
    kind = traced["kind"]
    for r in traced["results"]:
        assert tuple(r.stage_s) == STAGE_KEYS[kind]
        # no rewriter is resident in these servers: its clock stays at zero
        assert r.stage_s.get("rewrite", 0.0) == 0.0
        assert all(v > 0 for k, v in r.stage_s.items()
                   if k != "rewrite"), r.stage_s
        # step mode runs `begin` before it admits the request
        inside = sum(v for k, v in r.stage_s.items() if k != "begin")
        assert inside <= r.execute_s
        if kind == "step":
            assert r.stage_s["begin"] <= r.queue_wait_s


def test_stage_clocks_are_the_tracers_spans(traced):
    """With `observability.trace` on, the whole-batch executor's spans are
    mirrored into the Tracer under the same names, and the four phases ARE
    the stage clocks; the records it always had keep their names."""
    xs = [e for e in traced["tracer"] if e["ph"] == "X"]
    names = {e["name"] for e in xs}
    assert {"request", "queue_wait", "execute"} <= names
    if traced["kind"] == "staged":
        assert set(STAGE_KEYS["staged"]) <= names
        for r in traced["results"]:
            for key in STAGE_KEYS["staged"]:
                # the Tracer's stage record wraps the thread hand-off too
                assert r.stage_s[key] * 1e6 <= max(
                    e["dur"] for e in xs if e["name"] == key) + 1
        return
    if traced["kind"] != "whole":
        return
    assert "batch" in names and {"distri.exec.run", *PIPE_PHASES,
                                 *PIPE_ENQUEUES} <= names
    phase_of = dict(zip(STAGE_KEYS["whole"], PIPE_PHASES))
    for i, r in enumerate(traced["results"]):
        # tracer-local trace ids, in order of submission: the export holds
        # no process-global request id
        mine = [e for e in xs if e["args"].get("traces") == [i + 1]]
        assert not any("request_id" in e["args"] for e in xs)
        for key, name in phase_of.items():
            (rec,) = [e for e in mine if e["name"] == name]
            assert rec["dur"] == round(r.stage_s[key] * 1e6)
        # execute_s less the clocks is the hand-off: the scheduler's two
        # readings around the watchdog and the worker's first and last
        (run,) = [e for e in mine if e["name"] == "distri.exec.run"]
        assert run["dur"] <= round(r.execute_s * 1e6)


class CountingAnnotation:
    entered = []

    def __init__(self, name, **kwargs):
        self.name = name

    def __enter__(self):
        CountingAnnotation.entered.append(self.name)
        return self

    def __exit__(self, *exc):
        return None

    def set_metadata(self, **kwargs):
        pass


@pytest.mark.parametrize("factory", ["fake", "tiny"])
def test_span_entries_a_request_do_not_grow_with_the_steps(
        devices8, monkeypatch, factory):
    """The budget of utils/trace.py's docstring: a fixed number of entries
    per dispatch, <= 16 on the whole-batch path, whatever the step count."""
    monkeypatch.setattr(trace_mod, "TraceAnnotation", CountingAnnotation)
    clock_reads = []

    def clock():
        clock_reads.append(1)
        return float(len(clock_reads))

    counts = {}
    for steps in (2, 5):
        if factory == "fake":
            server = InferenceServer(
                FakeExecutorFactory(batch_size=1),
                ServeConfig(max_batch_size=1, batch_window_s=0.0,
                            buckets=((128, 128),), default_steps=steps),
                model_id="m", scheduler="ddim", mesh_plan="dp1.cfg1.sp1")
        else:
            server = tiny_server(devices8, "whole", steps=steps)
        with server:
            server.submit("warm", height=128, width=128).result(timeout=600)
            CountingAnnotation.entered = []
            result = server.submit("a cat", height=128, width=128,
                                   seed=1).result(timeout=600)
            counts[steps] = list(CountingAnnotation.entered)
        assert result.retries == 0
    assert sorted(counts[2]) == sorted(counts[5])
    assert len(counts[2]) == (4 if factory == "fake" else 14) <= 16
    if factory == "tiny":
        assert sorted(counts[2]) == sorted(
            ["distri.serve.batch", *WHOLE_BATCH_NESTING])
        # the spans' own clock reads: five phase boundaries, with the
        # executor driven directly under a scope as the server drives it
        ex = tiny_factory(devices8)(ExecKey(
            model_id="t", scheduler="ddim", height=128, width=128, steps=2,
            cfg=True, mesh_plan="dp1.cfg1.sp1"))
        clock_reads.clear()
        with Scope(clock, STAGE_KEYS["whole"], request_id=7) as scope:
            ex(["a cat"], [""], 5.0, [1])
        assert len(clock_reads) == 5 <= 10
        assert all(v >= 1.0 for k, v in scope.stage_s.items()
                   if k != "rewrite") and scope.stage_s["rewrite"] == 0.0


def test_span_primitive_sinks():
    """One entry, three sinks: the Tracer when one is given, the scope's
    stage clock when the stage is one it keeps, nothing but the annotation
    otherwise; phases join per thread and hand over at one clock reading."""
    clk = FakeClock(start=0.0, tick=1.0)
    tr = Tracer(clock=clk)
    with span("distri.t.explicit", tracer=tr, track="lane", answer=42):
        pass
    (rec,) = [e for e in tr.export()["traceEvents"] if e["ph"] == "X"]
    assert rec["name"] == "distri.t.explicit" and rec["dur"] == 1_000_000
    assert rec["args"]["answer"] == 42

    reads = []

    def clock():
        reads.append(1)
        return float(len(reads))

    with Scope(clock, ("a", "b"), request_id=3) as scope:
        with span("distri.t.unkept", stage="zzz"):
            pass
        assert not reads  # no sink wants a time
        with phases("distri.t.a", stage="a") as outer:
            with phases("distri.t.ignored", stage="b") as joined:
                assert joined is outer
                joined.next("distri.t.b", stage="b")
            # the joined block closed nothing: `b` is still open
            assert scope.stage_s == {"a": 1.0, "b": 0.0}
        assert scope.stage_s == {"a": 1.0, "b": 1.0} and len(reads) == 3
    with span("distri.t.after") as s:
        assert "request_id" not in s.args  # the scope is gone


# -- names on the device side ------------------------------------------------


def lowered_text(fn, *args):
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def test_lowered_unet_step_names_its_work(devices8):
    from distrifuser_tpu.models.unet import (
        init_unet_params,
        tiny_config,
        unet_forward,
    )

    cfg = tiny_config(cross_attention_dim=32, sdxl=False)
    params = init_unet_params(jax.random.PRNGKey(0), cfg)
    text = lowered_text(
        lambda p, x, t, e: unet_forward(p, cfg, x, t, e), params,
        jnp.zeros((1, 16, 16, cfg.in_channels)), jnp.zeros(()),
        jnp.zeros((1, 7, 32)))
    for path in ("time_embed/linear", "down_0/conv", "down_0/groupnorm",
                 "mid/layernorm", "mid/attn", "mid/ff/linear", "up_0/conv"):
        assert re.search(rf'loc\("(?:[^"]*/)?{path}/', text), path


def test_lowered_dit_step_names_its_work(devices8):
    from distrifuser_tpu.models.dit import (
        dit_forward,
        init_dit_params,
        tiny_dit_config,
    )

    cfg = tiny_dit_config(depth=2)
    params = init_dit_params(jax.random.PRNGKey(0), cfg)
    side = cfg.sample_size
    text = lowered_text(
        lambda p, x, t, e: dit_forward(p, cfg, x, t, e), params,
        jnp.zeros((1, side, side, cfg.in_channels)), jnp.zeros(()),
        jnp.zeros((1, 7, cfg.caption_dim)))
    for path in ("time_embed/linear", "adaln/linear", "block/layernorm",
                 "block/attn", "block/linear", "block/ff/linear"):
        assert re.search(rf'loc\("(?:[^"]*/)?{path}/', text), path


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps it from describing
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_flash_custom_call_carries_the_attn_scope(topo, monkeypatch):
    """Compiled for the described chip, the Mosaic flash kernel that `sdpa`
    routes L=4096, d=64 to keeps the scope path in its `op_name`: what a
    TPU trace's op metadata is made from."""
    from jax.sharding import SingleDeviceSharding

    from distrifuser_tpu.ops.attention import sdpa

    # the route asks `jax.devices()` for the platform: answer with the
    # described chip, in this test only
    monkeypatch.setattr(jax, "devices", lambda *a, **k: topo.devices)
    x = jax.ShapeDtypeStruct((2, 4096, 640), jnp.bfloat16,
                             sharding=SingleDeviceSharding(topo.devices[0]))

    def level(q, k, v):
        with jax.named_scope("down_1"):
            return sdpa(q, k, v, heads=10)

    text = jax.jit(level).lower(x, x, x).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if "custom-call(" in ln and "flash_attention" in ln]
    assert calls and all(
        re.search(r'op_name="[^"]*/down_1/attn/[^"]*pallas_call', ln)
        for ln in calls), calls


def _entry_instructions(text):
    """(result part, op_name) of every instruction of the ENTRY computation
    of a compiled program's HLO text."""
    lines = text.splitlines()
    start = next(i for i, ln in enumerate(lines) if ln.startswith("ENTRY "))
    for ln in lines[start + 1:]:
        if ln.startswith("}"):
            return
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\(.*?\)|\S+) [\w\-]+\(", ln)
        if m:
            op = re.search(r'op_name="([^"]*)"', ln)
            yield m.group(1), op.group(1) if op else ""


def test_two_row_groupnorm_writes_no_float32_activation(topo, monkeypatch):
    """The guided step's GroupNorm compiled for the described chip (PR 30):
    the UNet's last up block at the cell's size, its three skips and six
    norms at two rows.  Where a reduction takes each row's moments over the
    pixels alone the compiler writes the activation out in float32
    (`f32[128,16,17,640]`, 89 MB, and the moments as broadcasts of that
    size: 12 such results in this cut on PR 29's tree); with every reduction
    run through the batch axis none is left.  And what the norm became is
    found by `groupnorm_ms_per_step`: its arithmetic sits under the
    `groupnorm` scope and nowhere else."""
    from jax.sharding import SingleDeviceSharding

    from distrifuser_tpu.models.unet import (DenseDispatch, init_unet_params,
                                             sdxl_config)

    monkeypatch.setattr(jax, "devices", lambda *a, **k: topo.devices)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    cfg = sdxl_config()
    params = jax.eval_shape(
        lambda k: init_unet_params(k, cfg, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    bp = jax.tree.map(lambda x: sds(x.shape, x.dtype), params["up_blocks"][2])

    def up_2(bp, x, skips, temb):
        d = DenseDispatch()
        with jax.named_scope("up_2"):
            for j, skip in enumerate(skips):
                x = jnp.concatenate([x, skip], axis=-1)
                x = d.resnet(bp["resnets"][j], x, temb, f"resnets.{j}",
                             groups=cfg.norm_num_groups)
        return x

    rows, size, c = 2, 128, cfg.block_out_channels[0]
    text = jax.jit(up_2).lower(
        bp, sds((rows, size, size, 2 * c)),
        [sds((rows, size, size, c))] * 3, sds((rows, 4 * c))
    ).compile().as_text()

    ops = list(_entry_instructions(text))
    assert any("/groupnorm/" in op for _, op in ops), \
        "no instruction carries the groupnorm scope"
    large = [(op, f"f32[{dims}]") for result, op in ops
             for dims in re.findall(r"\bf32\[([\d,]+)\]", result)
             if 4 * math.prod(int(d) for d in dims.split(",")) >= 20e6]
    assert not large, large
    # the norm's own arithmetic (nothing else in a resnet divides, takes a
    # root or sums over pixels) is named by the scope wherever it survived
    # as an instruction of its own
    own = re.compile(r"/(rsqrt|reduce_sum|div|integer_pow|square)$")
    strays = [op for _, op in ops
              if own.search(op) and "/groupnorm/" not in op]
    assert not strays, strays


def _attention_patterns():
    """The pattern lists of the benchmark's attention metrics, as the reader
    joins them (`harness.readers._kernel_seconds_per_step`)."""
    import glob
    import json

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = sorted(glob.glob(os.path.join(
        here, "benchmark", "layer_metrics", "attn_*.json")))
    assert files
    return [re.compile("|".join(json.load(open(f))["params"]["patterns"]),
                       re.I) for f in files]


def _pixart_block_case(sds):
    from distrifuser_tpu.models.dit import dit_block, init_dit_params, pixart_config

    cfg = pixart_config()
    blocks = jax.eval_shape(lambda k: init_dit_params(k, cfg),
                            jax.random.PRNGKey(0))["blocks"]
    bp = jax.tree.map(lambda x: sds(x.shape[1:]), blocks)
    n, c = (cfg.sample_size // cfg.patch_size) ** 2, cfg.hidden_size
    fn = lambda bp, x, c6, kv: dit_block(bp, cfg, x, c6, kv)[0]  # noqa: E731
    return fn, (bp, sds((2, n, c)), sds((6, c)), sds((2, 120, 2 * c))), (
        2, n, cfg.num_heads, c // cfg.num_heads)


def _sdxl_attention_case(l, c, heads):
    def build(sds):
        from distrifuser_tpu.ops.attention import attention

        p = {"to_q": {"kernel": sds((c, c))},
             "to_kv": {"kernel": sds((c, 2 * c))},
             "to_out": {"kernel": sds((c, c)), "bias": sds((c,))}}
        fn = lambda p, x: x + attention(p, x, heads=heads)  # noqa: E731
        return fn, (p, sds((2, l, c))), (2, l, heads, c // heads)
    return build


@pytest.mark.parametrize("build", [
    pytest.param(_pixart_block_case, id="pixart_dit_block_L4096_d72"),
    pytest.param(_sdxl_attention_case(4096, 640, 10), id="sdxl_L4096_d64"),
    pytest.param(_sdxl_attention_case(1024, 1280, 20), id="sdxl_L1024_d64"),
])
def test_seq_minor_flash_stands_between_bitcasts(topo, monkeypatch, build):
    """Compiled for the described chip at the cells' widths, self-attention
    is the `flash_attention_seq_minor` custom call, under a name every
    attention metric of the benchmark matches, and no copy or transpose of a
    [B, L, H, D]-shaped tensor stands around it: q, k, v and o are bitcasts
    of what the projections write and read.  The split of `to_kv`'s fused
    output into K and V is allowed: at most one fusion that slices it."""
    from jax.sharding import SingleDeviceSharding

    monkeypatch.setattr(jax, "devices", lambda *a, **k: topo.devices)
    one = SingleDeviceSharding(topo.devices[0])
    fn, args, (b, l, h, d) = build(
        lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one))
    lines = jax.jit(fn).lower(*args).compile().as_text().splitlines()

    calls = [ln for ln in lines if "custom-call(" in ln
             and 'custom_call_target="tpu_custom_call"' in ln]
    names = [re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", ln).group(1)
             for ln in calls]
    assert len(names) == 1 and names[0].startswith(
        "flash_attention_seq_minor"), names
    for rx in _attention_patterns():
        assert rx.search(names[0]), (rx.pattern, names[0])
    assert re.search(rf"bf16\[{b * h},{d},{l}\]", calls[0])

    heads_shaped = sorted([b, l, h, d])
    layout_ops = []
    for ln in lines:
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\]\S* "
                     r"(copy|transpose)\(", ln)
        if m and sorted(map(int, m.group(1).split(","))) == heads_shaped:
            layout_ops.append(ln.strip()[:120])
    assert not layout_ops, layout_ops
    kv_split = [ln for ln in lines if re.search(
        rf"= \(bf16\[{b},{l},{h * d}\]\S*, bf16\[{b},{l},{h * d}\]\S*\) "
        r"fusion\(", ln)]
    assert len(kv_split) <= 1, kv_split


def test_decode_program_at_published_widths_compiles_for_the_chip(
        topo, monkeypatch):
    """The rewrite stage's two programs - Nemotron-3-Super's published
    widths, one chip's share - compiled for the described v5e.  Decode: every
    expert layer's routed experts are ONE call of the gather mat-vec kernel
    (`ops/moe.py gather_expert_sum`: the experts a token chose here, by id)
    under the `lm.moe.experts` scope, with no grouped matmul, no sort and no
    64-row padding around it, and never the dense form over all the held
    experts; the language model's scopes are on its ops; weights and state
    fit.  Prefill: its thousands of rows stay on the grouped kernel - the
    instruction's 896 x 22 once a server (`rewrite_prefix`), a request's
    128 x 22 entering that snapshot (PR 47), which holds no row of the
    instruction but in the record it puts together."""
    import json

    from jax.sharding import SingleDeviceSharding

    from distrifuser_tpu.models import nemotron_h as lm
    from distrifuser_tpu.pipelines import (
        PromptRewriter,
        RewriteSpec,
        SimpleTokenizer,
    )

    # `local_expert_sum` asks the first device for its platform
    monkeypatch.setattr(jax, "devices", lambda *a, **k: topo.devices)
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "nemotron-3-super-sdxl-rewrite.json")) as f:
        config = json.load(f)
    cfg = lm.nemotron_h_config_from_json(config)
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)

    params = jax.tree.map(
        lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one),
        lm.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    rw = PromptRewriter(cfg, None, RewriteSpec(**config["rewrite"]),
                        [SimpleTokenizer(49408)])
    def ids(t):
        return jax.ShapeDtypeStruct((t,), jnp.int32, sharding=one)

    reused = rw._prefix_len
    assert reused == 896  # whole chunks of the 1000-id instruction
    snapshot = jax.tree.map(
        on_chip, jax.eval_shape(rw._prefix, params, ids(reused)))
    assert snapshot[2].shape == (cfg.pattern.count("E"), reused,
                                 cfg.num_experts_per_tok)
    entering = (params, ids(1024 - reused), snapshot)
    assert jax.eval_shape(rw._prefill, *entering) == jax.eval_shape(
        rw._prefill, params, ids(1024))
    logits, state, counters, _ = jax.tree.map(
        on_chip, jax.eval_shape(rw._prefill, *entering))
    compiled = rw._decode.lower(
        params, logits, state, counters,
        [jax.ShapeDtypeStruct((cfg.vocab_size,), jnp.int32, sharding=one)]
    ).compile()
    text = compiled.as_text()
    n_e = cfg.pattern.count("E")

    def instructions(text):
        """(what stands left of ``metadata=``, op_name) per instruction."""
        for ln in text.splitlines():
            m = re.search(r'metadata=\{[^}]*?op_name="([^"]*)"', ln)
            if " = " in ln:
                yield ln.split("metadata=")[0], m.group(1) if m else ""

    experts = [body for body, scope in instructions(text)
               if "/lm.moe.experts/" in scope]
    assert experts
    kernel = [body for body in experts
              if 'custom_call_target="tpu_custom_call"' in body]
    assert len(kernel) == n_e and all(
        re.match(r"\s*(?:ROOT )?%expert_gather_matvec[\w.\-]* = ", body)
        for body in kernel), kernel
    assert "ragged-dot" not in text
    for body in experts:
        assert not re.search(r" sort\(", body), body
        assert not re.search(r"bf16\[64,1024\]", body), body
    assert not re.search(r"convolution[\w.\-]* \(kernel[^)]*bf16\[64,1024,2688\]",
                         text)  # the dense form over all the held experts
    for scope in ("lm.mamba", "lm.attn", "lm.moe.router", "lm.moe.experts",
                  "lm.moe.shared", "lm.head"):
        assert f"/{scope}/" in text, scope
    mem = compiled.memory_analysis()
    assert 5.4e9 < mem.argument_size_in_bytes < 5.7e9
    assert mem.temp_size_in_bytes < 0.5e9

    # (the instruction's program returns no logits: the last layer's
    # experts - not its router, whose choice is recorded - compile away)
    for program, args, t, layers in (
            (rw._prefix, (params, ids(reused)), reused, n_e - 1),
            (rw._prefill, entering, 1024 - reused, n_e)):
        prefill = program.lower(*args).compile()
        # a request's program holds the weights, the snapshot and one chunk
        assert prefill.memory_analysis().temp_size_in_bytes < (
            0.5e9 if t == reused else 0.1e9)
        prefill = prefill.as_text()
        # (the compiler names them itself: op_name "ragged-dot-none", no
        # scope)
        rows = t * cfg.num_experts_per_tok
        grouped = [body for body, _ in instructions(prefill)
                   if re.match(r"\s*%ragged-dot-none[\w.\-]* = "
                               rf"f32\[{rows},(2688|1024)\]\S* "
                               r"custom-call\(", body)]
        assert len(grouped) == 2 * layers, len(grouped)
        assert "expert_gather_matvec" not in prefill
    # the scan of a request is one chunk: no row count of the instruction
    # survives in its program but the record's
    assert not re.search(rf"\[(?:\d+,)*{reused}(?:,\d+)*\]", re.sub(
        rf"s32\[{n_e},{reused},{cfg.num_experts_per_tok}\]", "", prefill))


@pytest.fixture(scope="module")
def byte_programs(topo):
    """The byte-level rewrite stage at EvaByte's published widths, 16
    layers: the rewriter, the shapes its programs take on the described v5e,
    and the decode program compiled for it."""
    import json
    import types

    from jax.sharding import SingleDeviceSharding

    from distrifuser_tpu.models import evabyte as lm
    from distrifuser_tpu.pipelines import (
        PromptRewriter,
        RewriteSpec,
        SimpleTokenizer,
    )

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "evabyte-sdxl-rewrite.json")) as f:
        config = json.load(f)
    cfg = lm.evabyte_config_from_json(config)
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)

    params = jax.tree.map(
        lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one),
        lm.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    spec = RewriteSpec(**config["rewrite"])
    rw = PromptRewriter(cfg, None, spec, [SimpleTokenizer(49408)] * 2)
    t = spec.instruction_tokens + spec.user_tokens
    ids = jax.ShapeDtypeStruct((t,), jnp.int32, sharding=one)
    logits, state, counters, _ = jax.tree.map(
        on_chip, jax.eval_shape(rw._prefill, params, ids))
    # `step_attention` asks the first device for its platform: answer with
    # the described chip, while the decode program compiles
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "devices", lambda *a, **k: topo.devices)
        decode = rw._decode.lower(params, logits, state, counters,
                                  []).compile()
    return types.SimpleNamespace(
        cfg=cfg, spec=spec, rw=rw, t=t, one=one, on_chip=on_chip,
        params=params, ids=ids, state=state, counters=counters, decode=decode)


def test_byte_level_rewrite_programs_compile_for_the_chip(byte_programs):
    """The rewrite stage's two programs at EvaByte's published widths, 16
    layers, compiled for the described v5e.  Decode: the donated state - 16
    rings and summary tables, 608 MB - is carried in place (aliased to the
    output, no second copy among the temporaries), the language model's
    scopes are on its ops, weights and state fit.  Prefill: 3840 positions
    by query block, no array of all positions squared."""
    bp = byte_programs
    rw, spec, t, one, on_chip = bp.rw, bp.spec, bp.t, bp.one, bp.on_chip
    params, ids, state, counters = bp.params, bp.ids, bp.state, bp.counters
    state_bytes = 16 * 2 * 2 * 4096 * (2048 + (t + spec.new_tokens) // 16)
    assert state_bytes == 608_174_080

    compiled = bp.decode
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == state_bytes
    assert mem.temp_size_in_bytes < 0.1e9
    assert 7.0e9 < mem.argument_size_in_bytes < 7.2e9  # weights + state
    text = compiled.as_text()
    for scope in ("lm.eva.proj", "lm.eva.pool", "lm.eva.attn", "lm.mlp",
                  "lm.head"):
        assert f"/{scope}/" in text, scope

    prefill = rw._prefill.lower(params, ids).compile()
    shapes = {tuple(int(n) for n in dims.split(","))
              for dims in re.findall(r"\[((?:\d+,)+\d+)\]", prefill.as_text())}
    assert shapes and not [s for s in shapes if s.count(t) >= 2]
    assert prefill.memory_analysis().temp_size_in_bytes < 1.0e9

    n = spec.instruction_tokens
    assert rw._prefix_len == n == 3712
    snapshot = jax.tree.map(on_chip, jax.eval_shape(
        rw._prefix, params, jax.ShapeDtypeStruct((n,), jnp.int32,
                                                 sharding=one)))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), snapshot[:2]) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), (state, counters))
    entering = rw._prefill.lower(
        params, jax.ShapeDtypeStruct((t - n,), jnp.int32, sharding=one),
        snapshot).compile()
    mem = entering.memory_analysis()
    assert mem.alias_size_in_bytes == 0
    assert mem.output_size_in_bytes - state_bytes < 1e6
    assert mem.temp_size_in_bytes < 0.5e9
    flops = [c.cost_analysis()["flops"] for c in (prefill, entering)]
    assert flops[1] < flops[0] / 20, flops


def _rewrite_programs(topo, lm, from_json, config_file):
    """The rewrite stage's three programs of one language model at its
    published widths, one chip's share, compiled for the described v5e, with
    the shapes they were compiled from."""
    import json
    import types

    from jax.sharding import SingleDeviceSharding

    from distrifuser_tpu.pipelines import (
        PromptRewriter,
        RewriteSpec,
        SimpleTokenizer,
    )

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs", config_file)) as f:
        config = json.load(f)
    cfg = from_json(config)
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)

    def ids(n):
        return jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one)

    params = jax.tree.map(
        lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one),
        lm.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    spec = RewriteSpec(**config["rewrite"])
    rw = PromptRewriter(cfg, None, spec, [SimpleTokenizer(49408)] * 2)
    t, n = spec.instruction_tokens + spec.user_tokens, rw._prefix_len
    # `local_expert_sum` and `cache_attention` ask the first device for its
    # platform: answer with the described chip, while these compile
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "devices", lambda *a, **k: topo.devices)
        prefix = rw._prefix.lower(params, ids(n)).compile()
        snapshot = jax.tree.map(on_chip, jax.eval_shape(rw._prefix, params,
                                                        ids(n)))
        entering = rw._prefill.lower(params, ids(t - n), snapshot).compile()
        logits, state, counters, _ = jax.tree.map(on_chip, jax.eval_shape(
            rw._prefill, params, ids(t - n), snapshot))
        decode = rw._decode.lower(
            params, logits, state, counters,
            [jax.ShapeDtypeStruct((cfg.vocab_size,), jnp.int32,
                                  sharding=one)] * 2).compile()
    return types.SimpleNamespace(
        cfg=cfg, spec=spec, t=t, n=n, prefix=prefix, entering=entering,
        decode=decode, snapshot=snapshot, state=state, counters=counters)


@pytest.fixture(scope="module")
def latent_programs(topo):
    """Kanana-2-30B-A3B: 24 layers, 16 of 128 experts."""
    from distrifuser_tpu.models import deepseek_v3 as lm

    return _rewrite_programs(topo, lm, lm.deepseek_v3_config_from_json,
                             "kanana-2-30b-sdxl-rewrite.json")


@pytest.fixture(scope="module")
def linear_programs(topo):
    """Kimi-Linear-48B-A3B: 12 layers, 32 of 256 experts."""
    from distrifuser_tpu.models import kimi_linear as lm

    return _rewrite_programs(topo, lm, lm.kimi_linear_config_from_json,
                             "kimi-linear-48b-sdxl-rewrite.json")


def test_latent_attention_rewrite_programs_compile_for_the_chip(
        latent_programs):
    """Prefix: the instruction's 8064 tokens by the materialised form in
    query blocks - no array with the prompt's length twice among its dims,
    the routed experts on the grouped matmul.  The request's prefill: 128
    ids ENTERING the snapshot (read, not aliased) at a twentieth of the
    whole prompt's FLOPs.  Decode: the donated state - 24 latent caches of
    576 numbers a position and the record of the experts chosen - carried in
    place, every expert layer's routed experts ONE call of the gather
    mat-vec kernel in its gated form, the language model's scopes on its
    ops, weights and state fit."""
    lp = latent_programs
    cfg, t, n = lp.cfg, lp.t, lp.n
    assert (t, n, t - n) == (8192, 8064, 128)
    max_len, n_e = t + lp.spec.new_tokens, cfg.n_expert_layers
    cache_bytes = 24 * max_len * 576 * 2
    state_bytes = cache_bytes + n_e * max_len * cfg.num_experts_per_tok * 4
    assert cache_bytes == 240_648_192

    text = lp.prefix.as_text()
    shapes = {tuple(int(x) for x in dims.split(","))
              for dims in re.findall(r"\[((?:\d+,)+\d+)\]", text)}
    assert (32, 32, n) in shapes  # one query block's logits
    assert not [s for s in shapes if s.count(n) >= 2]
    assert "ragged-dot" in text and "expert_gather_matvec" not in text
    assert lp.prefix.memory_analysis().temp_size_in_bytes < 2.0e9

    mem = lp.entering.memory_analysis()
    assert mem.alias_size_in_bytes == 0
    assert mem.temp_size_in_bytes < 0.6e9
    flops = [c.cost_analysis()["flops"] for c in (lp.prefix, lp.entering)]
    assert flops[1] < flops[0] / 10, flops
    # the 128 entering rows keep the XLA form, 32 queries at a time
    assert "latent_cache_attention" not in lp.entering.as_text()

    assert jax.tree.map(lambda a: (a.shape, a.dtype),
                        (lp.state, lp.counters)) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), lp.snapshot[:2])
    mem = lp.decode.memory_analysis()
    assert 0 <= mem.alias_size_in_bytes - state_bytes < 1e6
    assert mem.temp_size_in_bytes < 0.3e9
    assert 5.5e9 < mem.argument_size_in_bytes < 5.8e9  # weights + state
    text = lp.decode.as_text()
    kernels = re.findall(r"%(expert_gather_matvec[\w.\-]*) = ", text)
    assert len(kernels) == n_e and "ragged-dot" not in text
    for scope in ("lm.mla.proj", "lm.mla.attn", "lm.moe.router",
                  "lm.moe.experts", "lm.moe.shared", "lm.mlp", "lm.head"):
        assert f"/{scope}/" in text, scope


def _loop_body(text):
    """The largest computation of a compiled program's HLO text that is not
    its entry: the decode loop's body."""
    comps = re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", text)
    return max((c for c in comps if not c.startswith("ENTRY ")), key=len)


def test_decode_step_reads_each_latent_cache_through_one_kernel(
        latent_programs):
    """The compiled decode step: one `latent_cache_attention` custom call a
    layer, under the `lm.mla.attn` scope (what `mla_attn_ms_per_token` reads
    it by); the cache arrays reach it as they are carried - no copy,
    transpose or slice with a cache's shape as its result in the loop's body
    (a copy the compiler schedules ASYNCHRONOUSLY, `copy-start`, to keep a
    layer's cache in VMEM is its own affair: the parent's program has those
    too) - and no float32 array of a whole cache's logits is left."""
    cfg = latent_programs.cfg
    max_len = latent_programs.t + latent_programs.spec.new_tokens
    h, layers = cfg.num_attention_heads, cfg.num_hidden_layers
    body = _loop_body(latent_programs.decode.as_text())
    calls = [ln for ln in body.splitlines()
             if re.match(r"\s*%latent_cache_attention[\w.\-]* = ", ln)
             and "custom-call(" in ln]
    assert len(calls) == layers
    assert all('custom_call_target="tpu_custom_call"' in ln and re.search(
        r'op_name="[^"]*/lm\.mla\.attn/[^"]*pallas_call', ln)
        for ln in calls)
    def cache_shaped(dims):
        """[max_len, 512 | 64], or the same rows as blocks of a leading
        axis."""
        return (len(dims) >= 2 and math.prod(dims[:-1]) == max_len
                and dims[-1] in (cfg.kv_lora_rank, cfg.qk_rope_head_dim))

    moved = []
    for ln in body.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]+)\]\S* "
                     r"([\w\-]+)\(", ln)
        if m and cache_shaped([int(d) for d in m.group(2).split(",")]) and (
                m.group(3) in ("copy", "transpose", "slice", "dynamic-slice")
                or re.match(r"(copy|transpose|slice)", m.group(1))
                and m.group(3) == "fusion"):
            moved.append(ln.strip()[:160])
    assert not moved, moved
    logits = [ln.strip()[:160] for ln in body.splitlines() if re.search(
        rf"= f32\[(?:{h},1,{max_len}|{max_len},1,{h}|{h},{max_len}|"
        rf"{max_len},{h})\]", ln)]
    assert not logits, logits


def test_decode_step_reads_each_ring_through_one_kernel(byte_programs):
    """The compiled byte-level decode step: one `eva_state_attention` custom
    call a layer, under the `lm.eva.attn` scope (what `eva_attn_ms_per_byte`
    reads it by); ring and summary table reach it as the loop carries them,
    in HBM - no copy, transpose or slice with a ring's or a table's shape
    in the loop's body, and none the compiler schedules ASYNCHRONOUSLY
    either (`copy-start` / `slice-start`: the program before the kernel
    moved one layer's rings and tables into VMEM for the row's write and
    back, 67 MB a step; `streamed_decode_attention` holds its operands to
    the HBM) - and no float32 array of a whole ring's logits is left."""
    cfg = byte_programs.cfg
    h, d, window = cfg.num_attention_heads, cfg.head_dim, cfg.window_size
    table = (byte_programs.t + byte_programs.spec.new_tokens) // cfg.chunk_size
    body = _loop_body(byte_programs.decode.as_text())
    calls = [ln for ln in body.splitlines()
             if re.match(r"\s*%eva_state_attention[\w.\-]* = ", ln)
             and "custom-call(" in ln]
    assert len(calls) == cfg.num_hidden_layers == 16
    assert all('custom_call_target="tpu_custom_call"' in ln and re.search(
        r'op_name="[^"]*/lm\.eva\.attn/[^"]*pallas_call', ln)
        for ln in calls)
    moved = []
    for ln in body.splitlines():
        # (an asynchronous copy's result is a tuple: its first array; the
        # opcode stands in front of the first operand)
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = \(*\w+\[([\d,]+)\]", ln)
        op = re.search(r" ([\w\-]+)\(%", ln)
        if not m or not op:
            continue
        dims = [int(x) for x in m.group(2).split(",")]
        state_shaped = (len(dims) >= 2 and dims[-1] == d and math.prod(
            dims[:-1]) in (window * h, table * h))
        if state_shaped and (
                op.group(1) in ("copy", "transpose", "slice", "dynamic-slice",
                                "copy-start", "slice-start")
                or re.match(r"(copy|transpose|slice)", m.group(1))
                and op.group(1) == "fusion"):
            moved.append(ln.strip()[:160])
    assert not moved, moved
    logits = [ln.strip()[:160] for ln in body.splitlines() if re.search(
        rf"= f32\[(?:{h},1,{window}|{window},1,{h}|{h},{window}|"
        rf"{window},{h})\]", ln)]
    assert not logits, logits


def test_linear_attention_rewrite_programs_compile_for_the_chip(
        linear_programs):
    """The rewrite stage's three programs at Kimi-Linear-48B-A3B's published
    widths, one chip's share (12 layers, 32 of 256 experts), compiled for the
    described v5e.  Prefix: the instruction's 8064 tokens - the chunked KDA
    form `SPAN_CHUNKS` chunks at a time, so that its temporaries fit beside
    13.3 GB of weights, the full layers by query block.  The request's
    prefill: 128 ids ENTERING the snapshot (read, not aliased), whose state
    is of two kinds side by side.  Decode: the donated state carried in
    place, the full layers' attention the single-pass kernel, every expert
    layer's routed experts one gather mat-vec call, every scope on its ops."""
    lp = linear_programs
    cfg, spec, t, n = lp.cfg, lp.spec, lp.t, lp.n
    prefix, entering, decode = lp.prefix, lp.entering, lp.decode
    snapshot, state, counters = lp.snapshot, lp.state, lp.counters
    assert (t, n, t - n) == (8192, 8064, 128)
    max_len = t + spec.new_tokens
    kinds = [sorted(layer) for layer in state["layers"]]
    assert kinds == [["conv", "s"]] * 3 + [["c", "k_pe"]] + kinds[4:]
    kda_bytes = 9 * (32 * 128 * 128 * 4 + 3 * 12288 * 2)
    cache_bytes = 3 * max_len * 576 * 2
    state_bytes = (kda_bytes + cache_bytes
                   + cfg.n_expert_layers * max_len * 8 * 4)
    assert (kda_bytes, cache_bytes) == (19_537_920, 30_081_024)
    assert prefix.memory_analysis().temp_size_in_bytes < 1.6e9
    text = prefix.as_text()
    assert "ragged-dot" in text and "expert_gather_matvec" not in text
    assert "triangular_solve" in text  # one forward substitution a chunk

    mem = entering.memory_analysis()
    assert mem.alias_size_in_bytes == 0
    assert mem.temp_size_in_bytes < 0.3e9
    flops = [c.cost_analysis()["flops"] for c in (prefix, entering)]
    assert flops[1] < flops[0] / 10, flops
    assert "latent_cache_attention" not in entering.as_text()

    assert jax.tree.map(lambda a: (a.shape, a.dtype), (state, counters)) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), snapshot[:2])
    mem = decode.memory_analysis()
    assert 0 <= mem.alias_size_in_bytes - state_bytes < 1e6
    assert mem.temp_size_in_bytes < 0.3e9
    assert 6.35e9 < mem.argument_size_in_bytes < 6.5e9  # weights + state
    text = decode.as_text()
    assert len(re.findall(r"%(expert_gather_matvec[\w.\-]*) = ", text)) \
        == cfg.n_expert_layers == 11
    assert len([ln for ln in text.splitlines() if re.match(
        r"\s*%latent_cache_attention[\w.\-]* = ", ln)
        and "custom-call(" in ln]) == 3
    assert "ragged-dot" not in text
    for scope in ("lm.kda.proj", "lm.kda.conv", "lm.kda.gate", "lm.kda.recur",
                  "lm.kda.norm", "lm.mla.proj", "lm.mla.attn",
                  "lm.moe.router", "lm.moe.experts", "lm.moe.shared",
                  "lm.mlp", "lm.head"):
        assert f"/{scope}/" in text, scope


@pytest.mark.parametrize("programs, latent_layers", [
    ("linear_programs", 3), ("latent_programs", 24)])
def test_decode_step_writes_each_cache_row_in_hbm(request, programs,
                                                  latent_layers):
    """The compiled decode step leaves every latent cache where the loop
    carries it: no asynchronous copy (`copy-start` / `slice-start`) moves a
    cache-shaped array between memory spaces in the loop's body, and every
    row's `dynamic-update-slice` - one into `c`, one into `k_pe` a layer -
    lands in the HBM.  (Before `streamed_attention` held its two cache
    operands there, the compiler moved whole caches into VMEM for the row's
    write and back: 31 MB a token in Kimi's program, 53 in Kanana's.)"""
    from distrifuser_tpu.utils.overlap import cache_staging

    lp = request.getfixturevalue(programs)
    max_len = lp.t + lp.spec.new_tokens
    caches = [a for a in jax.tree.leaves(lp.state)
              if a.ndim == 2 and a.shape[0] == max_len]
    assert len(caches) == 2 * latent_layers
    staging = cache_staging(lp.decode.as_text(), max_len)
    assert staging == {"staged_bytes": 0, "staged_copies": 0,
                       "writes": 2 * latent_layers, "writes_outside_hbm": 0}


@pytest.fixture(scope="module")
def block_programs(topo):
    """SDAR-30B-A3B-Chat: 24 layers, 16 of 128 experts, blocks of 4."""
    from distrifuser_tpu.models import sdar as lm

    return _rewrite_programs(topo, lm, lm.sdar_config_from_json,
                             "sdar-30b-a3b-sdxl-rewrite.json")


def test_block_diffusion_rewrite_programs_compile_for_the_chip(
        block_programs):
    """Prefix: the instruction's 8064 tokens under the block rule, the
    grouped-query attention by query blocks of 32 - no array with the
    prompt's length twice among its dims -, the experts on the grouped
    matmul.  The request's prefill: 128 ids ENTERING the snapshot (read, not
    aliased) at a tenth of the whole prompt's FLOPs.  Decode: the donated
    state - 24 KV caches [4, 8704, 128] twice and the record of the experts
    chosen - carried in place; the stack traced THREE times - a denoise
    pass alone (four rows, the head; an inner loop), the sweep a commit pass
    shares with the next block's first denoise pass (eight rows, the head
    on the last four) and the last block's commit pass alone (four rows, no
    head), the two a conditional's branches -, so every layer's experts are
    four calls of the gather kernel, each over FOUR rows (eight rows in one
    call would be 64 assignments: the grouped matmul's); the language
    model's scopes on its ops; weights and state fit; every sweep's
    attention is the single-pass kernel, once a layer in each trace, and no
    sweep stages any of the caches through VMEM: every row is written where
    the loop carries its cache.  The prompt and the entering suffix keep the
    XLA form.
    """
    lp = block_programs
    cfg, t, n = lp.cfg, lp.t, lp.n
    assert (t, n, t - n) == (8192, 8064, 128)
    max_len, layers = t + lp.spec.new_tokens, cfg.num_hidden_layers
    cache_bytes = layers * 2 * 4 * max_len * 128 * 2
    state_bytes = cache_bytes + layers * max_len * 8 * 4
    assert cache_bytes == 427_819_008

    text = lp.prefix.as_text()
    shapes = {tuple(int(x) for x in dims.split(","))
              for dims in re.findall(r"\[((?:\d+,)+\d+)\]", text)}
    assert (4, 8, 32, n) in shapes  # one query block's logits
    assert not [s for s in shapes if s.count(n) >= 2]
    assert "ragged-dot" in text and "expert_gather_matvec" not in text
    assert lp.prefix.memory_analysis().temp_size_in_bytes < 2.0e9

    mem = lp.entering.memory_analysis()
    assert mem.alias_size_in_bytes == 0
    assert mem.temp_size_in_bytes < 0.3e9
    flops = [c.cost_analysis()["flops"] for c in (lp.prefix, lp.entering)]
    assert flops[1] < flops[0] / 10, flops

    assert jax.tree.map(lambda a: (a.shape, a.dtype),
                        (lp.state, lp.counters)) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), lp.snapshot[:2])
    mem = lp.decode.memory_analysis()
    assert 0 <= mem.alias_size_in_bytes - state_bytes < 1e6
    assert mem.temp_size_in_bytes < 0.3e9
    assert 5.1e9 < mem.argument_size_in_bytes < 5.2e9  # weights + state
    text = lp.decode.as_text()
    kernel = r"%expert_gather_matvec[\w.\-]* = "
    assert len(re.findall(kernel, text)) == 4 * layers == len(re.findall(
        kernel + r"\(f32\[4,2048\]", text))  # four rows a call
    assert "ragged-dot" not in text
    comps = {m.group(1): c for c in re.split(
        r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", text)
        if (m := re.match(r"(?:ENTRY )?%([\w.\-]+) \(", c))}
    # the traces of the stack by the kernel calls they hold: alone a layer's
    # one, shared its two; the head's matmul [4, 2048] x [2048, 18992] in
    # the denoise pass's and the shared sweep's
    traces = sorted(
        (len(re.findall(kernel, c)) // layers, bool(re.search(
            r"= f32\[4,18992\]\S* (?:fusion|convolution|dot)\(", c)), name)
        for name, c in comps.items() if re.search(kernel, c))
    assert [tr[:2] for tr in traces] == [(1, False), (1, True), (2, True)]
    (_, _, alone), (_, _, denoise), (_, _, shared) = traces
    assert f"body=%{denoise}" in text
    branches = re.search(r"conditional\(.*branch_computations=\{([^}]*)\}",
                         text).group(1).replace("%", "").split(", ")
    assert sorted(branches) == sorted([alone, shared])
    # what one trip of each moves between the HBM and VMEM of the caches,
    # whole (`cache_staging` reads loop bodies: each is handed to it as one)
    from distrifuser_tpu.utils.overlap import cache_staging

    staged = {name: cache_staging(
        f"%w = () while(), body=%{name}\n{comps[name]}\n",
        shapes=[(4, max_len, 128)]) for name in (alone, denoise, shared)}
    # every row written in place: `streamed_gqa_attention` holds its two
    # cache operands to the HBM.  (With the sweeps' attention XLA's einsums
    # a block's sweeps staged 3 x 98.0 + 8.9 = 303 MB of whole caches
    # through VMEM round the rows' writes - PR 42's program, compiled here
    # the same way; PR 41's five passes 410 MB; other schedules of the same
    # mathematics anything from 0 to 2.2 GB.)
    assert all(s == {"staged_bytes": 0, "staged_copies": 0,
                     "writes": 2 * layers, "writes_outside_hbm": 0}
               for s in staged.values()), staged
    # each trace of the stack: the single-pass attention kernel once a
    # layer, under the `lm.attn` scope (what `sdar_attn_ms_per_token` reads
    # it by), and no float32 array of a whole cache's logits left
    for name in (alone, denoise, shared):
        calls = [ln for ln in comps[name].splitlines()
                 if re.match(r"\s*%gqa_cache_attention[\w.\-]* = ", ln)
                 and "custom-call(" in ln]
        assert len(calls) == layers, (name, len(calls))
        assert all('custom_call_target="tpu_custom_call"' in ln and re.search(
            r'op_name="[^"]*/lm\.attn/[^"]*pallas_call', ln) for ln in calls)
        assert not re.search(rf"= f32\[4,8,[48],{max_len}\]", comps[name])
    assert "gqa_cache_attention" not in lp.entering.as_text()
    assert "gqa_cache_attention" not in lp.prefix.as_text()
    for scope in ("lm.attn.proj", "lm.attn", "lm.moe.router",
                  "lm.moe.experts", "lm.head", "lm.sdar.unmask"):
        assert f"/{scope}/" in text, scope


@pytest.fixture(scope="module")
def conv_programs(topo):
    """LFM2-24B-A2B: 20 layers (15 conv, 5 attention), 16 of 64 experts."""
    from distrifuser_tpu.models import lfm2 as lm

    return _rewrite_programs(topo, lm, lm.lfm2_config_from_json,
                             "lfm2-24b-a2b-sdxl-rewrite.json")


def test_convolution_attention_rewrite_programs_compile_for_the_chip(
        conv_programs):
    """Prefix: the instruction's 8064 tokens, the convolutions from zero
    tails, the five attention layers by query blocks of 32 against rows of
    128 - no array with the prompt's length twice among its dims -, the
    experts on the grouped matmul.  The request's prefill: 128 ids ENTERING
    the snapshot (read, not aliased).  Decode: the donated state - five KV
    caches [4, 8704, 128] twice (two KV heads of 64 a row: nothing padded),
    fifteen tails [2, 2048] and the record of the experts chosen - carried
    in place; one gather kernel an expert layer over one row; every step's
    attention the single-pass kernel, once an attention layer, under the
    `lm.attn` scope, and no cache staged through VMEM; the head a
    contraction over the embedding's second axis with no transposed copy of
    it; the language model's scopes on its ops; weights and state fit."""
    lp = conv_programs
    cfg, t, n = lp.cfg, lp.t, lp.n
    assert (t, n, t - n) == (8192, 8064, 128)
    max_len = t + lp.spec.new_tokens
    convs, attns = (cfg.kinds.count(k) for k in ("conv", "full_attention"))
    assert (convs, attns, cfg.n_expert_layers, cfg.kv_pack) == (15, 5, 18, 2)
    cache_bytes = attns * 2 * 4 * max_len * 128 * 2
    state_bytes = (cache_bytes + convs * 2 * 2048 * 2
                   + cfg.n_expert_layers * max_len * 4 * 4)
    assert cache_bytes == 89_128_960

    text = lp.prefix.as_text()
    shapes = {tuple(int(x) for x in dims.split(","))
              for dims in re.findall(r"\[((?:\d+,)+\d+)\]", text)}
    assert (4, 8, 32, n) in shapes  # one query block's logits
    assert not [s for s in shapes if s.count(n) >= 2]
    assert "ragged-dot" in text and "expert_gather_matvec" not in text
    assert lp.prefix.memory_analysis().temp_size_in_bytes < 2.0e9

    mem = lp.entering.memory_analysis()
    assert mem.alias_size_in_bytes == 0
    assert mem.temp_size_in_bytes < 0.3e9

    assert jax.tree.map(lambda a: (a.shape, a.dtype),
                        (lp.state, lp.counters)) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), lp.snapshot[:2])
    mem = lp.decode.memory_analysis()
    assert 0 <= mem.alias_size_in_bytes - state_bytes < 1e6
    assert mem.temp_size_in_bytes < 0.3e9
    assert 6.4e9 < mem.argument_size_in_bytes < 6.6e9  # weights + state
    text = lp.decode.as_text()
    kernel = r"%expert_gather_matvec[\w.\-]* = "
    assert len(re.findall(kernel, text)) == cfg.n_expert_layers == len(
        re.findall(kernel + r"\(f32\[1,2048\]", text))  # one row a call
    assert "ragged-dot" not in text
    calls = [ln for ln in text.splitlines()
             if re.match(r"\s*%gqa_cache_attention[\w.\-]* = ", ln)
             and "custom-call(" in ln]
    assert len(calls) == attns
    assert all('custom_call_target="tpu_custom_call"' in ln and re.search(
        r'op_name="[^"]*/lm\.attn/[^"]*pallas_call', ln) for ln in calls)
    assert "gqa_cache_attention" not in lp.entering.as_text()
    assert "gqa_cache_attention" not in lp.prefix.as_text()
    from distrifuser_tpu.utils.overlap import cache_staging

    assert cache_staging(text, shapes=[(4, max_len, 128)]) == {
        "staged_bytes": 0, "staged_copies": 0, "writes": 2 * attns,
        "writes_outside_hbm": 0}
    # the tied head: no [2048, 16384] copy of the embedding anywhere
    assert not re.search(r"bf16\[2048,16384\]", text)
    for scope in ("lm.conv.proj", "lm.conv", "lm.attn.proj", "lm.attn",
                  "lm.mlp", "lm.moe.router", "lm.moe.experts", "lm.head"):
        assert f"/{scope}/" in text, scope
