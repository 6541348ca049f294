"""A request is its model programs (PR 46): what the request path dispatches
besides them, counted.

`executed` runs a callable under the profiler and reads the trace for one
`run_id` per program execution - what `programs_per_image`
(benchmark/layer_metrics) counts in a traced benchmark run on the chip, so
the next eager op on the request path fails here and not on a benchmark
line."""

import collections
import glob
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distrifuser_tpu import pipelines as P
from distrifuser_tpu.serve.executors import PipelineExecutor


def executed(fn, tmp_path):
    """(fn(), Counter of the XLA modules fn executed): every execution
    carries a `run_id` in the host's trace, its ops name the module."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = jax.block_until_ready(fn())
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    runs = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for event in line.events:
                stats = dict(event.stats)
                if "run_id" in stats:
                    runs[stats["run_id"]] = stats.get(
                        "hlo_module", runs.get(stats["run_id"], "?"))
    return out, collections.Counter(runs.values())


# -- the chunk paths -----------------------------------------------------------

BS = 2
CFG = types.SimpleNamespace(batch_size=BS, latent_height=4, latent_width=4)
SCHEDULER = types.SimpleNamespace(init_noise_sigma=1.0)


def reference_generate(prompts, negs, latents, run_chunk):
    """`_batched_generate` as the explicit slice / pad / concatenate
    composition (what it was for every chunk count before PR 46)."""
    outs = []
    for i, stop, pad in P._pad_chunks(len(prompts), BS):
        cp, cn, cl = prompts[i:stop], negs[i:stop], latents[i:stop]
        if pad:
            cp, cn = cp + [cp[-1]] * pad, cn + [cn[-1]] * pad
            cl = jnp.concatenate([cl, jnp.repeat(cl[-1:], pad, axis=0)])
        outs.append(run_chunk(cp, cn, cl, BS - pad)[:BS - pad])
    return jnp.concatenate(outs, axis=0)


def reference_decode(decode, params, latent, scaling, shift):
    outs = []
    for i, stop, pad in P._pad_chunks(latent.shape[0], BS):
        cl = latent[i:stop]
        if pad:
            cl = jnp.concatenate([cl, jnp.repeat(cl[-1:], pad, axis=0)])
        outs.append(decode(params, cl, scaling, shift)[:BS - pad])
    return jnp.concatenate(outs, axis=0)


@pytest.mark.parametrize("total", [BS, BS + 1, 2 * BS])
def test_chunk_paths_return_what_the_explicit_composition_returns(
        total, tmp_path):
    prompts = [f"p{i}" for i in range(total)]
    negs = [f"n{i}" for i in range(total)]
    latents = jax.random.normal(jax.random.PRNGKey(total), (total, 4, 4, 3),
                                jnp.float32)
    double = jax.jit(lambda x: 2.0 * x)
    descale = jax.jit(lambda p, l, scaling, shift: (l / scaling + shift) * p)
    jax.block_until_ready((double(latents[:BS]),
                           descale(3.0, latents[:BS], 0.5, 0.25)))

    def recording(calls):
        def run_chunk(cp, cn, cl, n_real):
            calls.append((tuple(cp), tuple(cn), n_real))
            return double(cl)
        return run_chunk

    want_calls, got_calls = [], []
    want = reference_generate(prompts, negs, latents, recording(want_calls))
    got, ran = executed(lambda: P._batched_generate(
        CFG, SCHEDULER, prompts, negs, 1, 0, latents, 3,
        recording(got_calls)), tmp_path / "generate")
    assert got_calls == want_calls
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    want_image = reference_decode(descale, 3.0, latents, 0.5, 0.25)
    image, decoded = executed(lambda: P._decode_chunked(
        descale, 3.0, latents, BS, 0.5, 0.25), tmp_path / "decode")
    np.testing.assert_array_equal(np.asarray(image), np.asarray(want_image))
    if total == BS:
        # one whole chunk: the chunk's program and nothing around it
        assert sum(ran.values()) == 1 and sum(decoded.values()) == 1, (
            ran, decoded)
    else:
        assert sum(ran.values()) > len(got_calls), ran


# -- a warmed request, family by family ------------------------------------------


def unet(devices, **kw):
    from test_pipelines import build_sdxl_pipeline

    return build_sdxl_pipeline(devices, 1, **kw)[0]


def unet_with_rewriter(devices):
    import test_rewrite_stage

    return test_rewrite_stage.build(devices, True,
                                    do_classifier_free_guidance=False)


def dit(devices):
    """PixArt at test size WITH a T5 (the caption path the benchmark's cell
    runs; without one the pipeline draws pseudo-embeddings eagerly)."""
    from test_pixart import _tiny_pixart_stack

    return _tiny_pixart_stack(1)[0]


# the model programs of each family, and the one program of glue PR 46 left
# between the encoders and the loop: the latent draw, the text encoders,
# [the conditioning | the DiT's reshape], the loop, the VAE decode
FAMILIES = {
    "unet": (unet, 6),
    "dit": (dit, 5),
    # + rewrite_prefill and rewrite_decode
    "unet_with_rewriter": (unet_with_rewriter, 8),
}


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_warmed_request_executes_its_model_programs_and_no_more(
        family, devices8, tmp_path):
    build, most = FAMILIES[family]
    ex = PipelineExecutor(build(devices8), steps=2)
    ex.warm()
    images, ran = executed(
        lambda: ex(["a red fox on a hill"], [""], 5.0, [2**31 + 12345]),
        tmp_path)
    assert images[0].dtype == np.float32 and images[0].shape[-1] == 3
    assert 0.0 <= images[0].min() and images[0].max() <= 1.0
    assert "jit_loop" in ran and "jit__seeded_latents" in ran, ran
    assert sum(ran.values()) <= most, (
        f"{sum(ran.values())} programs where PR 46 left {most}: an eager op "
        f"on the request path? {dict(ran)}")


def test_a_snapshots_record_joins_the_requests_inside_its_prefill_program(
        devices8, tmp_path):
    """A rewriter whose model enters a snapshot (PR 47: Nemotron's too)
    hands back the record of the WHOLE prompt - the snapshot's positions in
    front of the request's - and dispatches nothing for it: the join is in
    `jit(rewrite_prefill)`, the instruction's program ran in the warm-up,
    and the request's count is where PR 46 left it."""
    pipe = unet_with_rewriter(devices8)
    rewriter = pipe.rewriter
    ex = PipelineExecutor(pipe, steps=2)
    ex.warm()
    assert rewriter._prefix_len == 8 and rewriter._snapshot is not None
    _, ran = executed(
        lambda: ex(["a red fox on a hill"], [""], 0.0, [2**31 + 12345]),
        tmp_path)
    assert (ran["jit_rewrite_prefill"], ran["jit_rewrite_decode"]) == (1, 1)
    assert "jit_rewrite_prefix" not in ran, ran
    assert sum(ran.values()) <= FAMILIES["unet_with_rewriter"][1], dict(ran)
    served = rewriter.served[-1]
    assert served.experts[0].shape[1] == len(served.prompt_ids) == 16
    counters = dict(zip(rewriter.lm.counters,
                        np.asarray(served.counters).tolist()))
    assert counters["tokens_reused"] == 8
