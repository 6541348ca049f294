"""What bringing one decoded image home costs, form by form, on the chip.

    chiprun -- python scripts/to_host_forms.py

`_decode_to_np` (pipelines.py) copies a ready `bf16[N,1024,1024,3]` to the
host and widens it to float32.  This times, over fresh device arrays, the
copy (`np.asarray`) of the same 3 Mi numbers laid out five ways, the
widening two ways, and the whole tail (copy, widening, `clip(x / 2 + 0.5)`
into a warm buffer) as it was and as it is, and prints one JSON line.  Root
PERF.md section 5 has the readings.
"""

import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

H = W = 1024
FORMS = {"bf16[1,H,W,3]": ((1, H, W, 3), jnp.bfloat16),
         "bf16[1,H,W*3]": ((1, H, W * 3), jnp.bfloat16),
         "bf16[1,3,H,W]": ((1, 3, H, W), jnp.bfloat16),
         "f32[1,H,W,3]": ((1, H, W, 3), jnp.float32),
         "f32[1,H,W*3]": ((1, H, W * 3), jnp.float32)}


def ms(fn, fresh, reps=12):
    """Median ms of fn(x) over fresh, ready x."""
    out = []
    for i in range(reps):
        x = fresh(i)
        t = time.perf_counter()
        fn(x)
        out.append(1e3 * (time.perf_counter() - t))
    return round(statistics.median(out), 3)


def widen_cast(host, out):
    np.copyto(out, host, casting="unsafe")


def widen_shift(host, out):
    # bf16 is float32's upper half: exact, and integer arithmetic
    np.left_shift(host.view(np.uint16), 16, out=out.view(np.uint32),
                  dtype=np.uint32, casting="unsafe")


def tail_was(x, out):
    """PR 27's tail: bf16 [N,H,W,3] home, widened by the cast, three passes."""
    widen_cast(np.asarray(x), out)
    out *= 0.5
    out += 0.5
    return np.clip(out, 0.0, 1.0, out=out)


def tail_is(x, out):
    """Widened on the device, lane-dense: the first pass fills the buffer."""
    np.multiply(np.asarray(x).reshape(out.shape), 0.5, out=out)
    out += 0.5
    return np.clip(out, 0.0, 1.0, out=out)


def main():
    dev = jax.devices()[0]
    line = {"device": dev.device_kind, "copy_ms": {}, "copy_async_ms": {},
            "widen_ms": {}, "tail_ms": {}}
    draws = {}
    for name, (shape, dtype) in FORMS.items():
        draw = draws[name] = jax.jit(
            # bf16's numbers in every form (the barrier keeps the rounding)
            lambda i, s=shape, d=dtype: jax.lax.optimization_barrier(
                jax.random.normal(jax.random.PRNGKey(i), s, jnp.float32
                                  ).astype(jnp.bfloat16)).astype(d))
        fresh = lambda i: jax.block_until_ready(draw(i))  # noqa: E731
        np.asarray(fresh(99))
        line["copy_ms"][name] = ms(np.asarray, fresh)

        def started(i):
            x = draw(i)
            x.copy_to_host_async()  # as the decode is enqueued
            return jax.block_until_ready(x)

        line["copy_async_ms"][name] = ms(np.asarray, started)
    host = np.asarray(jax.random.normal(
        jax.random.PRNGKey(0), (1, H, W, 3), jnp.float32).astype(jnp.bfloat16))
    a, b = (np.empty(host.shape, np.float32) for _ in range(2))
    for name, fn, out in (("copyto", widen_cast, a), ("shift16", widen_shift, b)):
        fn(host, out)
        line["widen_ms"][name] = ms(lambda _: fn(host, out), lambda i: None)
    line["widen_same_bits"] = bool(np.array_equal(a.view(np.uint32),
                                                  b.view(np.uint32)))
    for name, fn, form, out in (("was", tail_was, "bf16[1,H,W,3]", a),
                                ("is", tail_is, "f32[1,H,W*3]", b)):
        fresh = lambda i: jax.block_until_ready(draws[form](i))  # noqa: E731
        fn(fresh(99), out)
        line["tail_ms"][name] = ms(lambda x: fn(x, out), fresh)
    tail_was(draws["bf16[1,H,W,3]"](7), a)
    tail_is(draws["f32[1,H,W*3]"](7), b)
    line["tail_same_bits"] = bool(np.array_equal(a.view(np.uint32),
                                                 b.view(np.uint32)))
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
