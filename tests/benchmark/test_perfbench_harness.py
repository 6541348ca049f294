"""The yardstick's own arithmetic: trace reduction on a hand-written trace
and a recorded one, the load generator's clocks, the percentile rules."""

import os
import threading
import time
from concurrent.futures import Future

import pytest
from _util import BENCH  # noqa: F401  (puts the repo root on sys.path)

from benchmark.harness import measure as M
from benchmark.harness import trace_reduce as T
from benchmark.harness.loadgen import LoadGen, open_schedule

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# One device, times in ns.  jit_loop runs twice (100..500, 700..1100), a
# `while` container spans each body; jit_decode once (1200..1450).
#   compute   : fusion.1 [110,200) [710,800); flash_attention [220,320) [820,920)
#   collective: all-gather.3 [300,400) [900,1000)  (20 of each 100 under flash)
#   decode    : conv.9 [1200,1450)
HAND = {
    "devices": {0: {
        "ops": T.leaf_ops([
            ("while.1", 100, 400), ("fusion.1", 110, 90),
            ("flash_attention", 220, 100), ("all-gather.3", 300, 100),
            ("while.1", 700, 400), ("fusion.1", 710, 90),
            ("flash_attention", 820, 100), ("all-gather.3", 900, 100),
            ("conv.9", 1200, 250)]),
        "modules": [("jit_loop(17)", 100, 400), ("jit_loop(17)", 700, 400),
                    ("jit_decode(3)", 1200, 250)]}},
    "host": [("bench.submit", 0, 50), ("bench.wait", 50, 1450)],
}


def test_interval_arithmetic():
    assert T.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert T.total(T.union([(1, 3), (2, 4)])) == 3
    assert T.intersection([(1, 4), (6, 9)], [(3, 7)]) == [(3, 4), (6, 7)]
    assert T.gaps([(2, 3), (5, 6)], 0, 8) == [(0, 2), (3, 5), (6, 8)]
    assert T.module_base("jit_loop(17)") == "loop"


def test_containers_are_not_work():
    names = [n for n, _, _ in HAND["devices"][0]["ops"]]
    assert "while.1" not in names and names.count("fusion.1") == 2


def test_busy_idle_and_breakdown_by_hand():
    lo, hi = T.window(HAND)
    assert (lo, hi) == (110, 1450)
    busy = T.busy_summary(HAND)
    # per loop 90 + 180 (220..400), plus 250 of decode = 790 of 1340
    assert busy["busy_s"] == pytest.approx(790e-9)
    assert busy["window_s"] == pytest.approx(1340e-9)
    assert busy["idle_share_worst"] == pytest.approx(1 - 790 / 1340)
    bd = T.breakdown(HAND)
    assert bd["device_ops"][0] == ["conv.9", pytest.approx(250e-9)]
    assert bd["idle_gaps"] == [["bench.wait", pytest.approx(550e-9)]]


def test_readers_by_hand():
    from benchmark.harness import readers as R

    class Fam:
        DENOISE_MODULES = ("loop",)

    class Bench:
        family_module, steps, chips, peaks = Fam, 2, 2, None

    ctx = {"trace": HAND, "bench": Bench}
    assert R.step_ms(ctx) == pytest.approx(400e-6 / 2)
    # outside the loop programs only the decode is busy: 250 ns over 2 images
    assert R.nondenoise_ms(ctx) == pytest.approx(125e-6)
    assert R.kernel_ms_per_step(ctx, ["flash"]) == pytest.approx(100e-6 / 2)
    assert R.kernel_ms_per_step(ctx, ["no_such_kernel"]) is None
    # each all-gather: 100 ns, 20 of them under the flash kernel
    assert R.collective_exposed_share(ctx) == pytest.approx(100 * 160 / 1340)
    assert R.device_idle_share(ctx) == pytest.approx(100 * (1 - 790 / 1340))
    Bench.chips = 1
    assert R.collective_exposed_share(ctx) is None


def test_tail_and_rate_are_of_the_whole_window():
    from benchmark.harness import readers as R

    class Bench:
        traffic = {"tail": "max"}

    recs = [{"index": i, "ok": True, "due": t, "done": t + d} for i, (t, d)
            in enumerate([(0, 5), (5, 9), (20, 5), (30, 5), (35, 6), (41, 4)])]
    ctx = {"records": recs, "bench": Bench}
    assert R.image_tail_s(ctx) == 9
    assert R.images_per_s(ctx) == pytest.approx(6 / 45)
    Bench.traffic = {"tail": "p50"}
    assert R.image_tail_s(ctx) == 5
    # a window that finished nothing has nothing to read
    ctx["records"] = [dict(recs[0], ok=False)]
    assert R.image_tail_s(ctx) is None and R.images_per_s(ctx) is None


def test_image_checks_keep_digests_and_the_latest_image():
    import numpy as np

    from benchmark.harness.images import ImageChecks

    rng = np.random.default_rng(3)
    pool = [rng.random((8, 8, 3), dtype=np.float32) for _ in range(3)]
    checks = ImageChecks((8, 8, 3))
    for i in (0, 1, 2, 3, 5, 4):  # an open loop finishes out of order
        checks.put(i, pool[i % 3].copy())
    checks.close()
    assert checks.bad == [] and checks.last_index == 5
    assert np.array_equal(checks.last, pool[2])
    assert checks.repeats_that_differ(3) == []
    assert checks.distinct_that_agree(3) == []

    checks = ImageChecks((8, 8, 3))
    nan = pool[1].copy()
    nan[2, 2, 2] = np.nan
    altered = pool[0].copy()
    altered[0, 0, 0] += 1e-6
    for i, image in enumerate([pool[0], pool[0], nan, altered,
                               np.full((8, 8, 3), 0.5, np.float32),
                               np.zeros((4, 4, 3), np.float32)]):
        checks.put(i, image)
    checks.close()
    assert [i for i, _ in checks.bad] == [2, 4, 5]
    assert checks.repeats_that_differ(3) == [(3, 0), (4, 1)]
    assert checks.distinct_that_agree(3) == [(0, 1)]


def test_recorded_cpu_trace_loads():
    trace = T.load_xplane(os.path.join(DATA, "cpu_loop.xplane.pb"))
    dev = trace["devices"][0]
    assert [T.module_base(n) for n, _, _ in dev["modules"]] == ["loop"] * 3
    assert sum(n.startswith("dot_general") for n, _, _ in dev["ops"]) == 15
    assert [n for n, _, _ in trace["host"]] == ["bench.wait"]
    busy = T.busy_summary(trace)
    assert 0 < busy["busy_s"] <= busy["window_s"]
    span = trace["host"][0]
    lo, hi = T.window(trace)
    assert span[1] <= lo and hi <= span[1] + span[2]


# -- load generator -------------------------------------------------------------


def later(delay_s):
    fut = Future()
    threading.Timer(delay_s, fut.set_result, args=("ok",)).start()
    return fut


def test_open_loop_due_times_do_not_drift_and_lateness_is_reported():
    arrivals = {"kind": "open", "rate_per_s": 50.0}

    def slow_submit(index):  # every send blocks longer than the interval
        time.sleep(0.03)
        return later(0.01)

    gen = LoadGen(slow_submit, arrivals, 0.4, seed=1)
    t0 = time.perf_counter()
    records = gen.run()
    assert len(records) == 20
    dues = [r["due"] - records[0]["due"] for r in records]
    assert dues == pytest.approx([i * 0.02 for i in range(20)], abs=1e-9)
    assert abs(records[0]["due"] - t0) < 0.05
    # the generator fell behind (30 ms a send, 20 ms apart) and says so
    med, worst = M.lateness_ms(records)
    assert worst > 150 and med > 50
    # a request is timed from when it was due, so the stall shows
    assert max(M.latencies(records)) > 0.15


def test_open_schedule_is_seeded_and_bursty():
    a = {"kind": "open", "rate_per_s": 8.0, "burst": 4, "jitter": 0.5}
    s1, s2 = open_schedule(a, 3.0, 5), open_schedule(a, 3.0, 5)
    assert s1 == s2 and s1 != open_schedule(a, 3.0, 6)
    assert len(s1) == 24 and s1[0] == s1[3] and s1[4] > s1[3]
    assert all(0.5 * k <= t < 0.5 * k + 0.25
               for k, t in enumerate(s1[::4]))


def test_closed_loop_stops_offering_and_lets_the_last_request_finish():
    gen = LoadGen(lambda i: later(0.05), {"kind": "closed", "clients": 2},
                  0.22, seed=0)
    records = gen.run()
    assert 8 <= len(records) <= 12 and all(r["ok"] for r in records)
    assert all(r["sent"] >= r["due"] for r in records)
    # rates come from the requests' own stamps, not completions in a window
    assert M.completed_rate(records) == pytest.approx(2 / 0.05, rel=0.25)


def test_a_closed_loop_of_no_seconds_is_one_request_a_caller():
    """The traced sample: the window's mix once more, numbered on from it."""
    gen = LoadGen(lambda i: later(0.01), {"kind": "closed", "clients": 2},
                  0.0, seed=0, first_index=7)
    assert [r["index"] for r in gen.run()] == [7, 8]


def test_a_refused_request_counts_as_failed():
    def submit(index):
        if index == 1:
            raise RuntimeError("queue full")
        fut = Future()
        if index == 2:
            fut.set_exception(ValueError("boom"))
        else:
            fut.set_result("ok")
        return fut

    records = LoadGen(submit, {"kind": "closed", "clients": 1}, 0.05,
                      seed=0).run()
    bad = [r["index"] for r in records if not r["ok"]]
    assert bad == [1, 2] and len(M.latencies(records)) == len(records) - 2


def test_percentiles_and_tails():
    xs = list(range(1, 101))
    assert M.percentile(xs, 50) == pytest.approx(50.5)
    assert M.tail(xs, "max") == 100 and M.tail(xs, "p95") == pytest.approx(95.05)
    assert M.auto_tail(7) == "max" and M.auto_tail(100) == "p90"
    assert M.auto_tail(200) == "p95" and M.auto_tail(1000) == "p99"
    with pytest.raises(ValueError):
        M.tail(xs, "median")
