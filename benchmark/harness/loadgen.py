"""The one traffic generator: reads a traffic file's `arrivals`, offers that
load through `submit`, and records due / sent / done for every request.

closed   `clients` callers, each sending its next request when the previous
         one is back; a request is due when its caller became free.
open     a schedule fixed before the window opens from the seed: evenly
         spaced at `rate_per_s` (bursts of `burst` share a due time), each
         shifted by a seeded jitter of up to `jitter` of the interval.
         Requests are timed from when they were DUE, sent or not, so a stall
         shows in every request behind it; how late the generator ran is
         reported beside them.

Both stop offering new requests once `seconds` have passed and let what is in
flight finish; a closed-loop caller always sends its first request and an
open schedule its first burst, so a window of 0 seconds is one of either.  One thread per closed-loop
caller, one for the open schedule; open-loop completions are stamped by the
thread that completes the future.
"""

import threading
import time

import jax
import numpy as np


def open_schedule(arrivals: dict, seconds: float, seed: int):
    """Due offsets (seconds from the window's start) of an open loop."""
    rate, burst = float(arrivals["rate_per_s"]), int(arrivals.get("burst", 1))
    interval = burst / rate
    n_slots = max(1, int(seconds / interval))  # the first is always sent
    rng = np.random.default_rng([int(seed), 0x0A771])
    jitter = float(arrivals.get("jitter", 0.0)) * interval
    slots = np.arange(n_slots) * interval + rng.uniform(0, jitter, n_slots) \
        if jitter else np.arange(n_slots) * interval
    return [float(t) for t in np.repeat(slots, burst)]


class LoadGen:
    """`submit(index) -> Future`; the future's result is the served answer."""

    def __init__(self, submit, arrivals: dict, seconds: float, seed: int,
                 on_done=None, clock=time.perf_counter, timeout_s=600.0,
                 first_index=0):
        self.submit, self.arrivals = submit, arrivals
        self.seconds, self.seed = float(seconds), seed
        self.on_done = on_done or (lambda record, n_done: None)
        self.clock, self.timeout_s = clock, timeout_s
        self.records = []
        self._lock = threading.Lock()
        self._first = self._next = first_index

    def _finish(self, rec, fut):
        try:
            with jax.profiler.TraceAnnotation("bench.wait"):
                rec["result"] = fut.result(timeout=self.timeout_s)
            rec["ok"] = True
        except Exception as exc:  # a refused or failed request counts as failed
            rec["ok"], rec["error"] = False, f"{type(exc).__name__}: {exc}"
        rec["done"] = self.clock()
        with self._lock:
            self.records.append(rec)
            n_done = len(self.records)
        self.on_done(rec, n_done)

    def _send(self, due):
        with self._lock:
            index = self._next
            self._next += 1
        rec = {"index": index, "due": due}
        try:
            with jax.profiler.TraceAnnotation("bench.submit"):
                fut = self.submit(index)
            rec["sent"] = self.clock()
        except Exception as exc:
            now = self.clock()
            rec.update(sent=now, done=now, ok=False,
                       error=f"{type(exc).__name__}: {exc}")
            with self._lock:
                self.records.append(rec)
            return None, rec
        return fut, rec

    def _closed_client(self, t_end):
        due = self.clock()
        while True:
            fut, rec = self._send(due)
            if fut is not None:
                self._finish(rec, fut)
            del fut  # the future holds the answer; on_done may have let go of it
            due = self.clock()
            if due >= t_end:
                return

    def run(self):
        """Offer the load; returns the records in order of request index."""
        kind = self.arrivals["kind"]
        t0 = self.clock()
        if kind == "closed":
            threads = [threading.Thread(target=self._closed_client,
                                        args=(t0 + self.seconds,), daemon=True)
                       for _ in range(int(self.arrivals["clients"]))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        elif kind == "open":
            pending = []
            for offset in open_schedule(self.arrivals, self.seconds, self.seed):
                due = t0 + offset
                delay = due - self.clock()
                if delay > 0:
                    time.sleep(delay)
                fut, rec = self._send(due)
                if fut is not None:
                    # the completing thread stamps the time; no thread of the
                    # generator's own per request
                    fut.add_done_callback(
                        lambda f, rec=rec: self._finish(rec, f))
                    pending.append(fut)
            deadline = time.monotonic() + self.timeout_s
            while any(not f.done() for f in pending):
                if time.monotonic() > deadline:
                    raise TimeoutError("open loop: requests still in flight")
                time.sleep(0.01)
            while len(self.records) < self._next - self._first:  # callbacks
                time.sleep(0.001)
        else:
            raise ValueError(f"arrivals.kind {kind!r}: 'closed' or 'open'")
        self.records.sort(key=lambda r: r["index"])
        return self.records
