"""What the checks need of every served image, without keeping the images.

A window's images are 12.6 MB each at 1024x1024.  Held until the window
closes they force the program's next image into memory the process has
never touched, and on the chip machine's kernel those page faults cost a
request ~40 ms on some requests and not on others (PERF.md section 5).  So
each image is looked at once, by a thread of its own beside the generator -
right size, finite, not constant, a digest of its bytes - and let go; only
the latest is kept whole, in one buffer made during set-up, for the
reference to be held against.
"""

import hashlib
import queue
import threading

import numpy as np


class ImageChecks:
    def __init__(self, shape):
        self.shape = tuple(shape)
        self.last = np.full(self.shape, 0.0, np.float32)  # touched now
        self.last_index = None
        self.digests = {}  # request index -> digest of the image's bytes
        self.bad = []  # (request index, what was wrong)
        self._queue = queue.SimpleQueue()
        self._thread = threading.Thread(target=self._work, daemon=True)
        self._thread.start()

    def put(self, index: int, image) -> None:
        """Hand over request `index`'s image; the caller lets go of it."""
        self._queue.put((index, image))

    def close(self) -> None:
        """Wait until every image handed over has been looked at."""
        self._queue.put(None)
        self._thread.join()

    def _work(self):
        while True:
            item = self._queue.get()
            if item is None:
                return
            try:
                self._note(*item)
            except Exception as exc:
                self.bad.append((item[0], f"{type(exc).__name__}: {exc}"))
            del item

    def _note(self, index, image):
        image = np.ascontiguousarray(image)
        if image.shape != self.shape:
            self.bad.append((index, f"shape {image.shape}"))
            return
        # min and max make no copy of the image; a NaN comes out of both
        lo, hi = float(image.min()), float(image.max())
        if not (np.isfinite(lo) and np.isfinite(hi)):
            self.bad.append((index, "not finite"))
        elif hi - lo <= 1e-3:
            self.bad.append((index, f"constant: {lo}..{hi}"))
        self.digests[index] = hashlib.blake2b(image, digest_size=16).hexdigest()
        if self.last_index is None or index > self.last_index:
            np.copyto(self.last, image, casting="unsafe")
            self.last_index = index

    def repeats_that_differ(self, pool: int):
        """Request i repeats request i % pool: the same bytes are due."""
        return [(i, i % pool) for i, d in sorted(self.digests.items())
                if i >= pool and self.digests.get(i % pool, d) != d]

    def distinct_that_agree(self, pool: int):
        """The pool's requests differ, so their images must."""
        firsts = sorted(i for i in self.digests if i < pool)
        return [(a, b) for a, b in zip(firsts, firsts[1:])
                if self.digests[a] == self.digests[b]]
