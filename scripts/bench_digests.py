"""A benchmark cell run exactly as `benchmark/run.py` runs it, which also
says the digest of every served image's bytes: two checkouts that print the
same digests for one `--seed` served the same images, byte for byte.

    cd <checkout> && python <this file> --workload <cell> --seed <n> \
        --seconds 51 --trace 0

The benchmark of the checkout it is started IN is the one that runs (this
file only needs to exist in one of them).
"""

import os
import sys

sys.path.insert(0, os.getcwd())

from benchmark import run  # noqa: E402
from benchmark.harness.images import ImageChecks  # noqa: E402

_note = ImageChecks._note


def _note_and_say(self, index, image):
    _note(self, index, image)
    print(f"[digest] request {index}: {self.digests.get(index)}", flush=True)


ImageChecks._note = _note_and_say

if __name__ == "__main__":
    sys.exit(run.main())
