"""Requests from a traffic file and the seed: the same seed gives the same
prompts and request seeds.  Every seed gives the same amount of work - the
same number of distinct requests of the same size - in another order."""

import numpy as np

# plain words: the served tokenizer is the weightless word hash
WORDS = ("a photo of an astronaut riding green horse on mars castle in the "
         "clouds oil painting red fox forest at dawn studio light portrait "
         "old sailor city street rain neon night watercolor mountain lake "
         "tiny robot reading book under tree macro shot dew spider web "
         "wide angle desert road storm").split()


def request_pool(traffic: dict, seed: int):
    """`request.pool` distinct requests; request i of the window is
    pool[i % pool], so a window longer than the pool repeats requests and
    the repeat must reproduce the first answer byte for byte."""
    req = traffic["request"]
    rng = np.random.default_rng([int(seed), 0x7EA])
    lo, hi = req["prompt_words"]
    pool = []
    for _ in range(int(req["pool"])):
        n = int(rng.integers(lo, hi + 1))
        pool.append({
            "prompt": " ".join(rng.choice(WORDS, size=n).tolist()),
            "negative_prompt": req.get("negative_prompt", ""),
            "seed": int(rng.integers(0, 2**31 - 1)),
        })
    return pool
