"""One generator for every traffic mix: a mix is a data file of parameters
under ``traffic/``, read by name.

Closed loop (``"loop": "closed"``): ``clients`` clients, each with one
request outstanding; a client whose request finishes sends its next one at
once.  Request sizes follow a cycle of ``clients`` entries: prompt lengths
cycle through ``prompt_lengths`` and output lengths are spread evenly over
``output_range`` (both ends included).  The seed draws only the token ids.
Requests run to their output length whatever tokens they are served, so
every seed offers the same work in the same order and the engine schedules
it the same way: runs differ only in the ids.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

FIRST_TOKEN_ID = 2          # ids 0 and 1 are left to padding and BOS


@dataclass(frozen=True)
class RequestSpec:
    index: int
    client: int
    prompt: np.ndarray
    max_new: int


def cycle_sizes(mix: dict) -> list:
    """The (prompt, output) sizes of one cycle, in the order served."""
    n = int(mix["clients"])
    lens = list(mix["prompt_lengths"])
    lo, hi = mix["output_range"]
    outs = np.rint(np.linspace(lo, hi, n)).astype(int)
    # outputs in golden-ratio order, so neighbours in the cycle differ
    order = sorted(range(n), key=lambda i: (i * 0.6180339887) % 1.0)
    return [(lens[i % len(lens)], int(outs[j])) for i, j in enumerate(order)]


class ClosedLoop:
    def __init__(self, mix: dict, vocab: int, seed: int):
        if mix.get("loop") != "closed":
            raise ValueError(f"unsupported loop {mix.get('loop')!r}")
        self.clients = int(mix["clients"])
        self.vocab = vocab
        self._rng = np.random.default_rng(np.random.SeedSequence(seed))
        self._sizes = cycle_sizes(mix)
        self._count = 0

    def next_request(self, client: int) -> RequestSpec:
        prompt_len, max_new = self._sizes[self._count % len(self._sizes)]
        prompt = self._rng.integers(FIRST_TOKEN_ID, self.vocab,
                                    size=prompt_len, dtype=np.int32)
        spec = RequestSpec(self._count, client, prompt, max_new)
        self._count += 1
        return spec
