"""Closed loop: one caller sends batches back to back through ``answer_batch``.

A mix's ``arrivals``: ``{"kind": "closed", "batch": B, "batch_s": S}``.
The window is a fixed amount of work, ``ceil(seconds / S)`` batches of
``B`` distinct queries, made before it starts: every run serves the same
batches, so no run gains or loses a batch at the window's end. ``S`` is
what one batch of the mix took on the chip when the mix was written, so
the window lasts about ``seconds``; the rate is taken over all of it.
"""

from __future__ import annotations

import math
import time

from chipbench.served import Window


def warm_sizes(params: dict) -> list[int]:
    """The batch sizes the window sends."""
    return [int(params["batch"])]


def units(params: dict, seconds: float) -> int:
    """Batches in a window of ``seconds``."""
    return max(1, math.ceil(seconds / float(params["batch_s"])))


def drive(engine, gen, params: dict, seconds: float, capture) -> Window:
    """Serve the window's batches; ``capture(i, n)`` wraps batch ``i`` of ``n``."""
    batch = int(params["batch"])
    work = [gen.next_batch(batch) for _ in range(units(params, seconds))]
    responses: list = []
    t0 = time.perf_counter()
    for i, queries in enumerate(work):
        with capture(i, len(work)):
            responses += engine.answer_batch(queries)
    elapsed = time.perf_counter() - t0
    queries = [q for qs in work for q in qs]
    return Window(elapsed, queries, responses, len(queries), len(queries) - len(responses))
