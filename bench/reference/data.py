"""The training batches a run is fed, rebuilt from ``--seed`` for the
reference.

The program's loader (``repro.data.pipeline.make_stream``) feeds the timed
window; the reference never reads what the program made, so it draws the
same batches again here.  This is a copy of that loader's synthetic
distribution: an order-1 Markov chain over the first 4096 token ids, 75%
deterministic transitions, seeded per ``(seed, host, step)``.  If the
program's loader ever feeds anything else, the losses part and the run
reads as not correct.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

MARKOV_ORDER = 1
TOKEN_RANGE = 4096      # ids are drawn from min(vocab, TOKEN_RANGE)
P_DETERMINISTIC = 0.75


def batch(seed: int, step: int, batch_size: int, seq: int,
          vocab: int) -> Dict[str, np.ndarray]:
    """Rows ``tokens`` and next-token ``labels`` (int32, ``(batch, seq)``)
    of training step ``step``."""
    k = min(vocab, TOKEN_RANGE)
    table = np.random.default_rng(seed)
    proj = table.integers(1, 2**31 - 1, size=(MARKOV_ORDER,), dtype=np.int64)
    bias = table.integers(0, 2**31 - 1, dtype=np.int64)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0, step]))
    toks = np.zeros((batch_size, seq + 1), np.int64)
    toks[:, :MARKOV_ORDER] = rng.integers(0, k, size=(batch_size, MARKOV_ORDER))
    noise = rng.random((batch_size, seq + 1))
    for t in range(MARKOV_ORDER, seq + 1):
        ctx = sum(toks[:, t - i - 1] * proj[i]
                  for i in range(MARKOV_ORDER)) + bias
        det = (ctx % k).astype(np.int64)
        rand = rng.integers(0, k, size=batch_size)
        toks[:, t] = np.where(noise[:, t] < P_DETERMINISTIC, det, rand)
    return {"tokens": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:].astype(np.int32)}
