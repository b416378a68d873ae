"""Drain of a log that is in EVENT-TIME order, but for bounded disorder.

`Session.generate` permutes all stored batches by ``--seed``, which for
a corpus whose records carry event times would scatter event time over
the whole log. This mode hands `modes/drain.py:run` a session whose
`generate` draws the corpus once from ``corpus.base_seed`` in event
order and lets ``--seed`` decide, for each consecutive pair of stored
batches (0-1, 2-3, ...), whether the two swap places; a batch without a
partner and the short last batch stay where they are. Every seed then
serves the same bytes and batch shapes, disorder is at most two stored
batches of event time, and each seed folds out-of-order records into
open windows at other places. Everything else (warm-up, the full
comparison of the first pass, the window, counts, faults) is `drain`'s;
the run's observations gain `window_replicas`, the configuration's
window over its slide.
"""

from __future__ import annotations

import numpy as np

from spubench import manifest


def event_order(config: dict, seed: int, n: int, stream: int = 0):
    """Start and end record of each stored batch of an ``n``-record log,
    in the order ``seed`` writes them."""
    per = int(config["stored_batch_records"])
    whole = n // per
    swap = np.random.default_rng([seed, stream]).integers(0, 2, size=whole // 2)
    order = []
    for pair, s in enumerate(swap):
        order += [2 * pair + 1, 2 * pair] if s else [2 * pair, 2 * pair + 1]
    order += range(len(order), whole)
    bounds = [(b * per, (b + 1) * per) for b in order]
    if whole * per < n:
        bounds.append((whole * per, n))
    return bounds


def _generate(s, corpus_mod, n: int, stream: int = 0):
    corpus = s.config["corpus"]
    flat, off = corpus_mod.generate(
        n, [int(corpus["base_seed"]), stream], **corpus.get("params", {})
    )
    bounds = event_order(s.config, s.seed, n, stream)
    lens = np.concatenate([off[a + 1:b + 1] - off[a:b] for a, b in bounds])
    new_off = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lens, out=new_off[1:])
    return np.concatenate([flat[off[a]:off[b]] for a, b in bounds]), new_off


async def run(s) -> dict:
    corpus_mod = manifest.load_plugin(
        s.cell.bench_dir, "corpora", s.config["corpus"]["generator"]
    )
    s.generate = lambda n, stream=0: _generate(s, corpus_mod, n, stream)
    drain = manifest.load_plugin(s.cell.bench_dir, "modes", "drain")
    obs = await drain.run(s)
    # the window phases a record counts in, for the readers of the
    # window stage's bytes (`spubench/window_bytes.py`)
    geometry = s.config["reference"]["params"]
    obs["window_replicas"] = (
        int(geometry["window_ms"]) // int(geometry["slide_ms"])
    )
    return obs
