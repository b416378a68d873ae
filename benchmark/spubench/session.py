"""One run of one cell: what every loop mode shares.

A mode (`modes/<mode>.py`) gets a `Session`, does its own warm-up and its
own window through it, and returns what it observed. The session owns the
clock that `setup_s` is read from, the corpus, the reference, the broker
and the profiler trace of the traced run.
"""

from __future__ import annotations

import asyncio
import os
import shutil
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from spubench import check, manifest, window
from spubench.broker import Broker, encode_batches
from spubench.ragged import to_values
from spubench.trace_reduce import Tracer


class Session:
    def __init__(self, cell: manifest.Cell, seed: int, seconds: float,
                 trace: bool, t_process_start: float):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = seed
        self.seconds = seconds
        self.t_process_start = t_process_start
        self.t_open = None          # perf_counter when the window opened
        self.setup_s = None
        self.c_start = None         # program counters before any traffic
        self.c_open = None          # ... when the window opened
        self.shape = None           # record shapes: the widest of the references
        self.tmp = tempfile.mkdtemp(prefix="spubench-")
        self.broker = Broker(self.config, self.tmp + "/log")
        self.tracer = Tracer(self.tmp + "/trace", enabled=trace,
                             chips=cell.chips)
        self._corpus_mod = manifest.load_plugin(
            cell.bench_dir, "corpora", self.config["corpus"]["generator"]
        )
        self._reference_mod = manifest.load_plugin(
            cell.bench_dir, "references", self.config["reference"]["name"]
        )

    def note(self, what: str) -> None:
        """A line of the run's timeline on standard error: where set-up
        time goes is read from these."""
        print(f"[{time.perf_counter() - self.t_process_start:7.2f}s] {what}",
              file=sys.stderr, flush=True)

    # -- data ----------------------------------------------------------------

    def generate(self, n: int, stream: int = 0):
        """``n`` corpus records as (flat, offsets). The records are drawn
        from the configuration's ``base_seed``; the run's ``--seed`` gives
        the ORDER of the stored batches they are written in. Every seed
        thus serves the same set of batches (the same bytes to compress,
        the same shapes to compile) in another order: measured on the
        chip, seeds that drew their own records differed by 3.7 % in
        `records_in_per_s` where two runs of one seed differ by 0.1 %.
        ``stream`` separates independent draws of one run."""
        corpus = self.config["corpus"]
        flat, off = self._corpus_mod.generate(
            n, [int(corpus["base_seed"]), stream], **corpus.get("params", {})
        )
        per = int(self.config["stored_batch_records"])
        whole = n // per
        order = np.random.default_rng([self.seed, stream]).permutation(whole)
        bounds = [(b * per, (b + 1) * per) for b in order]
        if whole * per < n:
            bounds.append((whole * per, n))       # the short last batch stays last
        lens = np.concatenate([off[a + 1:b + 1] - off[a:b] for a, b in bounds])
        new_off = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(lens, out=new_off[1:])
        new_flat = np.concatenate([flat[off[a]:off[b]] for a, b in bounds])
        return new_flat, new_off

    def reference(self, flat, off, lo: int) -> check.Reference:
        """The host reference over a corpus whose first record has log
        offset ``lo``. The record shapes the readers size buffers by are
        the widest over every reference of the run (one a partition)."""
        ref = check.Reference(
            self._reference_mod, to_values(flat, off), lo,
            self.config["reference"].get("params", {}),
        )
        self.note(f"reference over {len(off) - 1} records, {len(ref.lens)} out")
        n_in = len(off) - 1
        shape = {
            "max_in_len": int((off[1:] - off[:-1]).max()),
            "max_out_len": int(ref.lens.max()) if len(ref.lens) else 1,
            "fanout": max(1, -(-len(ref.lens) // n_in)),
        }
        if self.shape is not None:
            shape = {k: max(v, self.shape[k]) for k, v in shape.items()}
        self.shape = shape
        return ref

    async def write_backlog(self, flat, off, partition: int = 0) -> int:
        """A partition's backlog, one stored batch per write, as a
        producer's batches would have arrived."""
        per = int(self.config["stored_batch_records"])
        for batch in encode_batches(flat, off, 0, len(off) - 1, per):
            await self.broker.write([batch], partition)
        end = self.broker.log_end(partition)
        self.note(f"backlog written to partition {partition}, log end {end}")
        return end

    # -- lifecycle -----------------------------------------------------------

    def adopt_loop(self, loop) -> None:
        """Give the event loop a default executor of the default size that
        the session can see into (`settle`). The SPU runs chain builds
        and its small-shape chain warm-up on the loop's default executor."""
        self._workers = min(32, (os.cpu_count() or 1) + 4)
        self._pool = ThreadPoolExecutor(
            max_workers=self._workers, thread_name_prefix="asyncio"
        )
        loop.set_default_executor(self._pool)

    async def settle(self, timeout: float = 900.0) -> None:
        """Wait until every worker thread of the loop's executor is free.
        When a stream first opens, the SPU compiles the chain's smallest
        shape on such a thread, off the hot path; that compile belongs to
        set-up and must have ended before the window opens. A barrier of
        as many parties as the pool has threads passes only when all of
        them are free at once."""
        barrier = threading.Barrier(self._workers)
        loop = asyncio.get_running_loop()
        await asyncio.gather(*[
            loop.run_in_executor(self._pool, barrier.wait, timeout)
            for _ in range(self._workers)
        ])
        self.note("executor threads idle")

    async def start(self) -> None:
        self.note("imports done, starting the SPU")
        await self.broker.start()
        self.c_start = window.snapshot(self.broker)
        self.note("SPU up, device open")

    def open_window(self) -> float:
        """Set-up ends here: everything before is `setup_s`."""
        self.c_open = window.snapshot(self.broker)
        self.t_open = time.perf_counter()
        self.setup_s = self.t_open - self.t_process_start
        self.tracer.arm(self.seconds)
        self.note(f"window open (setup_s {self.setup_s:.1f})")
        return self.t_open

    async def close(self) -> None:
        self.tracer.finish()
        await self.broker.stop()

    def cleanup(self) -> None:
        """Remove the run's log and trace; after the trace was reduced."""
        shutil.rmtree(self.tmp, ignore_errors=True)
