"""The device-resident window state bank.

The generalization of the partition carry bank (partition/runtime.py):
instead of one (acc, win, has) triple per aggregate stage, the bank
holds up to ``capacity`` (composite id, acc, count) rows (a keyed
table's: composite id and one acc per lane, ``accs`` then int64[lanes,
capacity] and `WindowSpec.counted` off) plus one watermark scalar — still tiny, still constant-size, still living in
device memory across batches so nothing but the per-batch DELTA ever
crosses the link down.

Host mirrors (`occupancy`, `watermark`) update from each batch's scalar
header fetch; `snapshot`/`restore` produce the host tuples that ride
the CarryReplica failover/migration bus (partition/failover.py), and
`to_device` is the lazy re-placement migration move the partition
runtime established.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from fluvio_tpu.windows.spec import EMPTY_ID, INT64_MIN, WindowSpec

# bytes one live bank entry occupies on device (id + acc + count, i64);
# a keyed table's: `WindowSpec.entry_bytes`
ENTRY_BYTES = 24


def _filled(spec: WindowSpec, n: int):
    """``n`` empty slots of the accumulator column(s): int64[n] of the
    monoid's neutral, or int64[lanes, n] of each lane's (numpy)."""
    neutral = np.asarray(spec.neutral, dtype=np.int64)
    return np.broadcast_to(neutral[..., None], neutral.shape + (n,)).copy()


class WindowStateBank:
    """Per-stream (or per-partition) windowed carry state."""

    def __init__(self, spec: WindowSpec, device=None):
        self.spec = spec
        self.device = device
        self.occupancy = 0  # live entries (host mirror of the header)
        self.watermark = INT64_MIN + 1  # host mirror
        self._init_arrays()

    def _init_arrays(self) -> None:
        import jax
        import jax.numpy as jnp

        k = self.spec.capacity
        arrs = (
            jnp.full((k,), EMPTY_ID, dtype=jnp.int64),
            jnp.asarray(_filled(self.spec, k)),
            jnp.zeros((k,), dtype=jnp.int64) if self.spec.counted else None,
            jnp.int64(self.watermark),
        )
        if self.device is not None:
            arrs = jax.device_put(arrs, self.device)
        self.ids, self.accs, self.counts, self.wm = arrs

    def arrays(self) -> tuple:
        return self.ids, self.accs, self.counts, self.wm

    def commit(self, ids, accs, counts, wm, occupancy: int,
               watermark: int) -> None:
        """Install one batch's merged state (called only after the
        batch's fetch succeeded — a faulted batch leaves the previous
        carry untouched, which is what makes retries exact)."""
        self.ids, self.accs, self.counts, self.wm = ids, accs, counts, wm
        self.occupancy = int(occupancy)
        self.watermark = int(watermark)
        self._note_ledger()

    @property
    def capacity(self) -> int:
        """Entries the device arrays hold (the spec's, until `grow`)."""
        return int(self.ids.shape[0])

    def grow(self, capacity: int) -> None:
        """Pad the device arrays to ``capacity`` entries (never
        shrinks): a served stream's bank starts small and doubles when
        a slice's header reports more open entries than it holds. The
        live entries stay compacted at the front."""
        import dataclasses

        import jax.numpy as jnp

        pad = capacity - self.capacity
        if pad <= 0:
            return
        self.ids = jnp.concatenate(
            [self.ids, jnp.full((pad,), EMPTY_ID, dtype=jnp.int64)]
        )
        self.accs = jnp.concatenate(
            [self.accs, jnp.asarray(_filled(self.spec, pad))], axis=-1
        )
        if self.counts is not None:
            self.counts = jnp.concatenate(
                [self.counts, jnp.zeros((pad,), dtype=jnp.int64)]
            )
        self.spec = dataclasses.replace(self.spec, capacity=capacity)

    def checkpoint(self) -> tuple:
        """`commit`'s arguments for putting the bank back where it
        stands (a served slice that declines after its commit is re-run
        from where it started): references only, device arrays are
        immutable."""
        return (self.ids, self.accs, self.counts, self.wm,
                self.occupancy, self.watermark)

    def state_bytes(self) -> int:
        """Live device bytes (the `window_state_bytes` gauge)."""
        return self.occupancy * self.spec.entry_bytes + 8

    def _note_ledger(self) -> None:
        # window_bank device-memory booking is ALWAYS-ON (state size
        # is exactness evidence, like the delta byte counters); the
        # window_state_bytes gauge republishes from the ledger, still
        # gated on capture being enabled
        from fluvio_tpu.telemetry import memory as memory_mod

        memory_mod.note_window_bank(id(self), self.state_bytes())

    # -- failover / migration (CarryReplica tuple format) --------------------

    def snapshot(self) -> Tuple[List[tuple], int]:
        """Host snapshot: ([(id, acc, count), ...] live entries, the
        watermark) — the carries/inst_state pair the CarryReplica bus
        publishes at commit cadence. A keyed table's ``acc`` is the
        tuple of its lanes and its ``count`` None."""
        import jax

        n = self.occupancy
        ids, accs, counts = jax.device_get((
            self.ids[:n], self.accs[..., :n],
            None if self.counts is None else self.counts[:n],
        ))
        accs = accs.tolist() if accs.ndim == 1 else list(zip(*accs.tolist()))
        counts = [None] * n if counts is None else counts.tolist()
        return list(zip(ids.tolist(), accs, counts)), self.watermark

    def restore(self, entries: List[tuple], watermark: int) -> None:
        """Seed from a snapshot (promotion / migration / consumer
        resync). Entries land compacted and the device arrays rebuild
        in one put — the same whole-state seed shape as
        `PartitionRuntime.seed_partition`."""
        import jax
        import jax.numpy as jnp

        k = self.spec.capacity
        if len(entries) > k:
            from fluvio_tpu.windows.spec import WindowCapacityError

            raise WindowCapacityError(
                f"snapshot holds {len(entries)} entries; bank capacity "
                f"is {k} (raise FLUVIO_WINDOW_CAPACITY)"
            )
        ids = np.full((k,), EMPTY_ID, dtype=np.int64)
        accs = _filled(self.spec, k)
        counts = np.zeros((k,), dtype=np.int64)
        for i, (eid, acc, cnt) in enumerate(entries):
            ids[i], accs[..., i], counts[i] = eid, acc, cnt or 0
        arrs = (
            jnp.asarray(ids),
            jnp.asarray(accs),
            jnp.asarray(counts) if self.spec.counted else None,
            jnp.int64(watermark),
        )
        if self.device is not None:
            arrs = jax.device_put(arrs, self.device)
        self.ids, self.accs, self.counts, self.wm = arrs
        self.occupancy = len(entries)
        self.watermark = int(watermark)
        self._note_ledger()

    def to_device(self, device) -> None:
        """Lazy carry re-placement (the partition runtime's migration
        move): put the live arrays on ``device`` without a host
        round-trip of the values."""
        import jax

        if device is self.device:
            return
        self.ids, self.accs, self.counts, self.wm = jax.device_put(
            (self.ids, self.accs, self.counts, self.wm), device
        )
        self.device = device

    def full_rows(self) -> np.ndarray:
        """Every live entry as host rows [[id, acc, count], ...] — the
        resync payload (consumer attach / emit-capacity overflow)."""
        import jax

        n = self.occupancy
        ids, accs, counts = jax.device_get(
            (self.ids[:n], self.accs[:n], self.counts[:n])
        )
        return np.stack(
            [np.asarray(ids), np.asarray(accs), np.asarray(counts)], axis=1
        ) if n else np.zeros((0, 3), dtype=np.int64)


def merge_banks(
    jits, a: WindowStateBank, b: WindowStateBank,
    out: Optional[WindowStateBank] = None,
) -> WindowStateBank:
    """Associative combine of two banks (striped/sharded ingest):
    ``out`` (default: a fresh bank on ``a``'s device) receives the
    merged entries and max watermark. Serial-equivalence is pinned by
    tests: split ingest + merge == one-stream ingest, bit-equal."""
    header, ids, accs, counts = jits.merge(a.arrays(), b.arrays())
    import jax

    n_open, wm, overflow = (int(x) for x in jax.device_get(header))
    if overflow:
        from fluvio_tpu.windows.spec import WindowCapacityError

        raise WindowCapacityError(
            f"bank merge overflows capacity {a.spec.capacity} "
            "(raise FLUVIO_WINDOW_CAPACITY)"
        )
    if out is None:
        out = WindowStateBank(a.spec, device=a.device)
    out.ids, out.accs, out.counts = ids, accs, counts
    import jax.numpy as jnp

    out.wm = jnp.int64(wm)
    out.occupancy = n_open
    out.watermark = wm
    return out
