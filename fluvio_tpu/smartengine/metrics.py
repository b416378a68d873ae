"""Chain execution metrics.

Capability parity: fluvio-smartengine/src/engine/metrics.rs
(`SmartModuleChainMetrics{bytes_in, records_out, invocation_count,
fuel_used}`). The reference meters cost in wasmtime fuel; the analog here is
user-transform invocations (python backend: one unit per record per
instance) or device kernel records processed (tpu backend).
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field

from fluvio_tpu.analysis.lockwatch import make_lock


@dataclass
class SmartModuleChainMetrics:
    bytes_in: int = 0
    records_out: int = 0
    invocation_count: int = 0
    fuel_used: int = 0
    # fast-path observability: a slice silently dropping from the
    # coalesced TPU path to the per-record loop is a ~100x throughput
    # cliff — count both outcomes and the decline reason so operators can
    # see it happening (review round 2 weak#6)
    fastpath_slices: int = 0
    fallback_slices: int = 0
    fallback_reasons: dict = field(default_factory=dict)
    # stream opens served from the SPU's stream-chain cache, and those
    # that built their chain (a re-trace and an executable load per
    # shape bucket: hundreds of ms a stream)
    stream_chain_hits: int = 0
    stream_chain_builds: int = 0
    # slices re-run because a record held more elements than the fan-out
    # chain's learned bound (one compile each; the `fanout-grow` event)
    fanout_grows: int = 0
    _lock: object = field(
        default_factory=lambda: make_lock("smartengine.metrics"), repr=False
    )

    def add_bytes_in(self, n: int) -> None:
        with self._lock:
            self.bytes_in += n
            self.invocation_count += 1

    def add_records_out(self, n: int) -> None:
        with self._lock:
            self.records_out += n

    def add_fuel_used(self, n: int) -> None:
        with self._lock:
            self.fuel_used += n

    def add_fastpath(self) -> None:
        with self._lock:
            self.fastpath_slices += 1

    def add_fallback(self, reason: str) -> None:
        with self._lock:
            self.fallback_slices += 1
            self.fallback_reasons[reason] = (
                self.fallback_reasons.get(reason, 0) + 1
            )

    def add_stream_chain(self, hit: bool) -> None:
        with self._lock:
            if hit:
                self.stream_chain_hits += 1
            else:
                self.stream_chain_builds += 1

    def add_fanout_grows(self, n: int) -> None:
        if n:
            with self._lock:
                self.fanout_grows += n

    def to_dict(self) -> dict:
        # snapshot under the lock: a scrape concurrent with add_* must
        # never see torn multi-field state (e.g. bytes_in advanced but
        # invocation_count not yet)
        with self._lock:
            return {
                "bytes_in": self.bytes_in,
                "records_out": self.records_out,
                "invocation_count": self.invocation_count,
                "fuel_used": self.fuel_used,
                "fastpath_slices": self.fastpath_slices,
                "fallback_slices": self.fallback_slices,
                "fallback_reasons": dict(self.fallback_reasons),
                "stream_chain_hits": self.stream_chain_hits,
                "stream_chain_builds": self.stream_chain_builds,
                "fanout_grows": self.fanout_grows,
            }

    def to_json(self) -> str:
        return json.dumps(self.to_dict())
