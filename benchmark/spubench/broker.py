"""The system under test and the consumer's side of its socket.

One in-process `SpuServer` (engine ``backend="tpu"``), one topic and
partition, the log written through the leader as natively encoded stored
batches, and a consumer that drives `StreamFetchRequest` /
`UpdateOffsetsRequest` over the client's own socket so that it sees each
response (and its ``next_filter_offset``) and not only the batches.

Copied in shape from `chip_smoke.py` (`wire_batches`, `_broker`) and
`client/consumer.py:stream_batches`; the benchmark imports neither
script, because they may change and the yardstick may not.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import List

import numpy as np

TOPIC = "bench"
BASE_TIMESTAMP = 1_000_000


def encode_batches(flat: np.ndarray, off: np.ndarray, lo: int, hi: int,
                   per_batch: int) -> list:
    """Records [lo, hi) of a corpus as stored batches of ``per_batch``
    records, encoded by the program's native record encoder."""
    from fluvio_tpu.protocol.record import Batch
    from fluvio_tpu.smartengine import native_backend

    out = []
    no_key = np.zeros(1, np.uint8)
    for a in range(lo, hi, per_batch):
        b = min(a + per_batch, hi)
        m = b - a
        raw = native_backend.encode_record_columns(
            flat[off[a]:off[b]], off[a:b + 1] - off[a],
            no_key, np.zeros(m + 1, np.int64), np.zeros(m, np.uint8),
            np.arange(m, dtype=np.int64), np.zeros(m, np.int64),
        )
        if raw is None:
            raise RuntimeError("the program's native record codec did not build")
        batch = Batch(base_offset=0, raw_records=raw, raw_record_count=m)
        batch.header.first_timestamp = BASE_TIMESTAMP
        batch.header.max_time_stamp = BASE_TIMESTAMP
        batch.header.last_offset_delta = m - 1
        out.append(batch)
    return out


def invocations(chain: list) -> list:
    """A configuration's ``chain`` as wire SmartModule invocations."""
    from fluvio_tpu.schema.smartmodule import (
        SmartModuleInvocation,
        SmartModuleInvocationKind,
        SmartModuleInvocationWasm,
    )

    return [
        SmartModuleInvocation(
            wasm=SmartModuleInvocationWasm.adhoc(step["adhoc"].encode()),
            kind=SmartModuleInvocationKind[step["kind"]],
            params=dict(step.get("params") or {}),
        )
        for step in chain
    ]


@dataclass
class Response:
    """One stream-fetch response as the consumer saw it. Nothing here is
    a per-record Python object: counts, byte lengths and batch headers."""

    t: float            # perf_counter at arrival, before the ack
    next_offset: int    # input progress: next_filter_offset
    records_out: int
    bytes_out: int      # wire bytes of the response's record batches
    batches: list       # shallow-decoded batches (raw record slabs)


class Broker:
    """SPU + client in this process, talking over a localhost socket."""

    def __init__(self, config: dict, log_dir: str):
        self.config = config
        self.log_dir = log_dir
        self.server = None
        self.client = None
        self.socket = None
        self.leader = None

    async def start(self) -> None:
        from fluvio_tpu.client import Fluvio
        from fluvio_tpu.spu import SpuConfig, SpuServer
        from fluvio_tpu.storage.config import ReplicaConfig

        dep = self.config["deployment"]
        if dep["partitions"] != 1 or dep["replication"] != 1:
            raise ValueError("this harness serves one partition, replication 1")
        cfg = SpuConfig(
            id=9001,
            public_addr="127.0.0.1:0",
            log_base_dir=self.log_dir,
            replication=ReplicaConfig(base_dir=self.log_dir),
        )
        cfg.smart_engine.backend = dep["engine_backend"]
        self.server = SpuServer(cfg)
        await self.server.start()
        self.server.ctx.create_replica(TOPIC, 0)
        self.leader = self.server.ctx.leader_for(TOPIC, 0)
        self.client = await Fluvio.connect(self.server.public_addr)
        consumer = await self.client.partition_consumer(TOPIC, 0)
        self.socket = consumer._socket

    async def stop(self) -> None:
        if self.client is not None:
            await self.client.close()
        if self.server is not None:
            await self.server.stop()

    async def write(self, batches: list) -> int:
        """Append stored batches as ONE record set; returns the log end."""
        from fluvio_tpu.protocol.record import RecordSet

        rs = RecordSet()
        for b in batches:
            rs.add(b)
        await self.leader.write_record_set(rs)
        return self.leader.offsets().leo

    def log_end(self) -> int:
        return self.leader.offsets().leo

    def slice_counts(self) -> dict:
        """Fast-path / fallback slices and reasons, as the SPU books them."""
        return self.server.ctx.metrics.smartmodule.to_dict()

    def stream(self, start: int, max_bytes: int) -> "ConsumerStream":
        """A stream fetch from ``start``; use as ``async with``."""
        return ConsumerStream(self, start, max_bytes)


class ConsumerStream:
    """One `StreamFetchRequest` on the client's socket. Each response is
    stamped on arrival, counted, and acked with `UpdateOffsetsRequest`
    exactly as `client/consumer.py:stream_batches` acks it."""

    def __init__(self, broker: Broker, start: int, max_bytes: int):
        self.broker = broker
        self.start = start
        self.max_bytes = max_bytes
        self._stream = None
        self._last_seen = start - 1

    async def __aenter__(self) -> "ConsumerStream":
        from fluvio_tpu.schema.spu import StreamFetchRequest

        request = StreamFetchRequest(
            topic=TOPIC,
            partition=0,
            fetch_offset=self.start,
            max_bytes=self.max_bytes,
            smartmodules=invocations(self.broker.config["chain"]),
        )
        self._stream = await self.broker.socket.create_stream(request)
        return self

    async def __aexit__(self, *exc) -> None:
        await self._stream.close()

    async def next(self) -> Response:
        from fluvio_tpu.protocol.error import ErrorCode, FluvioError
        from fluvio_tpu.schema.spu import OffsetUpdate, UpdateOffsetsRequest

        response = await self._stream.next()
        if response is None:
            raise ConnectionError("the SPU closed the stream")
        t = time.perf_counter()
        part = response.partition
        if part.error_code != ErrorCode.NONE:
            raise FluvioError(part.error_code, part.error_message)
        batches: List = part.records.batches
        for b in batches:
            self._last_seen = max(self._last_seen, b.computed_last_offset() - 1)
        next_offset = (
            part.next_filter_offset
            if part.next_filter_offset >= 0
            else self._last_seen + 1
        )
        await self.broker.socket.send_async(
            UpdateOffsetsRequest(offsets=[
                OffsetUpdate(offset=next_offset, session_id=response.stream_id)
            ])
        )
        return Response(
            t=t,
            next_offset=next_offset,
            records_out=sum(b.records_len() for b in batches),
            bytes_out=sum(b.write_size() for b in batches),
            batches=batches,
        )
