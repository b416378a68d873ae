"""SPU process assembly (parity: fluvio-spu/src/start.rs:15,66).

Builds the GlobalContext and runs: the public API server, the internal
(peer replication) server, the followers controller, and — when an SC
address is configured — the SC dispatcher (register + metadata pushes +
LRS reporting).
"""

from __future__ import annotations

import os
from typing import Optional

from fluvio_tpu.spu.cleaner_controller import CleanerController
from fluvio_tpu.spu.config import SpuConfig
from fluvio_tpu.spu.context import GlobalContext
from fluvio_tpu.spu.follower import FollowersController
from fluvio_tpu.spu.internal_service import SpuInternalService
from fluvio_tpu.spu.monitoring import MonitoringServer
from fluvio_tpu.spu.public_service import SpuPublicService
from fluvio_tpu.spu.sc_dispatcher import ScDispatcher
from fluvio_tpu.transport.service import FluvioApiServer
from fluvio_tpu.transport.tls import server_ssl


def keep_large_buffers_on_heap() -> bool:
    """Tell glibc's allocator to keep freed blocks of up to 32 MiB on
    ONE heap. A served slice's way out allocates its response several
    times over (the native slab, its `bytes`, the frame; the consumer's
    reader and decode on the other side), tens of MB each for a chain
    that answers every record. By default every block over 128 KiB is a
    fresh `mmap` handed back on `free`, and a worker thread's arena
    gives its heaps back the same way: each buffer is then page-faulted
    in anew, which costs three to five times its memcpy (three copies
    of 28.6 MB: 50 ms on the main thread and 25 on a worker, against 9
    with these settings; PERF.md section 6, PR 39) and swings with the
    host's neighbours. One arena, because the thresholds reach only the
    main one; the process's allocations are made under the GIL or are
    few. The arena limit binds threads that allocate from now on only:
    a stream loop's worker may still share an arena that the runtime's
    threads made before, whose extra heaps glibc unmaps as soon as they
    empty, whatever the trim threshold, unless the top pad is larger
    than they are. Without that pad, about half of a session slice's
    16 MB upload copies took 16 ms instead of 1.5 on a TPU v5e host
    (PERF.md section 6). Best effort: False where libc has no `mallopt`
    (not glibc), and nothing is changed."""
    import ctypes

    m_trim_threshold, m_top_pad, m_mmap_threshold, m_arena_max = (
        -1, -2, -3, -8  # malloc.h
    )
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return False
    # 32 MiB is the largest mmap threshold glibc takes; a heap that is
    # never trimmed below 1 GiB of free space keeps the blocks mapped; a
    # top pad of a whole thread heap (64 MiB) keeps such heaps mapped
    return bool(mallopt(m_mmap_threshold, 32 << 20)
                and mallopt(m_trim_threshold, 1 << 30)
                and mallopt(m_top_pad, 64 << 20)
                and mallopt(m_arena_max, 1))


class SpuServer:
    def __init__(self, config: SpuConfig):
        self.config = config
        self.ctx = GlobalContext(config)
        self.public_server = FluvioApiServer(
            config.public_addr,
            SpuPublicService(),
            self.ctx,
            ssl_context=server_ssl(config.tls),
        )
        self.internal_server: Optional[FluvioApiServer] = (
            FluvioApiServer(config.private_addr, SpuInternalService(), self.ctx)
            if config.private_addr
            else None
        )
        self.followers_controller = FollowersController(self.ctx)
        self.ctx.followers_controller = self.followers_controller
        self.sc_dispatcher: Optional[ScDispatcher] = (
            ScDispatcher(self.ctx, config.sc_addr) if config.sc_addr else None
        )
        self.monitoring: Optional[MonitoringServer] = (
            MonitoringServer(self.ctx, config.monitoring_path or None)
            if config.monitoring_path is not None
            else None
        )
        self.cleaner = CleanerController(
            self.ctx, config.cleaner_interval_seconds
        )

    @property
    def public_addr(self) -> str:
        return self.public_server.local_addr

    @property
    def private_addr(self) -> str:
        assert self.internal_server is not None, "internal server disabled"
        return self.internal_server.local_addr

    async def start(self) -> None:
        # a FLUVIO_* var nothing reads is a deploy-manifest typo: warn
        # at boot, not after a silent week of the flag never applying
        from fluvio_tpu.analysis.envreg import warn_unknown_env

        warn_unknown_env()
        keep_large_buffers_on_heap()
        if self.config.smart_engine.backend == "tpu":
            # one process per chip: an SPU asked to serve from the device
            # opens it NOW, so a chip another process holds (or one that
            # cannot be opened) kills this start loudly — never a broker
            # that comes up and serves from the interpreter
            from fluvio_tpu.smartengine.engine import touch_device

            touch_device()
        if self.config.smart_engine.backend in ("auto", "native"):
            # warm the native engine's g++ build off the event loop so the
            # first SmartModule chain build doesn't stall request handling
            import threading

            from fluvio_tpu.smartengine.native_backend import load_library

            threading.Thread(target=load_library, daemon=True).start()
        if os.environ.get("FLUVIO_PARTITIONS"):
            # resolve the partition placement gate (plan + mesh build)
            # at server start so the first stream's slice never pays it
            from fluvio_tpu.partition import gate as partition_gate

            partition_gate()
        await self.public_server.start()
        if self.internal_server is not None:
            await self.internal_server.start()
        self.followers_controller.start()
        self.cleaner.start()
        if self.sc_dispatcher is not None:
            self.sc_dispatcher.start()
        if self.monitoring is not None:
            await self.monitoring.start()

    async def run(self) -> None:
        await self.public_server.run()

    async def stop(self) -> None:
        if self.monitoring is not None:
            await self.monitoring.stop()
        if self.sc_dispatcher is not None:
            await self.sc_dispatcher.stop()
        await self.cleaner.stop()
        await self.followers_controller.stop()
        if self.internal_server is not None:
            await self.internal_server.stop()
        await self.public_server.stop()
        self.ctx.close()
