"""Per-partition execution state over a shared compiled chain.

Each ``(topic, partition)`` owns its chain's aggregate carry —
HBM-resident on its placement group's device across batches — plus a
consumer-offset tracker wired to the replica layer's
``OffsetPublisher`` LEO/HW machinery. The executor's single
``_device_carries`` slot generalizes here to a carry *bank*: one
compiled chain (one jit cache — partitions never recompile) whose
tiny constant-size carry state is swapped per partition around
dispatch. That swap is exactly the SSM-style chunked-scan trick
(arxiv 2603.09555): the inter-batch state is a few scalars, so keeping
it device-resident per partition costs nothing while saving the
host round-trip every batch.

Threading: like ``TpuChainExecutor`` itself, a runtime is driven by ONE
dispatcher at a time (the broker's stream loop is a single asyncio
thread; the bench is single-threaded). The ``partition.runtime`` lock
guards only the control-plane maps (states, plan, rebalance counters) —
never a device dispatch — so the placement layer's lock edges stay
trivially static (PR-7 analyzer) and a rebalance from a health callback
thread is safe against state lookups.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional

import jax

from fluvio_tpu.analysis.lockwatch import make_lock
from fluvio_tpu.partition.placement import (
    PlacementPlan,
    device_for_group,
    make_partition_mesh,
    partition_key,
)
from fluvio_tpu.telemetry import TELEMETRY
from fluvio_tpu.types import OffsetPublisher

logger = logging.getLogger(__name__)


class PartitionOffsets:
    """Per-partition consumer-offset tracking on the replica buses.

    ``advance`` moves a partition's committed consumer offset (monotonic
    — a shed or quarantined-and-held slice simply never calls it, so
    offsets can never pass unserved records) and wakes that partition's
    ``OffsetPublisher`` listeners: the same bus/select-loop machinery
    the stream-fetch path already runs on replica LEO/HW
    (spu/replica.py), reused for the consumer side so fetch loops stay
    exact per partition.
    """

    def __init__(self):
        self._lock = make_lock("partition.offsets")
        self._committed: Dict[str, int] = {}
        self._publishers: Dict[str, OffsetPublisher] = {}
        self._leaders: Dict[str, object] = {}

    def publisher(self, key: str) -> OffsetPublisher:
        with self._lock:
            pub = self._publishers.get(key)
            if pub is None:
                pub = self._publishers[key] = OffsetPublisher(
                    self._committed.get(key, -1)
                )
            return pub

    def attach_leader(self, key: str, leader) -> None:
        """Bind the partition to its leader replica state (LEO/HW
        source); ``lag`` and the failover replay read through it. The
        pair also registers with the streaming lag engine, so the
        partition's consumer lag joins the SLO/admission control loop
        (telemetry/lag.py)."""
        with self._lock:
            self._leaders[key] = leader
        if TELEMETRY.enabled:
            from fluvio_tpu.telemetry import lag as lag_mod

            lag_mod.track_stream(key, leader)

    def leader(self, key: str):
        with self._lock:
            return self._leaders.get(key)

    def committed(self, key: str) -> int:
        with self._lock:
            return self._committed.get(key, -1)

    def advance(self, key: str, next_offset: int) -> bool:
        """Commit served progress; refuses to move backwards."""
        with self._lock:
            cur = self._committed.get(key, -1)
            if next_offset <= cur:
                return False
            self._committed[key] = next_offset
            pub = self._publishers.get(key)
        if pub is not None:
            pub.update(next_offset)
        if TELEMETRY.enabled:
            from fluvio_tpu.telemetry import lag as lag_mod

            lag_mod.note_commit(key, next_offset)
        return True

    def lag(self, key: str) -> Optional[int]:
        """Unserved records behind the leader's LEO (None: no leader)."""
        with self._lock:
            leader = self._leaders.get(key)
            cur = self._committed.get(key, -1)
        if leader is None:
            return None
        return max(0, leader.leo() - max(cur, 0))

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._committed)


@dataclass
class PartitionState:
    """One partition's execution state on its device group."""

    key: str
    group: int
    device_carries: object = None  # jit-native carry pytree (HBM-resident)
    host_carries: List[tuple] = field(default_factory=list)
    # per-instance (accumulator, window_start) for the interpreter
    # ladder (spill rerun / quarantine exactness)
    inst_state: Optional[List[tuple]] = None
    carry_device: object = None  # where device_carries currently live
    batches: int = 0


class PartitionRuntime:
    """Partition-parallel execution over one compiled chain.

    ``executor`` is the shared :class:`TpuChainExecutor`; ``chain`` (a
    ``SmartModuleChainInstance``, optional) additionally enables the
    full engine ladder per partition (`process_chain`: spill rerun,
    retry, quarantine — the failover replay path).
    """

    def __init__(
        self,
        executor,
        plan: PlacementPlan,
        mesh=None,
        chain=None,
        devices=None,
    ):
        if executor is None:
            raise ValueError("PartitionRuntime needs a TPU chain executor")
        self._executor = executor
        self._chain = chain
        self._mesh = (
            mesh
            if mesh is not None
            else make_partition_mesh(plan.n_groups, devices=devices)
        )
        self._lock = make_lock("partition.runtime")
        self._plan = plan
        self._states: Dict[str, PartitionState] = {}
        self.offsets = PartitionOffsets()
        # seed state: what a brand-new partition starts from — the
        # chain SPEC's initial aggregates, NOT the live executor's
        # carries (which may already hold another stream's sums if the
        # runtime wraps a warmed executor)
        self._seed_carries = executor.initial_carries()
        self._seed_inst = (
            self._seed_instance_state(chain) if chain is not None else None
        )
        self._stateful = bool(executor.agg_configs)

    def _seed_instance_state(self, chain) -> List[tuple]:
        """The interpreter mirror of the seed carries: aggregate
        instances derive from their spec carry slot (mirrors
        executor._sync_instances), stateless instances keep whatever
        they hold (their state is unused)."""
        from fluvio_tpu.smartmodule.types import SmartModuleKind

        out: List[tuple] = []
        slot = 0
        for inst in chain.instances:
            if (
                inst.kind == SmartModuleKind.AGGREGATE
                and slot < len(self._seed_carries)
            ):
                acc, win, has = self._seed_carries[slot]
                window_ms = self._executor.agg_configs[slot][1]
                out.append(
                    (
                        str(acc).encode("ascii"),
                        win if (has and window_ms) else None,
                    )
                )
                slot += 1
            else:
                out.append((inst.accumulator, inst._window_start))
        return out

    # -- control plane -------------------------------------------------------

    @property
    def plan(self) -> PlacementPlan:
        with self._lock:
            return self._plan

    @property
    def mesh(self):
        return self._mesh

    @property
    def rebalances(self) -> int:
        with self._lock:
            return self._plan.rebalances

    @property
    def moves(self) -> int:
        with self._lock:
            return self._plan.moves

    def partitions(self) -> List[str]:
        with self._lock:
            return sorted(self._states)

    def device_of(self, group: int):
        return device_for_group(self._mesh, group)

    def _state(self, key: str) -> PartitionState:
        with self._lock:
            st = self._states.get(key)
            if st is None:
                plan = self._plan
                if key not in plan.assignments:
                    plan = plan.with_partitions([key])
                    self._plan = plan
                st = PartitionState(
                    key=key,
                    group=plan.assignments[key],
                    host_carries=list(self._seed_carries),
                    inst_state=(
                        list(self._seed_inst)
                        if self._seed_inst is not None
                        else None
                    ),
                )
                self._states[key] = st
            return st

    def fail_group(self, group: int) -> int:
        """Leader-loss rebalance: move the group's partitions onto the
        survivors (deterministic — placement.rebalance). Carries
        migrate lazily: the next swap-in device_puts them onto the new
        group's device. Returns the number of partitions moved."""
        moved = 0
        with self._lock:
            self._plan = self._plan.rebalance(group)
            for st in self._states.values():
                new_group = self._plan.assignments.get(st.key, st.group)
                if new_group != st.group:
                    st.group = new_group
                    moved += 1
        logger.warning(
            "device group %d failed: rebalanced %d partitions", group, moved
        )
        return moved

    def move_partition(self, topic: str, partition: int, group: int) -> bool:
        """Voluntary single-partition move (the rebalancer's actuator).

        Unlike :meth:`fail_group` the vacated group stays schedulable.
        Carries migrate lazily — the next ``_swap_in`` device_puts them
        onto the new group's device, so the move itself touches no
        device state and is safe from a control thread. Returns whether
        the assignment actually changed.
        """
        key = partition_key(topic, partition)
        with self._lock:
            plan = self._plan
            if key not in plan.assignments:
                plan = plan.with_partitions([key])
            new_plan = plan.move_partition(key, group)
            changed = new_plan is not plan
            self._plan = new_plan
            st = self._states.get(key)
            if st is not None and changed:
                st.group = group
        return changed

    # -- carry bank ----------------------------------------------------------

    def _swap_in(self, st: PartitionState) -> tuple:
        """Point the shared executor at this partition's state; returns
        the previous state for ``_swap_out``. Carries placed on another
        group's device migrate here (group failure rebalance)."""
        ex = self._executor
        prev = (
            ex._device_carries,
            ex.carries,
            ex.span_chain,
            ex.partition_tag,
        )
        dev = self.device_of(st.group)
        carries = st.device_carries
        if carries is not None and st.carry_device is not dev:
            carries = jax.device_put(carries, dev)
        # record the device ACTUALLY used for this swap (a concurrent
        # fail_group can move st.group mid-dispatch; the carries the
        # dispatch commits still live on THIS device, and the next
        # swap-in migrates them from here)
        st.carry_device = dev
        ex._device_carries = carries
        ex.carries = list(st.host_carries)
        # chain@partition identity: SLO families, admission keys, and
        # the down-* link telemetry all hang off this suffix
        ex.set_partition_identity(st.key, st.group)
        return prev

    def _capture(self, st: PartitionState) -> None:
        # carry_device stays whatever _swap_in set — never re-derived
        # from the (concurrently rebalanceable) st.group
        ex = self._executor
        st.device_carries = ex._device_carries
        st.host_carries = list(ex.carries)
        st.batches += 1
        # device-memory ledger: this partition's aggregate carry bank
        # is HBM-resident between dispatches. Re-acquire on the same
        # key is a resize, so per-batch capture stays balanced; a
        # persistent owner, so quiesce drains do not expect zero.
        if TELEMETRY.enabled:
            carries = st.device_carries
            if carries is None:
                TELEMETRY.mem_release(("carry", st.key))
            else:
                # the carry is a pytree of tiny arrays, not one buffer
                nbytes = sum(
                    int(getattr(leaf, "nbytes", 0) or 0)
                    for leaf in jax.tree_util.tree_leaves(carries)
                )
                TELEMETRY.mem_acquire(
                    "carry_bank", ("carry", st.key), nbytes
                )

    def _swap_out(self, prev: tuple) -> None:
        ex = self._executor
        (
            ex._device_carries,
            ex.carries,
            ex.span_chain,
            ex.partition_tag,
        ) = prev

    def carry_snapshot(self, topic: str, partition: int) -> List[tuple]:
        """Host-side carry tuple for this partition — the tiny
        constant-size state the failover replica replicates."""
        st = self._state(partition_key(topic, partition))
        if st.device_carries is not None:
            host = jax.device_get(st.device_carries)
            return [
                (int(acc), int(win), bool(has)) for acc, win, has in host
            ]
        return [tuple(c) for c in st.host_carries]

    def seed_partition(
        self,
        topic: str,
        partition: int,
        host_carries: Iterable[tuple],
        inst_state: Optional[List[tuple]] = None,
    ) -> None:
        """Install replicated carry state (follower promotion): the
        partition resumes from the committed snapshot, device-resident
        again on its owning group at the next dispatch."""
        st = self._state(partition_key(topic, partition))
        st.device_carries = None
        st.carry_device = None
        # the promoted follower holds only the host snapshot — the old
        # device-resident bank (if any) is garbage now; retire its
        # ledger booking with it
        TELEMETRY.mem_release(("carry", st.key))
        st.host_carries = [tuple(c) for c in host_carries]
        if inst_state is not None:
            st.inst_state = [tuple(s) for s in inst_state]
        elif self._chain is not None:
            # derive the interpreter mirror from the carries, exactly
            # like executor._sync_instances: aggregate instances take
            # (accumulator, window_start) from their carry slot,
            # stateless instances keep their seed state
            from fluvio_tpu.smartmodule.types import SmartModuleKind

            mirror: List[tuple] = []
            slot = 0
            for inst, seed in zip(self._chain.instances, self._seed_inst):
                if (
                    inst.kind == SmartModuleKind.AGGREGATE
                    and slot < len(st.host_carries)
                ):
                    acc, win, has = st.host_carries[slot]
                    window_ms = self._executor.agg_configs[slot][1]
                    mirror.append(
                        (
                            str(acc).encode("ascii"),
                            win if (has and window_ms) else None,
                        )
                    )
                    slot += 1
                else:
                    mirror.append(tuple(seed))
            st.inst_state = mirror

    # -- data plane ----------------------------------------------------------

    def dispatch(self, topic: str, partition: int, buf):
        """Stage + dispatch one partition batch on its device group
        (async — device compute proceeds; `finish` collects). Carries
        commit at dispatch, so interleaving partitions is exact."""
        st = self._state(partition_key(topic, partition))
        prev = self._swap_in(st)
        try:
            with jax.default_device(self.device_of(st.group)):
                handle = self._executor.dispatch_buffer(buf)
        finally:
            self._capture(st)
            self._swap_out(prev)
        return handle

    def finish(self, topic: str, partition: int, buf, handle):
        """Block on one partition batch's results.

        Stateful chains re-enter the partition's carry slot first: the
        executor's failure ladders (fan-out retry, spill restore)
        mutate the live carry pointer, and those writes must land on
        THIS partition's state, not a neighbor's.
        """
        st = self._state(partition_key(topic, partition))
        if not self._stateful:
            # stateless: no carries to protect, but the fetch-side
            # telemetry (down-* variants, enc-ratio declines) still
            # books under the partition identity
            ex = self._executor
            prev = ex.set_partition_identity(st.key, st.group)
            try:
                return ex.finish_buffer(buf, handle)
            finally:
                ex.restore_partition_identity(prev)
        prev = self._swap_in(st)
        try:
            with jax.default_device(self.device_of(st.group)):
                return self._executor.finish_buffer(buf, handle)
        finally:
            self._capture(st)
            self._swap_out(prev)

    def process(self, topic: str, partition: int, buf):
        return self.finish(
            topic, partition, buf, self.dispatch(topic, partition, buf)
        )

    def process_interleaved(self, items, depth: int = 2):
        """Pipelined generator over ``(topic, partition, buf)`` triples.

        Partition A's batch k+1 dispatches (H2D + device compute in the
        background, on A's group) while partition B's batch k downloads
        — the multi-partition mirror of ``process_stream``.
        """
        items = list(items)
        if self._stateful and self._executor._fanout:
            # same guard as process_stream: a fan-out overflow retry at
            # finish must roll carries back, impossible once a later
            # same-partition batch dispatched against them — serialize
            depth = 0
        inflight: List[tuple] = []
        try:
            for topic, part, buf in items:
                handle = self.dispatch(topic, part, buf)
                inflight.append((topic, part, buf, handle))
                while len(inflight) > max(depth, 0):
                    t, p, b, h = inflight.pop(0)
                    yield (t, p, b, self.finish(t, p, b, h))
            while inflight:
                t, p, b, h = inflight.pop(0)
                yield (t, p, b, self.finish(t, p, b, h))
        except BaseException:
            for t, p, b, h in inflight:
                if self._stateful:
                    # the discard's carry restore must land in THIS
                    # partition's slot, not whatever the executor
                    # currently points at
                    st = self._state(partition_key(t, p))
                    prev = self._swap_in(st)
                    try:
                        self._executor.discard_dispatch(h)
                    finally:
                        self._capture(st)
                        self._swap_out(prev)
                else:
                    self._executor.discard_dispatch(h)
            raise

    def process_chain(self, topic: str, partition: int, inp):
        """Full engine ladder for one partition slab: fused attempt,
        spill rerun, bounded retry, dead-letter quarantine — with the
        chain's python-instance state ALSO swapped per partition so the
        interpreter path and quarantine rollback stay exact. This is
        the promotion-replay entry point (failover.py) and the
        stateful broker path's per-partition mirror."""
        if self._chain is None:
            raise ValueError("process_chain needs the runtime built with chain=")
        st = self._state(partition_key(topic, partition))
        chain = self._chain
        prev = self._swap_in(st)
        prev_inst = [
            (i.accumulator, i._window_start) for i in chain.instances
        ]
        if st.inst_state is not None:
            for inst, (acc, win) in zip(chain.instances, st.inst_state):
                inst.accumulator = acc
                inst._window_start = win
        try:
            with jax.default_device(self.device_of(st.group)):
                out = chain.process(inp)
        finally:
            self._capture(st)
            st.inst_state = [
                (i.accumulator, i._window_start) for i in chain.instances
            ]
            for inst, (acc, win) in zip(chain.instances, prev_inst):
                inst.accumulator = acc
                inst._window_start = win
            self._swap_out(prev)
        return out

    # -- observability -------------------------------------------------------

    def to_dict(self) -> dict:
        with self._lock:
            plan = self._plan
            states = {
                k: {"group": st.group, "batches": st.batches}
                for k, st in sorted(self._states.items())
            }
        return {
            "plan": plan.to_dict(),
            "partitions": states,
            "offsets": self.offsets.snapshot(),
            "mesh": {
                "axes": dict(zip(self._mesh.axis_names, self._mesh.devices.shape)),
            },
        }


class BrokerPartitionGate:
    """The broker-side placement seam (armed by ``FLUVIO_PARTITIONS``).

    Broker stream chains already hold per-stream executors (one stream
    == one partition), so the carries are naturally per-partition
    there; what the broker gains from the partition layer is PLACEMENT
    — each stream's dispatches run on its partition's device group —
    and the ``chain@partition`` identity on spans/admission/down-link
    telemetry. ``scope`` wraps a slice dispatch in exactly that.
    """

    def __init__(self, n_groups: int, rules=None, devices=None):
        from fluvio_tpu.partition.placement import (
            make_partition_mesh,
            plan_placement,
            rules_from_env,
            validate_rules,
        )

        self._lock = make_lock("partition.gate")
        rules = rules if rules is not None else rules_from_env()
        # fail at gate resolution (server start logs it and disarms),
        # never on the first slice of some topic
        validate_rules(rules, n_groups)
        self._plan = plan_placement(rules, [], n_groups)
        self._mesh = make_partition_mesh(n_groups, devices=devices)

    @property
    def plan(self) -> PlacementPlan:
        with self._lock:
            return self._plan

    @property
    def mesh(self):
        return self._mesh

    def group_for(self, topic: str, partition: int) -> int:
        key = partition_key(topic, partition)
        with self._lock:
            if key not in self._plan.assignments:
                self._plan = self._plan.with_partitions([key])
            return self._plan.assignments[key]

    def fail_group(self, group: int) -> None:
        with self._lock:
            self._plan = self._plan.rebalance(group)

    def move_partition(self, topic: str, partition: int, group: int) -> bool:
        """Voluntary move (rebalancer actuator): reroute the stream's
        dispatch device starting from its next slice. The source group
        stays schedulable. Returns whether the assignment changed."""
        key = partition_key(topic, partition)
        with self._lock:
            plan = self._plan
            if key not in plan.assignments:
                plan = plan.with_partitions([key])
            new_plan = plan.move_partition(key, group)
            changed = new_plan is not plan
            self._plan = new_plan
        return changed

    def scope(self, topic: str, partition: int, executor):
        """Context manager: partitioned identity + group device for one
        slice's dispatches on a broker stream's executor."""
        return _GateScope(self, topic, partition, executor)


class _GateScope:
    def __init__(self, gate: BrokerPartitionGate, topic, partition, executor):
        self._gate = gate
        self._topic = topic
        self._partition = partition
        self._ex = executor
        self._prev = None
        self._dev_ctx = None

    def __enter__(self):
        group = self._gate.group_for(self._topic, self._partition)
        key = partition_key(self._topic, self._partition)
        self._prev = self._ex.set_partition_identity(key, group)
        self._dev_ctx = jax.default_device(
            device_for_group(self._gate.mesh, group)
        )
        self._dev_ctx.__enter__()
        return group

    def __exit__(self, *exc):
        try:
            self._dev_ctx.__exit__(*exc)
        finally:
            self._ex.restore_partition_identity(self._prev)
        return False
