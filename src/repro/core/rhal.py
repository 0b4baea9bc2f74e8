"""Runtime Hardware Abstraction Layer — the ``hal_driver_t`` vtable.

The paper isolates all hardware heterogeneity behind a C struct of function
pointers covering four primitive families (register ops, DMA, sync, cache
coherency). The TPU adaptation keeps the strict boundary — the executor only
ever calls vtable slots — and re-bases the primitives on the XLA execution
model:

  register ops       -> buffer-table ops (alloc/free/bind_const)
  initiate/wait DMA  -> host<->device transfers (device_put / device_get)
  dispatch           -> compute-op dispatch (per-op eager, or traced-fused)
  poll/fence         -> block_until_ready barriers
  cache flush/inval  -> buffer donation hints (XLA owns coherency; donation
                        is the user-visible control point on TPU)

Two drivers ship:
  * ``EagerDriver``  — dispatches every op as its own device executable with
    a host sync in between: the OS-mediated analogue (per-op fixed cost,
    like Vitis AI's ioctl-per-DMA path).
  * ``TraceDriver``  — records the same calls symbolically so the executor
    can stage one fused XLA program per RCB program: the baremetal analogue
    (one dispatch per step, zero host round-trips inside).

Two memory/transfer extensions back the compiled data-movement path
(DESIGN.md §6):

  * ``DeviceArena`` — one up-front device slab suballocated by offset with
    RIMFS-matching 128 B alignment. On TPU/XLA the slab is *modeled* (XLA
    owns physical device memory), but the arena reproduces the paper's
    deterministic offset discipline: the linker's residency plan, the
    high-water mark, fragmentation and the free-list are all real and
    testable, and on a raw-pointer backend the same offsets would index an
    actual slab.
  * split-phase DMA — ``dma_async`` returns a ``DmaTicket`` immediately;
    ``dma_wait`` redeems it. Issue and wait are separate vtable slots so
    the linker can hoist issues ahead of use (prefetch H2D of op *k+1*
    under op *k*'s compute) and sink waits to the drain point (D2H of op
    *k−1* completes under op *k*). The blocking ``initiate_dma``/
    ``wait_dma`` pair remains the interpreted per-op baseline.
"""
from __future__ import annotations

import bisect
import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import oplib
from repro.core.integrity import IntegrityConfig, IntegrityError, payload_crc
from repro.core.rcb import Op

ARENA_ALIGN = 128                 # matches rimfs.ALIGN: one DMA lane quantum
DEFAULT_ARENA_BYTES = 1 << 30     # modeled slab size for the eager driver


class ArenaError(RuntimeError):
    pass


class DmaError(RuntimeError):
    """Split-phase DMA protocol violation (e.g. a ticket redeemed twice)."""


class TileFailure(RuntimeError):
    """A tile group's hardware went away mid-program (fault injection /
    elasticity). Raised by every vtable slot of a killed ``TileGroup``."""


class DeviceArena:
    """Offset-based suballocator over one up-front device slab.

    First-fit over a sorted free-list with neighbour coalescing on free;
    every range is aligned to ``align`` (128 B — RIMFS lane width). With
    ``debug=True`` every alloc/free re-verifies the full invariant set: live
    ranges pairwise disjoint, live and free ranges disjoint, everything
    aligned and in-bounds.
    """

    def __init__(self, capacity: int = DEFAULT_ARENA_BYTES,
                 align: int = ARENA_ALIGN, debug: bool = False):
        if capacity <= 0 or capacity % align:
            raise ArenaError(f"capacity {capacity} not a multiple of {align}")
        self.capacity = capacity
        self.align = align
        self.debug = debug
        self._free: list[tuple[int, int]] = [(0, capacity)]  # (offset, size)
        self._live: dict[int, int] = {}                      # offset -> size
        self.bytes_in_use = 0
        self.high_water = 0
        self.n_allocs = 0
        self.poisoned = False          # quarantined after a watchdog kill

    # ------------------------------------------------------------------ api
    def _round(self, nbytes: int) -> int:
        nbytes = max(1, int(nbytes))
        return (nbytes + self.align - 1) // self.align * self.align

    def quarantine(self) -> None:
        """Poison the arena: a hung/killed owner may have left any live
        range half-written, so no range is handed out again until the
        pinned contents are re-validated against RIMFS CRCs
        (``TileMesh.revive``) — ``alloc`` raises until then."""
        self.poisoned = True

    def clear_quarantine(self) -> None:
        self.poisoned = False

    def alloc(self, nbytes: int) -> int:
        """Reserve an aligned range; returns its slab offset."""
        if self.poisoned:
            # raised as TileFailure so the stage-re-queue machinery
            # treats a quarantined arena exactly like the dead group
            # that owns it (failover to a survivor, not a hard error)
            raise TileFailure(
                "arena quarantined: owner was preempted as hung — "
                "re-validate resident contents before reuse")
        size = self._round(nbytes)
        for i, (off, avail) in enumerate(self._free):
            if avail >= size:
                if avail == size:
                    self._free.pop(i)
                else:
                    self._free[i] = (off + size, avail - size)
                self._live[off] = size
                self.bytes_in_use += size
                self.high_water = max(self.high_water, self.bytes_in_use)
                self.n_allocs += 1
                if self.debug:
                    self.check()
                return off
        raise ArenaError(
            f"arena exhausted: need {size}B, in_use={self.bytes_in_use}B "
            f"of {self.capacity}B ({len(self._free)} free ranges)")

    def free(self, offset: int) -> None:
        """Return a range to the free-list (coalescing with neighbours)."""
        size = self._live.pop(offset, None)
        if size is None:
            raise ArenaError(f"free of unallocated offset {offset}")
        self.bytes_in_use -= size
        i = bisect.bisect_left(self._free, (offset, 0))
        # coalesce right
        if i < len(self._free) and offset + size == self._free[i][0]:
            size += self._free[i][1]
            self._free.pop(i)
        # coalesce left
        if i > 0 and self._free[i - 1][0] + self._free[i - 1][1] == offset:
            offset, size = (self._free[i - 1][0],
                            self._free[i - 1][1] + size)
            self._free[i - 1] = (offset, size)
        else:
            self._free.insert(i, (offset, size))
        if self.debug:
            self.check()

    def live_ranges(self) -> list:
        return sorted((o, s) for o, s in self._live.items())

    def check(self) -> None:
        """Assert the full disjointness/alignment invariant set."""
        ranges = ([(o, s, "live") for o, s in self._live.items()]
                  + [(o, s, "free") for o, s in self._free])
        ranges.sort()
        prev_end, prev_kind = 0, None
        covered = 0
        for off, size, kind in ranges:
            if off % self.align or size % self.align:
                raise ArenaError(f"unaligned {kind} range ({off}, {size})")
            if off < prev_end:
                raise ArenaError(
                    f"{kind} range at {off} overlaps previous "
                    f"{prev_kind} range ending at {prev_end}")
            prev_end, prev_kind = off + size, kind
            covered += size
        if prev_end > self.capacity or covered != self.capacity:
            raise ArenaError("arena ranges do not tile the slab")

    def reset(self) -> None:
        self._free = [(0, self.capacity)]
        self._live.clear()
        self.bytes_in_use = 0


@dataclasses.dataclass
class DmaTicket:
    """Split-phase transfer handle: issued by ``dma_async``, redeemed by
    ``dma_wait``. ``prefetched`` marks issues the linker hoisted ahead of
    the consuming op (the overlap-eligible bytes telemetry counts).
    ``redeemed`` is flipped by the first ``dma_wait`` — a second redemption
    raises ``DmaError`` (on a raw-pointer backend the descriptor is recycled
    at wait time, so a double wait would observe another transfer's state).

    Integrity plane (DESIGN.md §11): ``crc`` is the CRC-32 of the source
    payload stamped at ISSUE time, before the engine touches the bytes;
    ``src`` retains the source buffer so a mismatch at redeem can re-issue
    the transfer in place (bounded by the driver's
    ``integrity.dma_retries``) before escalating to ``IntegrityError``.
    ``crc is None`` marks an unverifiable transfer (d2h pulls, symbolic
    trace tickets) — those redeem unchecked; device-side corruption is
    instead caught by RIMFS CRC re-validation.
    """
    buf: Any
    direction: str
    nbytes: int
    prefetched: bool = False
    redeemed: bool = False
    crc: Optional[int] = None
    src: Any = None
    retries: int = 0

    def redeem(self) -> None:
        """Mark redemption; exactly-once is enforced, not assumed."""
        if self.redeemed:
            raise DmaError(
                f"DmaTicket({self.direction}, {self.nbytes}B) redeemed "
                f"twice — dma_wait already consumed this descriptor")
        self.redeemed = True


@dataclasses.dataclass
class HalDriver:
    """The vtable. Integrating a new backend == filling these slots."""
    name: str
    alloc: Callable[[tuple, str], Any]
    free: Callable[[Any], None]
    bind_const: Callable[[Any], Any]
    initiate_dma: Callable[[Any, str], Any]     # (host_buf, direction) -> buf
    wait_dma: Callable[[Any], Any]
    dispatch_compute: Callable[[Op, list, dict], Any]
    collective: Callable[[str, Any, dict], Any]
    fence: Callable[[list], None]
    poll: Callable[[Any], bool]
    donate: Callable[[Any], Any]
    stats: dict = dataclasses.field(default_factory=dict)
    # Optional compiled-dispatch slot (core/linker.py): resolve one opcode
    # to a specialized positional handler ``fn(*srcs) -> out`` ONCE at link
    # time, so the hot loop pays no table lookup / decode / sync per op.
    # ``None`` means the backend has no compiled path; the linker then falls
    # back to per-op ``dispatch_compute``.
    link_compute: Optional[Callable[[Op, dict], Callable]] = None
    # Optional split-phase DMA slots (compiled data-movement path). A
    # backend filling both lets the linker pipeline transfers; ``None``
    # falls back to the blocking initiate_dma/wait_dma pair.
    dma_async: Optional[Callable[[Any, str], DmaTicket]] = None
    dma_wait: Optional[Callable[[DmaTicket], Any]] = None
    # Optional batched issue: one engine call for a whole transfer stream
    # (the prefetch prologue, a resident-image upload). Falls back to
    # per-buffer dma_async when absent.
    dma_async_batch: Optional[Callable[[list, str], list]] = None
    # Optional device arena backing alloc/free and RIMFS residency.
    arena: Optional[DeviceArena] = None
    # Integrity policy: DMA payload CRC stamping/verification + bounded
    # retry (DESIGN.md §11). Shared by reference with the closures the
    # factory builds, so flipping ``integrity.enabled`` at runtime (the
    # CRC-on/off benchmark row) takes effect immediately.
    integrity: IntegrityConfig = dataclasses.field(
        default_factory=IntegrityConfig)
    # Per-driver compiled-handler memo (core/linker.py): identical
    # (opcode, attrs) sites across links — e.g. every tile of a
    # partitioned program — share ONE specialized handler instead of
    # re-resolving/re-staging per link.
    link_cache: dict = dataclasses.field(default_factory=dict)

    def _count(self, key: str, n: int = 1):
        self.stats[key] = self.stats.get(key, 0) + n


def _on_device(buf, device) -> bool:
    """True iff a jax Array is wholly resident on ``device``."""
    try:
        return buf.devices() == {device}
    except Exception:
        return False


def _nbytes_of(shape, dtype) -> int:
    n = 1
    for s in shape:
        n *= int(s)
    return n * np.dtype(dtype).itemsize


# ---------------------------------------------------------------------------
# Eager driver (OS-mediated analogue): one device round-trip per primitive.
# ---------------------------------------------------------------------------

def make_eager_driver(device: Optional[jax.Device] = None,
                      arena_bytes: int = DEFAULT_ARENA_BYTES,
                      debug_arena: bool = False) -> HalDriver:
    device = device or jax.devices()[0]
    arena = DeviceArena(arena_bytes, debug=debug_arena)
    # id(buf) -> arena offset for arena-backed allocations. An id is only
    # recorded while its buffer is registered, and re-allocation overwrites
    # the entry, so recycled ids cannot alias a stale offset.
    offsets: dict[int, int] = {}

    def _register(buf, nbytes):
        offsets[id(buf)] = arena.alloc(nbytes)
        return buf

    def alloc(shape, dtype):
        d._count("alloc")
        buf = jax.device_put(jnp.zeros(shape, jnp.dtype(dtype)), device)
        return _register(buf, _nbytes_of(shape, dtype))

    def free(buf):
        d._count("free")
        off = offsets.pop(id(buf), None)
        if off is not None:
            arena.free(off)         # offset really returns to the free-list
        if hasattr(buf, "delete"):
            try:
                buf.delete()
            except Exception:
                pass

    def bind_const(value):
        return jax.device_put(jnp.asarray(value), device)

    def initiate_dma(host_buf, direction):
        d._count("dma")
        d._count("dma_bytes", int(getattr(host_buf, "nbytes", 0)))
        if direction == "d2h":
            return np.asarray(host_buf)            # device -> host pull
        return jax.device_put(jnp.asarray(host_buf), device)

    def wait_dma(buf):
        d._count("dma_wait")
        return jax.block_until_ready(buf) if hasattr(buf, "block_until_ready") \
            else buf

    def _stamp(ticket, host_buf):
        """Stamp the source payload's CRC-32 onto the ticket at ISSUE
        time (before any engine touch) and retain the source buffer for
        in-place retry. d2h is never stamped: the reference bytes only
        exist device-side, and reading them at issue would force the
        host sync split-phase DMA exists to avoid — device-side
        corruption is covered by RIMFS CRC re-validation instead."""
        if d.integrity.enabled and ticket.direction != "d2h":
            ticket.crc = payload_crc(host_buf)
            ticket.src = host_buf
        return ticket

    def dma_async(host_buf, direction, prefetched=False):
        """Issue half: returns a ticket immediately, no host sync.

        h2d/d2d enqueue a device_put (asynchronous under XLA); d2h starts
        the device->host copy in the background. Completion is observed at
        ``dma_wait`` (d2h materialization) or, for device-side consumers,
        by XLA data-flow ordering — the host blocks only at FENCE/exit.
        """
        nbytes = int(getattr(host_buf, "nbytes", 0))
        d._count("dma_async")
        d._count("dma_bytes", nbytes)
        if prefetched:
            d._count("dma_overlapped_bytes", nbytes)
        if direction == "d2h":
            if hasattr(host_buf, "copy_to_host_async"):
                host_buf.copy_to_host_async()
            return DmaTicket(host_buf, "d2h", nbytes, prefetched)
        if direction == "d2d" and isinstance(host_buf, jax.Array) \
                and _on_device(host_buf, device):
            # modeled inter-tile hop: the source already lives on this
            # physical device, so the "transfer" is pure accounting — a
            # device_put here is host-side overhead per cut edge that a
            # zero-copy interconnect would never pay. Bytes/stats are
            # still counted above; cross-device or host-sourced d2d
            # still stages through device_put below.
            return _stamp(DmaTicket(host_buf, direction, nbytes,
                                    prefetched), host_buf)
        buf = jax.device_put(jnp.asarray(host_buf), device)
        return _stamp(DmaTicket(buf, direction, nbytes, prefetched),
                      host_buf)

    def dma_wait_(ticket):
        d._count("dma_ticket_wait")
        ticket.redeem()                            # double-wait raises
        if ticket.direction == "d2h":
            return np.asarray(ticket.buf)          # materialize on host
        if ticket.crc is None or not d.integrity.enabled:
            return ticket.buf                      # ordered by data flow
        # endpoint verification: delivered payload vs issue-time CRC,
        # with a bounded in-place re-issue from the retained source
        # before escalating (DESIGN.md §11)
        d._count("dma_crc_checked")
        buf = ticket.buf
        for attempt in range(d.integrity.dma_retries + 1):
            if payload_crc(buf) == ticket.crc:
                if attempt:
                    ticket.retries = attempt
                    d._count("dma_retry_recovered")
                ticket.buf = buf
                return buf
            d._count("dma_crc_mismatch")
            if attempt >= d.integrity.dma_retries:
                break
            d._count("dma_retry")
            buf = jax.device_put(jnp.asarray(ticket.src), device)
        raise IntegrityError(
            f"DMA payload CRC mismatch ({ticket.direction}, "
            f"{ticket.nbytes}B) after {d.integrity.dma_retries} "
            f"in-place retries", kind="dma_crc")

    def dma_async_batch(host_bufs, direction, prefetched=False):
        """One engine call for a whole transfer stream: n buffers move
        under a single descriptor (paper §5.3 batching), paying the
        issue fixed cost once instead of once per block."""
        sizes = [int(getattr(h, "nbytes", 0)) for h in host_bufs]
        d._count("dma_async", len(host_bufs))
        d._count("dma_batch")
        d._count("dma_bytes", sum(sizes))
        if prefetched:
            d._count("dma_overlapped_bytes", sum(sizes))
        if direction == "d2h":
            for h in host_bufs:
                if hasattr(h, "copy_to_host_async"):
                    h.copy_to_host_async()
            return [DmaTicket(h, "d2h", nb, prefetched)
                    for h, nb in zip(host_bufs, sizes)]
        bufs = jax.device_put(list(host_bufs), device)
        return [_stamp(DmaTicket(b, direction, nb, prefetched), h)
                for b, h, nb in zip(bufs, host_bufs, sizes)]

    def dispatch_compute(op, srcs, attrs):
        d._count("dispatch")
        out = oplib.compute(op, srcs, attrs)
        return jax.block_until_ready(out)          # per-op host sync

    def collective(kind, x, attrs):
        d._count("collective")
        return x                                    # single-device eager

    def fence(bufs):
        d._count("fence")
        for b in bufs:
            if hasattr(b, "block_until_ready"):
                b.block_until_ready()

    def poll(buf):
        d._count("poll")
        return True

    def donate(buf):
        return buf

    def link_compute(op, attrs):
        # Compiled dispatch: one jitted executable per (op, attrs) site,
        # staged once at link time.  Calls hit XLA's cached fast path and
        # dispatch asynchronously — the per-op host sync of the interpreted
        # eager path is replaced by syncs at FENCE ops / program exit (the
        # paper's move: per-op fixed cost paid once per stream).
        if op in oplib.OP_KERNELS:
            # Kernel opcodes resolve through the registry so the linked
            # handler picks up autotuned block params and the pallas→ref
            # fallback ladder (kernels/registry.py); the registry's own
            # wrappers are already jitted.
            from repro.kernels import registry
            return registry.linked_handler(oplib.OP_KERNELS[op], attrs)
        fn = oplib.lookup(op)

        def handler(*srcs):
            return fn(srcs, attrs)
        # the program's name in a device trace: jit_rcb_<opcode>
        handler.__name__ = handler.__qualname__ = \
            f"rcb_{Op(op).name.lower()}"
        return jax.jit(handler)

    d = HalDriver("eager", alloc, free, bind_const, initiate_dma,
                  wait_dma, dispatch_compute, collective, fence, poll, donate,
                  link_compute=link_compute, dma_async=dma_async,
                  dma_wait=dma_wait_, dma_async_batch=dma_async_batch,
                  arena=arena)
    return d


# ---------------------------------------------------------------------------
# Trace driver (baremetal analogue): records ops symbolically for fusion.
# ---------------------------------------------------------------------------

def make_trace_driver() -> HalDriver:
    """Dispatch slots operate on tracers; no device sync anywhere. The
    executor stages the whole RCB program through this driver inside one
    ``jax.jit``, yielding a single fused executable."""

    def alloc(shape, dtype):
        return jnp.zeros(shape, jnp.dtype(dtype))

    def free(buf):
        return None

    def bind_const(value):
        return jnp.asarray(value)

    def initiate_dma(host_buf, direction):
        return jnp.asarray(host_buf)

    def wait_dma(buf):
        return buf                                  # no sync under trace

    def dma_async(host_buf, direction, prefetched=False):
        # symbolic ticket: the staged program IS the overlap (XLA schedules
        # transfers and compute from one dataflow graph)
        return DmaTicket(jnp.asarray(host_buf), direction, 0, prefetched)

    def dma_wait_(ticket):
        ticket.redeem()                            # double-wait raises
        return ticket.buf

    def dma_async_batch(host_bufs, direction, prefetched=False):
        return [DmaTicket(jnp.asarray(h), direction, 0, prefetched)
                for h in host_bufs]

    def dispatch_compute(op, srcs, attrs):
        d._count("dispatch")
        return oplib.compute(op, srcs, attrs)       # stays symbolic

    def collective(kind, x, attrs):
        return x

    def fence(bufs):
        return None

    def poll(buf):
        return True

    def donate(buf):
        return buf

    def link_compute(op, attrs):
        # Under trace everything is symbolic already; the specialized
        # handler is just the pre-resolved oplib entry (no jit, no sync).
        if op in oplib.OP_KERNELS:
            from repro.kernels import registry
            return registry.linked_handler(oplib.OP_KERNELS[op], attrs)
        fn = oplib.lookup(op)
        return lambda *srcs: fn(srcs, attrs)

    d = HalDriver("trace_xla", alloc, free, bind_const, initiate_dma,
                  wait_dma, dispatch_compute, collective, fence, poll, donate,
                  link_compute=link_compute, dma_async=dma_async,
                  dma_wait=dma_wait_, dma_async_batch=dma_async_batch)
    return d


# ---------------------------------------------------------------------------
# Tile mesh (multi-tile-group execution, DESIGN.md §7)
# ---------------------------------------------------------------------------

_GUARDED_SLOTS = ("alloc", "free", "bind_const", "initiate_dma", "wait_dma",
                  "dispatch_compute", "collective", "fence", "poll",
                  "dma_async", "dma_wait", "dma_async_batch")


@dataclasses.dataclass
class TileGroup:
    """One tile group: an independent HalDriver (own arena, own DMA
    engines, own stats) plus a liveness flag the mesh's fault model flips.
    """
    gid: int
    driver: HalDriver
    alive: bool = True


def _guard_group(group: TileGroup) -> None:
    """Wrap every vtable slot of the group's driver so a killed group
    raises ``TileFailure`` at the next hardware touch — the modeled
    analogue of a tile array segment dropping off the interconnect.
    The liveness flag is read at CALL time, so programs linked before the
    failure (including their per-site compiled handlers) fail too."""
    driver = group.driver

    def guard(fn):
        def wrapped(*args, **kwargs):
            if not group.alive:
                raise TileFailure(f"tile group {group.gid} is down")
            return fn(*args, **kwargs)
        return wrapped

    for slot in _GUARDED_SLOTS:
        fn = getattr(driver, slot)
        if fn is not None:
            setattr(driver, slot, guard(fn))
    link_compute = driver.link_compute
    if link_compute is not None:
        driver.link_compute = lambda op, attrs: guard(link_compute(op,
                                                                   attrs))


class TileMesh:
    """N modeled tile-group drivers with inter-tile split-phase streams.

    The paper runs ResNet-18 over a 28-tile AIE array with tile groups
    pipelining layer stages; here each group is an independent RHAL driver
    (own ``DeviceArena``, own DMA counters) and cut-edge activations move
    between groups through split-phase ``DmaTicket`` streams — issued the
    moment the producer stage completes, redeemed when the consumer stage
    starts, so the transfer rides under whatever executes in between.
    ``edge_stats`` accounts movement bytes per (src, dst) cut edge.
    """

    def __init__(self, n_groups: int, driver_factory=None,
                 arena_bytes: int = DEFAULT_ARENA_BYTES):
        if n_groups < 1:
            raise ValueError(f"need >= 1 tile group, got {n_groups}")
        factory = driver_factory or (
            lambda gid: make_eager_driver(arena_bytes=arena_bytes))
        self._factory = factory        # retained for partial reshapes
        self.groups: list[TileGroup] = []
        for gid in range(n_groups):
            group = TileGroup(gid, factory(gid))
            _guard_group(group)
            self.groups.append(group)
        # (src_gid, dst_gid) -> {"bytes", "transfers", "syms"}
        self.edge_stats: dict[tuple, dict] = {}
        # gid of the group currently executing a partitioned stage.
        # Written only by the dispatcher thread (partition.execute), read
        # by the watchdog to target a hung dispatch's group — a benign
        # single-writer race by design.
        self.active_gid: Optional[int] = None

    # ----------------------------------------------------------------- api
    @property
    def n_groups(self) -> int:
        return len(self.groups)

    @property
    def gids(self) -> range:
        return range(len(self.groups))

    def group(self, gid: int) -> TileGroup:
        return self.groups[gid]

    def alive(self, gid: int) -> bool:
        return self.groups[gid].alive

    def kill(self, gid: int) -> None:
        """Fault injection / watchdog preemption: the group fails at its
        next hardware touch, and its arena is QUARANTINED — a killed
        owner may have left any buffer half-written, so no range is
        handed out again until ``revive`` re-validates the pinned
        contents against RIMFS CRCs."""
        group = self.groups[gid]
        group.alive = False
        if group.driver.arena is not None:
            group.driver.arena.quarantine()

    def revive(self, gid: int, rimfs=None) -> None:
        """Bring a killed group back. With ``rimfs`` given, every file
        the group's driver holds resident is CRC-compared against the
        image before the arena's quarantine lifts — a corrupted weight
        copy raises ``IntegrityError`` instead of silently serving.
        Without ``rimfs`` (no residency to check) the quarantine lifts
        unverified — fault-injection tests own that risk explicitly."""
        group = self.groups[gid]
        arena = group.driver.arena
        if arena is not None and arena.poisoned:
            if rimfs is not None:
                entry = rimfs._resident.get(id(group.driver))
                ri = entry[1] if entry is not None \
                    and entry[0]() is group.driver else None
                if ri is not None and not ri.revalidate():
                    raise IntegrityError(
                        f"tile group {gid}: resident weights fail CRC "
                        f"re-validation — arena stays quarantined",
                        kind="residency_crc")
            arena.clear_quarantine()
        group.alive = True

    def spawn_replacement(self, gid: int) -> TileGroup:
        """Build (but do NOT install) a fresh guarded tile group for slot
        ``gid`` — the expensive half of a *partial reshape*. The caller
        binds / pins / links against the new group's driver off the
        dispatcher thread, then splices it in with ``install_group``
        between requests. The incumbent group keeps serving (or keeps
        failing over) untouched until the splice."""
        group = TileGroup(gid, self._factory(gid))
        _guard_group(group)
        return group

    def install_group(self, group: TileGroup) -> TileGroup:
        """Splice a replacement group into its slot, returning the
        incumbent. O(1) pointer swap — the partial-reshape analogue of
        the whole-mesh flip, intended to run as a dispatcher control op
        so no stage is mid-flight across the swap. Surviving groups'
        drivers (and their pinned weights and DMA counters) are not
        touched."""
        if not (0 <= group.gid < len(self.groups)):
            raise ValueError(f"group gid {group.gid} outside mesh "
                             f"[0, {len(self.groups)})")
        old = self.groups[group.gid]
        self.groups[group.gid] = group
        return old

    @property
    def primary(self) -> HalDriver:
        """First live group's driver (weight residency / serving anchor)."""
        for g in self.groups:
            if g.alive:
                return g.driver
        raise TileFailure("no live tile group in mesh")

    def stream(self, sym: str, buf, src_gid: int, dst_gid: int):
        """Issue one cut-edge transfer src->dst, split-phase.

        Returns a ``DmaTicket`` the consumer group redeems (``dma_wait``)
        when its stage starts — or the transferred buffer directly when
        the destination driver has no async DMA slots (blocking fallback).
        Movement bytes are accounted per directed edge either way.
        """
        driver = self.groups[dst_gid].driver
        if driver.dma_async is not None:
            out = driver.dma_async(buf, "d2d", prefetched=True)
        else:
            out = driver.wait_dma(driver.initiate_dma(buf, "d2d"))
        # account only issues that actually went out (a dead destination
        # raises above — a phantom transfer must not inflate the edge)
        st = self.edge_stats.setdefault(
            (src_gid, dst_gid), {"bytes": 0, "transfers": 0, "syms": set()})
        st["bytes"] += int(getattr(buf, "nbytes", 0))
        st["transfers"] += 1
        st["syms"].add(sym)
        return out

    def moved_bytes(self) -> int:
        return sum(st["bytes"] for st in self.edge_stats.values())
