"""Elastic fleet operations — RTPM as the serving control plane.

PRs 3-5 built the recovery primitives in isolation: heartbeat fault
verdicts (rtpm), stage re-queue on tile failure (partition.execute),
graceful drain with explicit hand-back (ServiceLoop/server), per-group-
count partition caching (executor) and zero-byte RIMFS re-binds
(residency). This module composes them into one self-healing controller
(DESIGN.md §10):

  * ``FleetController.tick`` runs the observe -> decide -> act loop:
    dispatcher queue depth + admission backlog, deadline-miss (shed)
    rate, and heartbeat verdicts (including the per-worker EWMA
    straggler signal) feed a hysteresis scaler that walks the mesh
    ladder (2 -> 4 -> 8 -> 2) and a healer that replaces meshes with
    dead groups.
  * All mutations of dispatcher-owned state (``server.mesh``,
    ``server._bound``, ``platform.rimfs``) happen as **control ops on
    the dispatcher thread** (``InferenceServer.run_on_dispatcher``):
    the dispatcher executes one item at a time, so a control op runs
    with no request mid-flight — the single-owner model is the drain
    point, and a flip is atomic *between* requests by construction.
    Expensive work (partitioning, tile binds, weight pinning, linking)
    runs OFF the dispatcher beforehand; the flip itself is a pointer
    swap.
  * Hot weight swap: mount + CRC-verify the new image in the
    background, bind a **shadow** program against it, probe it with a
    golden input bit-compared against the live binding's answer, pre-
    warm the current mesh's tile binds from the new image, then flip
    atomically. Probe mismatch (or a post-swap deadline-miss spike
    during the probation window) rolls back to the old binding — whose
    residency was never unpinned, so rollback re-uploads **zero
    bytes**. Events: ``swap_started / swap_probed / swap_committed /
    swap_rolled_back`` (plus ``swap_finalized`` when probation ends).
  * Mesh cache: previously-built meshes are kept (bounded) per group
    count, so a 2 -> 8 -> 2 cycle returns to the *original* drivers and
    their already-pinned weights — scaling back down moves zero weight
    bytes.

PR 10 adds the safe-rollout plane (DESIGN.md §14):

  * Canary A/B serving: ``FleetController.canary(image, fraction)``
    binds the new image as a shadow and installs a ``CanaryState`` on
    the server — the dispatcher hash-routes a deterministic fraction of
    live plain-RCB traffic through the shadow binding and bit-compares
    sampled outputs against the primary's. A sequential probability
    ratio test (SPRT) over the agree/disagree stream auto-promotes the
    image (atomic flip, old residency released) or auto-aborts it
    (shadow dropped, primary untouched) — probation driven by real
    request outputs, not shed-rate alone. A sampled request that
    DISAGREES is answered with the primary's bytes, so a bad canary
    never serves a byte it is known to have gotten wrong.
  * Partial reshapes: a dead or stage-EWMA-straggling tile group is
    replaced in place (``TileMesh.spawn_replacement`` + prewarm one
    tile + CRC re-validation + ``install_group`` splice between
    requests) instead of rebuilding the whole mesh — zero dropped
    work, zero re-uploaded weight bytes for surviving groups.
  * Swap probation is request-count based: a swap finalizes only after
    ``probation_requests`` real requests were served on the new
    binding, so an idle period can never silently pass probation.

The chaos harness (tests/chaos.py) drives all of this under live
traffic with injected faults and asserts zero failed client requests
and bit-identical outputs throughout.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import threading
import time
import zlib
from typing import Any, Optional

import numpy as np

from repro.core import partition as partition_mod
from repro.core import rbl as rbl_mod
from repro.core import rhal as rhal_mod
from repro.core import rimfs as rimfs_mod


class FleetError(RuntimeError):
    pass


@dataclasses.dataclass
class FleetConfig:
    """Control-loop policy knobs (hysteresis lives here, not in code)."""
    ladder: tuple = (2, 4, 8)          # mesh sizes the scaler walks
    min_groups: int = 2
    max_groups: int = 8
    scale_up_depth: int = 8            # queue depth that argues for growth
    scale_down_depth: int = 1          # ... and for shrinking
    scale_up_ticks: int = 2            # consecutive ticks before acting
    scale_down_ticks: int = 3
    miss_rate_up: float = 0.10         # shed fraction that argues for growth
    probation_ticks: int = 3           # post-swap minimum watch ticks
    probation_requests: int = 8        # served requests before finalize
    miss_spike: float = 0.25           # post-swap shed fraction -> rollback
    spike_min_window: int = 4          # min requests before judging a spike
    mesh_cache_cap: int = 4
    control_timeout: float = 60.0      # dispatcher flip wait
    probe_seed: int = 0xF1EE7          # golden-input generator seed
    finalize_unpin: bool = True        # release old image after probation
    # --- partial reshape (replace one group instead of a full heal) ---
    partial_reshape: bool = True
    straggler_ticks: int = 3           # consecutive slow verdicts -> replace
    stage_straggler_ratio: float = 2.5  # group stage-EWMA vs median -> slow
    stage_ewma_alpha: float = 0.3
    # --- canary A/B rollout (SPRT over per-request agreement) ---
    canary_fraction: float = 0.25      # traffic hash-routed to the shadow
    canary_sample_fraction: float = 1.0  # routed requests also dual-run
    canary_serve_shadow: bool = True   # serve shadow bytes when they agree
    canary_p_good: float = 0.995       # H_good: per-request agree prob
    canary_p_bad: float = 0.80         # H_bad: a broken image's agree prob
    canary_alpha: float = 0.05         # P(abort | image good)
    canary_beta: float = 0.05          # P(promote | image bad)
    canary_min_samples: int = 4
    canary_max_samples: int = 400      # forced verdict at the cap
    canary_token_threshold: float = 1.0  # int outputs: agree fraction >= thr


@dataclasses.dataclass
class _SwapState:
    """A committed swap under probation (rollback stays possible)."""
    old_rimfs: Any
    old_bound: Any
    new_rimfs: Any
    new_bound: Any
    shed_baseline: int
    served_baseline: int
    ticks: int = 0


def golden_inputs(program, seed: int = 0xF1EE7) -> dict:
    """Deterministic probe inputs for a service program: every swap
    probe, canary splice check and circuit-breaker half-open probe runs
    the same goldens, so their reference answers are comparable across
    bindings and across time."""
    rng = np.random.RandomState(seed)
    out = {}
    for name, t in program.tensors.items():
        if t.kind != "input":
            continue
        dt = np.dtype(t.dtype)
        if dt.kind in "iu":
            out[name] = rng.randint(0, 4, size=t.shape).astype(dt)
        else:
            out[name] = rng.randn(*t.shape).astype(dt)
    return out


class SPRT:
    """Wald's sequential probability ratio test over a Bernoulli
    agree/disagree stream (DESIGN.md §14).

    ``llr`` accumulates log P(obs | H_bad)/P(obs | H_good): an agreement
    drives it down (toward *promote*), a disagreement drives it sharply
    up (toward *abort*). With the default priors (p_good=0.995,
    p_bad=0.8, alpha=beta=0.05) one disagreement adds ~+3.7 while an
    agreement adds ~-0.2, so a clean canary promotes after ~14 agreed
    samples and a broken one aborts after 1-2 disagreements — without
    ever serving enough bad traffic to matter.
    """

    def __init__(self, p_good: float = 0.995, p_bad: float = 0.80,
                 alpha: float = 0.05, beta: float = 0.05,
                 min_samples: int = 4, max_samples: int = 400):
        self.min_samples = min_samples
        self.max_samples = max_samples
        self.llr = 0.0
        self.n = 0
        self.agrees = 0
        self._abort_at = math.log((1.0 - beta) / alpha)
        self._promote_at = math.log(beta / (1.0 - alpha))
        self._l_agree = math.log(p_bad / p_good)
        self._l_disagree = math.log((1.0 - p_bad) / (1.0 - p_good))

    def observe(self, agree: bool) -> None:
        self.n += 1
        if agree:
            self.agrees += 1
            self.llr += self._l_agree
        else:
            self.llr += self._l_disagree

    def verdict(self) -> Optional[str]:
        """"promote" | "abort" | None (keep sampling)."""
        if self.n < self.min_samples:
            return None
        if self.llr >= self._abort_at:
            return "abort"
        if self.llr <= self._promote_at:
            return "promote"
        if self.n >= self.max_samples:     # undecided at the cap: the
            return "abort"                 # image failed to prove itself
        return None

    def summary(self) -> dict:
        return {"n": self.n, "agrees": self.agrees,
                "disagrees": self.n - self.agrees,
                "llr": round(self.llr, 4), "verdict": self.verdict()}


class CanaryState:
    """Dispatcher-visible state of one canary rollout.

    Installed on ``server.canary`` via a control op; the dispatcher
    consults it per request (hash routing + sampling are pure functions
    of the request id, so the split is deterministic and replayable) and
    feeds agree/disagree bits back through ``record``. The controller
    polls ``sprt.verdict()`` from its tick and promotes/aborts."""

    def __init__(self, bound, fs, fraction: float, sprt: SPRT,
                 label: str = "", sample_fraction: float = 1.0,
                 serve_shadow: bool = True, token_threshold: float = 1.0):
        self.bound = bound
        self.fs = fs
        self.fraction = max(0.0, min(1.0, fraction))
        self.sprt = sprt
        self.label = label
        self.sample_fraction = max(0.0, min(1.0, sample_fraction))
        self.serve_shadow = serve_shadow
        self.token_threshold = token_threshold
        self.stats = {"routed": 0, "sampled": 0, "agree": 0,
                      "disagree": 0, "served_shadow": 0}

    @staticmethod
    def _hash(tag: bytes, rid: int) -> int:
        return zlib.crc32(tag + int(rid).to_bytes(8, "little")) % 10_000

    def routes(self, rid: int) -> bool:
        """Deterministic traffic split: same rid always lands on the
        same side, regardless of arrival order or thread."""
        return self._hash(b"route", rid) < int(self.fraction * 10_000)

    def samples(self, rid: int) -> bool:
        """Of the routed requests, which also dual-run the primary for
        an agree/disagree SPRT sample (independent hash stream)."""
        return self._hash(b"sample", rid) < int(
            self.sample_fraction * 10_000)

    def judge(self, primary: dict, shadow: dict) -> bool:
        """Bit-compare float outputs; integer (token) outputs may use an
        agreement-fraction threshold for sampled LM decode."""
        if set(primary) != set(shadow):
            return False
        for k in primary:
            a, b = np.asarray(primary[k]), np.asarray(shadow[k])
            if a.shape != b.shape or a.dtype != b.dtype:
                return False
            if a.dtype.kind in "iu" and self.token_threshold < 1.0:
                agree = float(np.mean(a == b)) if a.size else 1.0
                if agree < self.token_threshold:
                    return False
            elif not np.array_equal(a, b):
                return False
        return True

    def record(self, agree: bool) -> None:
        self.sprt.observe(agree)
        self.stats["sampled"] += 1
        self.stats["agree" if agree else "disagree"] += 1


class FleetController:
    """Observe -> decide -> drain -> reshape/swap -> resume.

    Owns NO request-path state: everything the dispatcher touches is
    flipped via control ops. The controller may run its ``tick`` from a
    background thread (``start``/``stop``) or be stepped manually for
    deterministic tests. All actions are idempotent with respect to the
    serving invariants: no accepted request is dropped, outputs stay
    bit-identical to the single-device reference, and every transition
    emits an event through the platform's unified dispatcher.
    """

    EVENTS = ("scale_started", "scale_complete", "heal_started",
              "heal_complete", "swap_started", "swap_probed",
              "swap_committed", "swap_rolled_back", "swap_finalized",
              "straggler_detected", "fleet_error",
              "canary_started", "canary_promoted", "canary_aborted",
              "reshape_started", "reshape_complete")

    def __init__(self, server, config: Optional[FleetConfig] = None):
        self.server = server
        self.cfg = config or FleetConfig()
        self.events: list = []          # (kind, payload) in emit order
        self.history: list = []         # per-tick reports
        self._mesh_cache: "collections.OrderedDict[int, Any]" = \
            collections.OrderedDict()
        if server.mesh is not None:
            self._mesh_cache[server.mesh.n_groups] = server.mesh
        self._swap: Optional[_SwapState] = None
        self._canary: Optional[CanaryState] = None
        self._up_streak = 0
        self._down_streak = 0
        self._stage_ewma: dict = {}     # gid -> EWMA stage busy seconds
        self._straggler_streak: dict = {"gid": None, "n": 0}
        self._last = {"shed": self._shed_total(),
                      "served": self._served_total()}
        self._lock = threading.RLock()  # serializes control actions
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        for kind in self.EVENTS:        # record every fleet event locally
            server.platform.events.register(
                kind, (lambda k: lambda p: self.events.append((k, p)))(kind))
        # per-group stage busy time feeds the straggler EWMA (partial
        # reshape policy); posted by partition.execute on the dispatcher
        server.platform.events.register("stage_complete", self._on_stage)

    def _on_stage(self, payload: dict) -> None:
        gid, dt = payload.get("group"), payload.get("seconds")
        if gid is None or dt is None:
            return
        a = self.cfg.stage_ewma_alpha
        prev = self._stage_ewma.get(gid)
        self._stage_ewma[gid] = dt if prev is None else \
            (1.0 - a) * prev + a * dt

    # ----------------------------------------------------------- telemetry
    def _post(self, kind: str, payload: dict) -> None:
        self.server.platform.post(kind, payload)

    def _shed_total(self) -> int:
        s = self.server.scheduler.shed_count
        eng = getattr(self.server, "engine", None)
        if eng is not None and eng.scheduler is not None:
            s += eng.scheduler.shed_count
        return s

    def _served_total(self) -> int:
        return len(self.server.platform.telemetry._lat)

    def observe(self) -> dict:
        """One control-loop observation: queue pressure, miss rate since
        the previous observation, heartbeat verdicts (the controller's
        poll beats live groups and registers dead ones silent — exactly
        the liveness sweep partition.execute performs), and mesh ground
        truth."""
        server = self.server
        depth = server._loop.depth() + server.scheduler.pending()
        shed, served = self._shed_total(), self._served_total()
        shed_d = shed - self._last["shed"]
        served_d = served - self._last["served"]
        self._last = {"shed": shed, "served": served}
        mesh = server.mesh
        mesh_dead: list = []
        if mesh is not None:
            hb = server.platform.heartbeats
            for gid in mesh.gids:
                if mesh.alive(gid):
                    # step 0 on purpose: pipeline stages beat with their
                    # stage index during execution, which differs across
                    # groups legitimately — the step-lag straggler rule
                    # is for same-step data-parallel workers, not stages
                    hb.beat(f"tile{gid}", 0)
                else:
                    hb.register_silent(f"tile{gid}")
            mesh_dead = [g for g in mesh.gids if not mesh.alive(g)]
        verdict = server.platform.heartbeats.check()
        lat = server.platform.telemetry.summary(warmup=0)
        return {"depth": depth, "shed_delta": shed_d,
                "served_delta": served_d,
                "miss_rate": shed_d / max(1, shed_d + served_d),
                "n_groups": mesh.n_groups if mesh is not None else 1,
                "mesh_dead": mesh_dead, "verdicts": verdict["verdicts"],
                "failed": verdict["failed"],
                "stragglers": verdict["stragglers"],
                "p99": lat.get("p99")}

    # -------------------------------------------------------------- policy
    def _ladder_up(self, cur: int) -> Optional[int]:
        for n in sorted(self.cfg.ladder):
            if cur < n <= self.cfg.max_groups:
                return n
        return None

    def _ladder_down(self, cur: int) -> Optional[int]:
        for n in sorted(self.cfg.ladder, reverse=True):
            if cur > n >= self.cfg.min_groups:
                return n
        return None

    def _stage_straggler(self, obs: dict) -> Optional[int]:
        """A group whose stage-busy EWMA is ``stage_straggler_ratio``x
        the median of its peers, for ``straggler_ticks`` consecutive
        observations, is a straggler — replace it in place."""
        cfg = self.cfg
        if obs["n_groups"] < 2 or len(self._stage_ewma) < obs["n_groups"]:
            return None
        ew = {g: self._stage_ewma[g] for g in range(obs["n_groups"])
              if g in self._stage_ewma}
        if len(ew) < 2:
            return None
        worst = max(ew, key=ew.get)
        peers = [v for g, v in ew.items() if g != worst]
        med = float(np.median(peers))
        if med > 0 and ew[worst] > cfg.stage_straggler_ratio * med:
            st = self._straggler_streak
            st["n"] = st["n"] + 1 if st["gid"] == worst else 1
            st["gid"] = worst
            if st["n"] >= cfg.straggler_ticks:
                return worst
        else:
            self._straggler_streak = {"gid": None, "n": 0}
        return None

    def decide(self, obs: dict) -> Optional[tuple]:
        """Pure policy: observation -> action (None = hold). Hysteresis
        via consecutive-tick streaks so one noisy sample never reshapes
        the mesh."""
        cfg = self.cfg
        if obs["mesh_dead"]:
            dead = tuple(obs["mesh_dead"])
            # one dead group in a multi-group mesh: splice in a single
            # replacement instead of rebuilding the world
            if cfg.partial_reshape and len(dead) == 1 and \
                    obs["n_groups"] > 1:
                return ("replace", dead[0], "dead")
            return ("heal", dead)
        slow = self._stage_straggler(obs)
        if slow is not None and cfg.partial_reshape:
            return ("replace", slow, "straggler")
        pressure_up = obs["depth"] >= cfg.scale_up_depth or \
            obs["miss_rate"] > cfg.miss_rate_up
        pressure_down = obs["depth"] <= cfg.scale_down_depth and \
            obs["shed_delta"] == 0
        if pressure_up:
            self._up_streak += 1
            self._down_streak = 0
        elif pressure_down:
            self._down_streak += 1
            self._up_streak = 0
        else:
            self._up_streak = self._down_streak = 0
        cur = obs["n_groups"]
        if self._up_streak >= cfg.scale_up_ticks:
            nxt = self._ladder_up(cur)
            if nxt is not None:
                return ("scale", nxt)
        if self._down_streak >= cfg.scale_down_ticks:
            nxt = self._ladder_down(cur)
            if nxt is not None:
                return ("scale", nxt)
        return None

    def tick(self) -> dict:
        """One full control-loop iteration; callable from tests for
        deterministic stepping or from the background thread."""
        with self._lock:
            obs = self.observe()
            report: dict = {"obs": obs, "action": None}
            tile_stragglers = [w for w in obs["stragglers"]
                               if w.startswith("tile")]
            if tile_stragglers:
                self._post("straggler_detected",
                           {"workers": tile_stragglers})
            if self._swap is not None:
                report["swap"] = self._probation(obs)
            if self._canary is not None:
                report["canary"] = self._canary_tick()
            action = self.decide(obs)
            if action is not None:
                report["action"] = action
                try:
                    if action[0] == "heal":
                        self.heal(dead=action[1])
                    elif action[0] == "scale":
                        self.scale_to(action[1])
                    elif action[0] == "replace":
                        try:
                            self.replace_group(action[1], reason=action[2])
                        except Exception as e:
                            # a failed splice must not strand a dead
                            # group: fall back to the full heal path
                            self._post("fleet_error",
                                       {"action": action,
                                        "error": repr(e),
                                        "fallback": "heal"})
                            self.heal()
                except Exception as e:
                    report["error"] = repr(e)
                    self._post("fleet_error",
                               {"action": action, "error": repr(e)})
            self.history.append(report)
            return report

    # ------------------------------------------------------------- scaling
    def _build_mesh(self, n: int):
        mesh = self._mesh_cache.get(n)
        if mesh is not None and all(mesh.alive(g) for g in mesh.gids):
            self._mesh_cache.move_to_end(n)
            return mesh, True
        self._mesh_cache.pop(n, None)   # never reuse a mesh with dead groups
        mesh = rhal_mod.TileMesh(n)
        return mesh, False

    def _prewarm(self, mesh, bound=None, rimfs=None) -> None:
        """Partition + bind + link + pin weights against the new mesh's
        drivers, OFF the dispatcher thread: by flip time the first
        request pays nothing. The per-tile bind caches and the
        per-group-count partition cache make this idempotent."""
        server = self.server
        bound = bound if bound is not None else server._bound
        rimfs = rimfs if rimfs is not None else server.platform.rimfs
        part = partition_mod.ensure_partition(bound, mesh.n_groups)
        partition_mod.prewarm(part, mesh, rimfs=rimfs)

    def _cache_mesh(self, mesh) -> None:
        self._mesh_cache[mesh.n_groups] = mesh
        self._mesh_cache.move_to_end(mesh.n_groups)
        while len(self._mesh_cache) > self.cfg.mesh_cache_cap:
            self._mesh_cache.popitem(last=False)

    def scale_to(self, n: int) -> dict:
        """Reshape the live mesh to ``n`` tile groups without dropping a
        request: pre-warm off-thread, flip on the dispatcher (between
        requests), resume. Returns the scale report."""
        with self._lock:
            server = self.server
            if server._bound is None:
                raise FleetError("cannot scale: server not provisioned")
            cur = server.mesh.n_groups if server.mesh is not None else 1
            if n == cur and server.mesh is not None:
                return {"from": cur, "to": n, "noop": True}
            t0 = time.perf_counter()
            self._post("scale_started", {"from": cur, "to": n})
            mesh, cached = self._build_mesh(n)
            self._prewarm(mesh)

            def flip():
                server.mesh = mesh
                return server._loop.depth()

            depth_at_flip = server.run_on_dispatcher(
                flip, timeout=self.cfg.control_timeout)
            if server.mesh is not None:
                self._cache_mesh(mesh)
            self._up_streak = self._down_streak = 0
            report = {"from": cur, "to": n, "cached_mesh": cached,
                      "depth_at_flip": depth_at_flip,
                      "seconds": time.perf_counter() - t0}
            self._post("scale_complete", report)
            return report

    def heal(self, dead: tuple = ()) -> dict:
        """Replace a mesh with dead groups by a fresh same-size mesh.
        In-flight stages already failed over to survivors (partition
        re-queue); healing restores full capacity for what follows."""
        with self._lock:
            server = self.server
            mesh = server.mesh
            if mesh is None:
                raise FleetError("no mesh to heal")
            n = mesh.n_groups
            dead = tuple(dead) or tuple(g for g in mesh.gids
                                        if not mesh.alive(g))
            t0 = time.perf_counter()
            self._post("heal_started", {"n_groups": n, "dead": list(dead)})
            self._mesh_cache.pop(n, None)      # poisoned: drop it
            if server.platform.rimfs is not None:
                # tile-group death integrity sweep: the fresh mesh must
                # only ever prewarm from a CRC-clean weight store
                server.platform.rimfs.fsck(strict=False)
                self._post("rimfs_fsck", {"phase": "heal"})
            fresh = rhal_mod.TileMesh(n)
            self._prewarm(fresh)

            def flip():
                server.mesh = fresh
                return True

            server.run_on_dispatcher(flip, timeout=self.cfg.control_timeout)
            self._cache_mesh(fresh)
            # dead tile workers answered their last poll long ago; revive
            # the names so the fresh mesh's groups aren't born "failed"
            for gid in fresh.gids:
                server.platform.heartbeats.beat(f"tile{gid}", 0)
            report = {"n_groups": n, "dead": list(dead),
                      "seconds": time.perf_counter() - t0}
            self._post("heal_complete", report)
            return report

    # ----------------------------------------------------- partial reshape
    def replace_group(self, gid: int, reason: str = "manual") -> dict:
        """Replace ONE tile group in place (partial reshape, §14).

        Off-thread: spawn a fresh driver for the slot, prewarm exactly
        that stage's tile bind against it (one stage's weight bytes move
        — survivors' arenas, bind caches and DMA counters are untouched)
        and CRC re-validate the new residency. On-thread: a one-pointer
        ``install_group`` splice between requests. Zero dropped work —
        in-flight stages on a dead group already failed over."""
        with self._lock:
            server = self.server
            mesh = server.mesh
            if mesh is None:
                raise FleetError("no mesh to reshape")
            if server._bound is None:
                raise FleetError("cannot reshape: server not provisioned")
            t0 = time.perf_counter()
            self._post("reshape_started", {"group": gid, "reason": reason})
            fs = server.platform.rimfs
            if fs is not None:
                # the replacement must only prewarm from a CRC-clean
                # store (same integrity sweep the full heal runs)
                fs.fsck(strict=False)
                self._post("rimfs_fsck", {"phase": "reshape"})
            fresh = mesh.spawn_replacement(gid)
            part = partition_mod.ensure_partition(server._bound,
                                                  mesh.n_groups)
            partition_mod.prewarm_group(part, fresh.driver, gid, rimfs=fs)
            if fs is not None:
                entry = fs._resident.get(id(fresh.driver))
                if entry is not None and not entry[1].revalidate():
                    raise FleetError(
                        f"replacement group {gid} failed CRC revalidation")

            def splice():
                mesh.install_group(fresh)
                return server._loop.depth()

            depth_at_splice = server.run_on_dispatcher(
                splice, timeout=self.cfg.control_timeout)
            # the slot's worker name is live again; reset its rhythm and
            # the straggler bookkeeping that targeted the old hardware
            server.platform.heartbeats.beat(f"tile{gid}", 0)
            self._stage_ewma.pop(gid, None)
            self._straggler_streak = {"gid": None, "n": 0}
            report = {"group": gid, "reason": reason,
                      "depth_at_splice": depth_at_splice,
                      "seconds": time.perf_counter() - t0}
            self._post("reshape_complete", report)
            return report

    # ------------------------------------------------------------ hot swap
    def _golden_inputs(self, program) -> dict:
        return golden_inputs(program, seed=self.cfg.probe_seed)

    def swap_weights(self, image: bytes, label: str = "") -> str:
        """Zero-downtime weight swap. Returns "committed" or
        "rolled_back". The old binding's residency survives until
        ``finalize`` (probation's end), so rollback is a pointer flip
        that re-uploads zero bytes."""
        with self._lock:
            server = self.server
            if server._bound is None:
                raise FleetError("cannot swap: server not provisioned")
            if self._swap is not None:
                raise FleetError("swap already in probation; finalize or "
                                 "roll back first")
            self._post("swap_started",
                       {"label": label, "bytes": len(image)})
            try:
                new_fs = rimfs_mod.mount(image)
                new_fs.verify_image()
            except Exception as e:
                self._post("swap_rolled_back",
                           {"label": label, "reason": f"mount: {e}"})
                return "rolled_back"
            program = server.platform.program
            shadow = rbl_mod.bind(program, rimfs=new_fs)
            golden = self._golden_inputs(program)
            # reference answer from the LIVE binding, on the dispatcher
            # (so it reflects exactly what clients are being served)
            ref = server.run_on_dispatcher(
                lambda: server._infer(golden),
                timeout=self.cfg.control_timeout)
            from repro.core.executor import Executor
            probe = Executor().run(shadow, inputs=golden, rimfs=new_fs)
            probe = {k: np.asarray(v) for k, v in probe.items()}
            ok = set(probe) == set(ref) and all(
                probe[k].shape == ref[k].shape
                and np.array_equal(probe[k], ref[k]) for k in ref)
            self._post("swap_probed", {"label": label, "ok": ok})
            if not ok:
                self._post("swap_rolled_back",
                           {"label": label, "reason": "probe mismatch"})
                return "rolled_back"
            if server.mesh is not None:
                # pin the new image into the live mesh's arenas BEFORE
                # the flip — alongside the old image, never displacing it
                self._prewarm(server.mesh, bound=shadow, rimfs=new_fs)

            def flip():
                old = (server.platform.rimfs, server._bound)
                server.platform.rimfs = new_fs
                server._bound = shadow
                return old

            old_rimfs, old_bound = server.run_on_dispatcher(
                flip, timeout=self.cfg.control_timeout)
            self._swap = _SwapState(
                old_rimfs=old_rimfs, old_bound=old_bound,
                new_rimfs=new_fs, new_bound=shadow,
                shed_baseline=self._shed_total(),
                served_baseline=self._served_total())
            self._post("swap_committed", {"label": label})
            return "committed"

    def _probation(self, obs: dict) -> dict:
        """Post-swap watch: a deadline-miss spike rolls the swap back
        automatically; a quiet window finalizes it.

        Finalization is REQUEST-count gated, not wall-clock gated: the
        new binding must have served ``probation_requests`` real
        requests (plus ``probation_ticks`` ticks as a floor) before the
        old image's residency is released. An idle fleet therefore never
        silently passes probation — zero traffic means rollback stays a
        zero-byte pointer flip indefinitely."""
        swap = self._swap
        swap.ticks += 1
        shed = self._shed_total() - swap.shed_baseline
        served = self._served_total() - swap.served_baseline
        window = shed + served
        rate = shed / max(1, window)
        if window >= self.cfg.spike_min_window and \
                rate > self.cfg.miss_spike:
            self.rollback(reason=f"miss_spike: {rate:.2f} over "
                          f"{window} requests")
            return {"state": "rolled_back", "miss_rate": rate,
                    "served": served}
        if swap.ticks >= self.cfg.probation_ticks and \
                served >= self.cfg.probation_requests:
            self.finalize_swap()
            return {"state": "finalized", "miss_rate": rate,
                    "served": served}
        return {"state": "probation", "tick": swap.ticks,
                "served": served, "miss_rate": rate}

    def rollback(self, reason: str = "manual") -> None:
        """Flip back to the pre-swap binding. The old residency was kept
        pinned through probation, so this moves zero weight bytes."""
        with self._lock:
            swap = self._swap
            if swap is None:
                raise FleetError("no swap to roll back")
            server = self.server

            def flip_back():
                server.platform.rimfs = swap.old_rimfs
                server._bound = swap.old_bound
                return True

            server.run_on_dispatcher(flip_back,
                                     timeout=self.cfg.control_timeout)
            swap.new_rimfs.release()
            self._swap = None
            self._post("swap_rolled_back", {"reason": reason})

    def finalize_swap(self) -> None:
        """End probation: the new image is trusted; release the old
        image's device residency (configurable)."""
        with self._lock:
            swap = self._swap
            if swap is None:
                return
            freed = 0
            if self.cfg.finalize_unpin and \
                    swap.old_rimfs is not swap.new_rimfs:
                freed = swap.old_rimfs.release()
            self._swap = None
            self._post("swap_finalized", {"freed_bytes": freed})

    # -------------------------------------------------------------- canary
    def canary(self, image: bytes, fraction: Optional[float] = None,
               label: str = "", sample_fraction: Optional[float] = None,
               serve_shadow: Optional[bool] = None) -> str:
        """Start a canary A/B rollout of ``image`` (DESIGN.md §14).

        Mount + CRC-verify the image, bind it as a shadow, prewarm the
        live mesh from it (alongside the primary — never displacing it),
        then install a ``CanaryState`` on the dispatcher: a hash-routed
        ``fraction`` of plain-RCB traffic executes on the shadow, and a
        ``sample_fraction`` of THAT also dual-runs the primary to feed
        the SPRT an agree/disagree bit. A sampled disagreement is always
        answered with the primary's bytes, so with the default
        ``sample_fraction=1.0`` a broken canary serves zero wrong bytes
        before the SPRT aborts it. Returns "started" or "aborted"."""
        with self._lock:
            server = self.server
            cfg = self.cfg
            if server._bound is None:
                raise FleetError("cannot canary: server not provisioned")
            if self._canary is not None:
                raise FleetError("canary already in flight; promote or "
                                 "abort it first")
            if self._swap is not None:
                raise FleetError("swap in probation; finalize or roll "
                                 "back before starting a canary")
            frac = cfg.canary_fraction if fraction is None else fraction
            self._post("canary_started",
                       {"label": label, "fraction": frac,
                        "bytes": len(image)})
            try:
                new_fs = rimfs_mod.mount(image)
                new_fs.verify_image()
            except Exception as e:
                self._post("canary_aborted",
                           {"label": label, "reason": f"mount: {e}"})
                return "aborted"
            program = server.platform.program
            shadow = rbl_mod.bind(program, rimfs=new_fs)
            if server.mesh is not None:
                self._prewarm(server.mesh, bound=shadow, rimfs=new_fs)
            state = CanaryState(
                bound=shadow, fs=new_fs, fraction=frac,
                sprt=SPRT(p_good=cfg.canary_p_good,
                          p_bad=cfg.canary_p_bad,
                          alpha=cfg.canary_alpha, beta=cfg.canary_beta,
                          min_samples=cfg.canary_min_samples,
                          max_samples=cfg.canary_max_samples),
                label=label,
                sample_fraction=cfg.canary_sample_fraction
                if sample_fraction is None else sample_fraction,
                serve_shadow=cfg.canary_serve_shadow
                if serve_shadow is None else serve_shadow,
                token_threshold=cfg.canary_token_threshold)

            def install():
                server.canary = state
                return True

            server.run_on_dispatcher(install,
                                     timeout=cfg.control_timeout)
            self._canary = state
            return "started"

    def _canary_tick(self) -> dict:
        """Poll the SPRT from the control loop and act on its verdict."""
        state = self._canary
        verdict = state.sprt.verdict()
        if verdict == "promote":
            self.promote_canary()
        elif verdict == "abort":
            self.abort_canary(reason="sprt")
        return dict(state.sprt.summary(), stats=dict(state.stats),
                    state=verdict or "sampling")

    def promote_canary(self) -> None:
        """The SPRT accepted H_good: flip the shadow to primary (atomic,
        between requests) and release the OLD image's residency. The
        shadow's weights were prewarmed at canary start, so promotion
        moves zero weight bytes."""
        with self._lock:
            state = self._canary
            if state is None:
                raise FleetError("no canary to promote")
            server = self.server

            def flip():
                server.canary = None
                old = (server.platform.rimfs, server._bound)
                server.platform.rimfs = state.fs
                server._bound = state.bound
                return old

            old_fs, _old_bound = server.run_on_dispatcher(
                flip, timeout=self.cfg.control_timeout)
            freed = 0
            if self.cfg.finalize_unpin and old_fs is not state.fs:
                freed = old_fs.release()
            self._canary = None
            self._post("canary_promoted",
                       dict(state.sprt.summary(), label=state.label,
                            stats=dict(state.stats), freed_bytes=freed))

    def abort_canary(self, reason: str = "manual") -> None:
        """The SPRT accepted H_bad (or the operator pulled the cord):
        detach the canary and drop the shadow's residency. The primary
        binding was never touched — abort moves zero primary bytes."""
        with self._lock:
            state = self._canary
            if state is None:
                raise FleetError("no canary to abort")
            server = self.server

            def clear():
                server.canary = None
                return True

            server.run_on_dispatcher(clear,
                                     timeout=self.cfg.control_timeout)
            state.fs.release()
            self._canary = None
            self._post("canary_aborted",
                       dict(state.sprt.summary(), label=state.label,
                            stats=dict(state.stats), reason=reason))

    # ----------------------------------------------------------- lifecycle
    def start(self, interval: float = 0.2) -> None:
        """Run ``tick`` on a background thread every ``interval``s."""
        if self._thread is not None:
            raise FleetError("controller already running")
        self._stop.clear()

        def loop():
            while not self._stop.wait(interval):
                try:
                    self.tick()
                except Exception as e:   # a bad tick must not kill the loop
                    self._post("fleet_error", {"error": repr(e)})

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="fleet-controller")
        self._thread.start()

    def stop(self) -> None:
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join(timeout=10)
        self._thread = None

    def summary(self) -> dict:
        kinds = collections.Counter(k for k, _ in self.events)
        return {"ticks": len(self.history), "events": dict(kinds),
                "mesh_cache": sorted(self._mesh_cache),
                "swap_in_probation": self._swap is not None,
                "canary": self._canary.sprt.summary()
                if self._canary is not None else None}
