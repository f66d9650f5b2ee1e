"""MeshController — elastic multi-host mesh membership (ISSUE 16).

PR 15 proved the honest 2-host mesh and measured its production weakness:
the stock ``jax.distributed`` world is all-or-nothing. Any task death
propagates a fatal coordination-service error that ABORTS every survivor
(measured rc=-6 inside ``PollForError``, no Python frame on the stack),
so a host kill forced a full survivor restart (71.8 s on the emulated mesh).
This module is the replacement failure-domain owner:

- **Evidence convergence.** A peer is declared dead only when independent
  signals agree: heartbeat lapse on the shared board, a
  :class:`~stl_fusion_tpu.resilience.PeerCircuitBreaker` stuck open, the
  orchestrator's ``peer-dead`` flag, or a round-deadline overrun (the
  wedged-collective tell). Each signal carries a weight; death needs the
  sum to reach ``evidence_threshold`` — a heartbeat lapse alone (e.g. a
  DCN partition window) never kills a member, which is exactly what the
  ``mesh_partition`` chaos scenario certifies.
- **Counted degrade, never silent, never downtime.** On convergence the
  controller records ``mesh_degraded`` in the ResilienceEvents ledger,
  abandons the wedged world in-process
  (:func:`~.multihost.teardown_world` — the survivor process NEVER
  restarts; the blocked dispatch thread is a documented zombie), and the
  caller keeps serving its local shards eager/single-host while the
  re-form runs.
- **Re-form ladder.** Survivors re-elect a coordinator through the shared
  rendezvous board: the lowest-ranked survivor publishes a *call* (new
  epoch, member order, fresh coordinator port) with O_EXCL atomicity;
  every other survivor polls for it, and takes over publishing after a
  rank-staggered timeout if the caller-elect is itself dead. World
  formation retries on a jittered, capped, exponential backoff — every
  attempt counted (``mesh_reform_attempt`` / ``mesh_reform_failed`` /
  ``mesh_reform_ok``), no retry invisible.
- **Live JOIN.** A joiner writes a board request and polls for the first
  call that names it; members absorb pending joiners at the next round
  boundary by re-forming to N+1 (``mesh_join_absorbed``) and rebalancing
  shards onto the joiner via the ShardMap/warm-restore machinery the
  caller owns.

The controller is deliberately jax-free: world mechanics arrive through a
``WorldOps`` adapter (:class:`JaxWorldOps` in production, fakes in unit
tests), and time/randomness are injected so every ladder transition is
deterministic under test.
"""
from __future__ import annotations

import json
import os
import random
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

from ..resilience.events import ResilienceEvents, global_events

__all__ = [
    "EVIDENCE_WEIGHTS",
    "JaxWorldOps",
    "MeshController",
    "MeshReformError",
    "PeerEvidence",
    "RendezvousBoard",
]

#: independent death signals and how much each one is worth. The
#: orchestrator flag is authoritative (the process was SIGKILLed by the
#: chaos driver / supervisor — weight 2 converges alone); the soft signals
#: need a second opinion, so a lone heartbeat lapse (partition window) or
#: a lone slow round (GC pause) never evicts a live member.
EVIDENCE_WEIGHTS: Dict[str, int] = {
    "heartbeat_lapse": 1,
    "breaker_open": 1,
    "deadline_overrun": 1,
    "peer_dead_flag": 2,
}


class MeshReformError(RuntimeError):
    """The re-form ladder ran out of rungs without forming a world."""


@dataclass
class PeerEvidence:
    """Accumulated death evidence for one peer: distinct signal kinds,
    each recorded once until the peer's slate is cleared by a successful
    re-form (or a rejoin)."""

    peer: str
    kinds: Dict[str, float] = field(default_factory=dict)  # kind -> at

    def add(self, kind: str, at: float) -> bool:
        if kind not in EVIDENCE_WEIGHTS:
            raise ValueError(f"unknown evidence kind {kind!r}")
        if kind in self.kinds:
            return False
        self.kinds[kind] = at
        return True

    @property
    def score(self) -> int:
        return sum(EVIDENCE_WEIGHTS[k] for k in self.kinds)

    def snapshot(self) -> dict:
        return {"peer": self.peer, "score": self.score, "kinds": dict(self.kinds)}


class RendezvousBoard:
    """Shared-directory rendezvous: heartbeats, orchestrator flags, join
    requests, and re-form *calls*. Every write is atomic (tmp + replace,
    or O_EXCL for the single-writer call files) — the PR 15 lesson that a
    reader polling on existence must never observe a torn file."""

    def __init__(self, directory: str):
        self.directory = directory
        os.makedirs(directory, exist_ok=True)

    def _path(self, name: str) -> str:
        return os.path.join(self.directory, name)

    def _put(self, name: str, payload: dict) -> None:
        path = self._path(name)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as fp:
            json.dump(payload, fp)
        os.replace(tmp, path)

    def _get(self, name: str) -> Optional[dict]:
        try:
            with open(self._path(name)) as fp:
                return json.load(fp)
        except (OSError, json.JSONDecodeError):
            return None

    # ---------------------------------------------------------- heartbeats
    def beat(self, member: str, at: float) -> None:
        self._put(f"hb-{member}.json", {"member": member, "at": at})

    def last_beat(self, member: str) -> Optional[float]:
        rec = self._get(f"hb-{member}.json")
        return None if rec is None else float(rec.get("at", 0.0))

    # ------------------------------------------------------ orchestrator flag
    def flag_dead(self, member: str, why: str = "") -> None:
        self._put(f"dead-{member}.json", {"member": member, "why": why})

    def dead_flagged(self, member: str) -> bool:
        return os.path.exists(self._path(f"dead-{member}.json"))

    def clear_dead_flag(self, member: str) -> None:
        try:
            os.unlink(self._path(f"dead-{member}.json"))
        except OSError:
            pass

    # ------------------------------------------------------------- joins
    def request_join(self, member: str, at: float) -> None:
        self._put(f"join-{member}.json", {"member": member, "at": at})

    def pending_joins(self) -> List[str]:
        out = []
        for name in sorted(os.listdir(self.directory)):
            if name.startswith("join-") and name.endswith(".json"):
                rec = self._get(name)
                if rec is not None:
                    out.append(rec["member"])
        return out

    def clear_join(self, member: str) -> None:
        try:
            os.unlink(self._path(f"join-{member}.json"))
        except OSError:
            pass

    # --------------------------------------------------------- telemetry
    def put_telemetry(self, member: str, payload: dict) -> None:
        """One member's latest MetricsRegistry snapshot (ISSUE 18): the
        fleet-metrics channel that keeps working through a degrade window,
        because file rendezvous needs no formed world. Atomic like every
        board write — a scraper mid-merge never reads a torn snapshot."""
        self._put(f"telemetry-{member}.json", payload)

    def read_telemetry(self) -> Dict[str, dict]:
        """member → latest snapshot payload, for the aggregating host."""
        out: Dict[str, dict] = {}
        for name in sorted(os.listdir(self.directory)):
            if name.startswith("telemetry-") and name.endswith(".json"):
                rec = self._get(name)
                if rec is not None and rec.get("member"):
                    out[rec["member"]] = rec
        return out

    # ------------------------------------------------------------- calls
    def publish_call(
        self, epoch: int, members: Sequence[str], coordinator: str
    ) -> dict:
        """Single-writer world call for one epoch: O_EXCL create, so the
        re-election race (caller-elect vs takeover) has exactly one
        winner — the loser reads the winner's call."""
        payload = {
            "epoch": epoch,
            "members": list(members),
            "coordinator": coordinator,
        }
        path = self._path(f"call-{epoch}.json")
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as fp:
            json.dump(payload, fp)
        try:
            fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
        except FileExistsError:
            os.unlink(tmp)
            existing = self._get(f"call-{epoch}.json")
            if existing is None:
                raise  # torn loser-side read is impossible (writer is atomic)
            return existing
        os.close(fd)
        os.replace(tmp, path)
        return payload

    def read_call(self, epoch: int) -> Optional[dict]:
        rec = self._get(f"call-{epoch}.json")
        if rec is not None and "members" in rec and "coordinator" in rec:
            return rec
        return None

    def latest_call(self, min_epoch: int = 0) -> Optional[dict]:
        best: Optional[dict] = None
        for name in os.listdir(self.directory):
            if name.startswith("call-") and name.endswith(".json"):
                rec = self._get(name)
                if (
                    rec is not None
                    and rec.get("epoch", -1) >= min_epoch
                    and (best is None or rec["epoch"] > best["epoch"])
                ):
                    best = rec
        return best


class JaxWorldOps:
    """Production WorldOps: forms/detaches/tears down the real jax world
    (see :mod:`~.multihost`). ``form`` returns a
    :class:`~.multihost.MultiHostContext`."""

    def __init__(
        self,
        devices_per_host: int,
        *,
        init_timeout_s: int = 20,
        heartbeat_interval_s: int = 2,
        max_missing_heartbeats: int = 10,
    ):
        self.devices_per_host = devices_per_host
        self.init_timeout_s = init_timeout_s
        self.heartbeat_interval_s = heartbeat_interval_s
        self.max_missing_heartbeats = max_missing_heartbeats

    def form(self, members: Sequence[str], process_id: int, coordinator: str):
        from .multihost import MultiHostContext, form_world, teardown_world

        n = len(members)
        if n == 1:
            # the degrade rung: a plain local backend, no coordination
            # runtime at all (and no gloo config — the measured gotcha)
            teardown_world(rebuild_local=True)
            return MultiHostContext(
                process_id=0, n_hosts=1, devices_per_host=self.devices_per_host
            )
        form_world(
            n,
            process_id,
            coordinator,
            init_timeout_s=self.init_timeout_s,
            heartbeat_interval_s=self.heartbeat_interval_s,
            max_missing_heartbeats=self.max_missing_heartbeats,
        )
        return MultiHostContext(
            process_id=process_id,
            n_hosts=n,
            devices_per_host=self.devices_per_host,
            coordinator=coordinator,
        )

    def detach(self) -> bool:
        from .multihost import detach_world

        return detach_world()

    def teardown(self) -> None:
        from .multihost import teardown_world

        teardown_world(rebuild_local=True)


class MeshController:
    """Owns one host process's view of mesh membership end to end:
    evidence → counted degrade → coordinator re-election → re-form ladder
    → join absorption. See the module docstring for the state machine."""

    FORMING = "forming"
    SERVING = "serving"
    DEGRADED = "degraded"
    REFORMING = "reforming"

    def __init__(
        self,
        member_id: str,
        members: Sequence[str],
        board: RendezvousBoard,
        ops,
        *,
        events: Optional[ResilienceEvents] = None,
        evidence_threshold: int = 2,
        heartbeat_timeout_s: float = 5.0,
        reform_attempts: int = 6,
        backoff_base_s: float = 0.25,
        backoff_cap_s: float = 2.0,
        call_wait_s: float = 15.0,
        call_takeover_s: float = 3.0,
        poll_interval_s: float = 0.05,
        clock: Callable[[], float] = time.monotonic,
        wall_clock: Callable[[], float] = time.time,
        sleep: Callable[[float], None] = time.sleep,
        rng: Optional[random.Random] = None,
        pick_address: Optional[Callable[[], str]] = None,
    ):
        self.member_id = member_id
        self.members: List[str] = list(members)
        self.board = board
        self.ops = ops
        self.events = events if events is not None else global_events()
        self.evidence_threshold = evidence_threshold
        self.heartbeat_timeout_s = heartbeat_timeout_s
        self.reform_attempts = reform_attempts
        self.backoff_base_s = backoff_base_s
        self.backoff_cap_s = backoff_cap_s
        self.call_wait_s = call_wait_s
        self.call_takeover_s = call_takeover_s
        self.poll_interval_s = poll_interval_s
        self._clock = clock
        self._wall = wall_clock
        self._sleep = sleep
        self._rng = rng if rng is not None else random.Random()
        if pick_address is None:
            from .multihost import pick_coordinator

            pick_address = pick_coordinator
        self._pick_address = pick_address
        self.state = MeshController.FORMING
        self.epoch = 0
        self.world = None
        self.evidence: Dict[str, PeerEvidence] = {}
        self.degrades = 0
        self.reforms = 0
        self.joins_absorbed = 0
        self._register_epoch_gauge()

    # ------------------------------------------------------------- metrics
    def _register_epoch_gauge(self) -> None:
        from ..diagnostics.metrics import global_metrics

        reg = global_metrics()
        self._epoch_gauge = reg.gauge(
            "fusion_mesh_epoch",
            help="monotonic mesh world incarnation this host is serving in",
        )
        self._epoch_gauge.set(self.epoch)
        reg.set_aggregation("fusion_mesh_epoch", "max")

    # ------------------------------------------------------------ evidence
    def _evidence(self, peer: str) -> PeerEvidence:
        if peer not in self.evidence:
            self.evidence[peer] = PeerEvidence(peer)
        return self.evidence[peer]

    def _note(self, peer: str, kind: str) -> None:
        if self._evidence(peer).add(kind, self._clock()):
            self.events.record("mesh_evidence", f"{peer}:{kind}")

    def note_breaker_open(self, peer: str) -> None:
        self._note(peer, "breaker_open")

    def note_deadline_overrun(self, peer: str) -> None:
        self._note(peer, "deadline_overrun")

    def note_peer_dead_flag(self, peer: str) -> None:
        self._note(peer, "peer_dead_flag")

    def beat(self) -> None:
        """Publish this member's liveness on the board (wall clock — the
        board is cross-process, monotonic origins differ per reader)."""
        self.board.beat(self.member_id, self._wall())

    def poll_evidence(self) -> None:
        """One evidence sweep over the board: heartbeat lapses and
        orchestrator dead flags for every peer in the current world."""
        now = self._wall()
        for peer in self.members:
            if peer == self.member_id:
                continue
            if self.board.dead_flagged(peer):
                self.note_peer_dead_flag(peer)
            last = self.board.last_beat(peer)
            if last is not None and now - last > self.heartbeat_timeout_s:
                self._note(peer, "heartbeat_lapse")

    def dead_peers(self) -> List[str]:
        """Peers whose accumulated evidence converged past the threshold,
        in current member order."""
        return [
            m
            for m in self.members
            if m != self.member_id
            and m in self.evidence
            and self.evidence[m].score >= self.evidence_threshold
        ]

    # ------------------------------------------------------------ lifecycle
    def form_initial(self, coordinator: str) -> object:
        """First world formation at process start (launcher-provided
        coordinator, canonical member order)."""
        rank = self.members.index(self.member_id)
        self.world = self.ops.form(self.members, rank, coordinator)
        self.epoch = 1
        self._epoch_gauge.set(self.epoch)
        self.state = MeshController.SERVING
        self.beat()
        return self.world

    def adopt_world(self, world, *, epoch: int = 1) -> object:
        """Adopt an ALREADY-FORMED world (the :func:`~.multihost.
        init_multihost` bring-up path): the controller starts SERVING at
        ``epoch`` without re-forming — from here on it owns membership."""
        self.world = world
        self.epoch = epoch
        self._epoch_gauge.set(epoch)
        self.state = MeshController.SERVING
        self.beat()
        return world

    def detach(self) -> bool:
        """Retire the coordination agent once the caller has compiled its
        collective programs (blocks on the agent's own all-hosts shutdown
        barrier). Counted: this is the moment failure detection hands over
        from jax to this controller."""
        detached = bool(self.ops.detach())
        if detached:
            self.events.record("mesh_detached", f"epoch={self.epoch}")
        return detached

    def degrade(self, reason: str) -> None:
        """Counted degrade: abandon the current (possibly wedged) world
        in-process and fall to local serving. NEVER exits the process —
        the survivor keeps serving its shards between this call and the
        re-form completing."""
        self.events.record("mesh_degraded", reason)
        self.degrades += 1
        self.ops.teardown()
        self.world = None
        self.state = MeshController.DEGRADED

    def reform(self, survivors: Sequence[str]) -> object:
        """Re-form the world over ``survivors`` (canonical order) with the
        counted retry/timeout/backoff ladder on coordinator re-election."""
        survivors = list(survivors)
        if self.member_id not in survivors:
            raise ValueError(f"{self.member_id} not in survivor set {survivors}")
        self.state = MeshController.REFORMING
        last_err: Optional[Exception] = None
        for attempt in range(1, self.reform_attempts + 1):
            target = self.epoch + attempt
            self.events.record(
                "mesh_reform_attempt", f"epoch={target} attempt={attempt}"
            )
            try:
                world = self._attempt_reform(survivors, target)
            except Exception as e:  # noqa: BLE001 — every rung surfaces, counted
                last_err = e
                self.events.record(
                    "mesh_reform_failed", f"epoch={target}: {e}"
                )
                delay = min(
                    self.backoff_base_s * (2 ** (attempt - 1)), self.backoff_cap_s
                )
                # full jitter (0.5x..1.5x): simultaneous survivors must not
                # re-collide on the board in lockstep
                self._sleep(delay * (0.5 + self._rng.random()))
                continue
            self.world = world
            self.epoch = target
            self._epoch_gauge.set(self.epoch)
            retired = [m for m in self.members if m not in survivors]
            self.members = survivors
            self.state = MeshController.SERVING
            self.reforms += 1
            # fresh slate: evidence against reformed members is stale by
            # construction (it described the PREVIOUS world)
            for m in survivors:
                self.evidence.pop(m, None)
            # retire the dropped members' clock samples with their
            # membership: the per-peer fusion_clock_* series otherwise
            # accumulate one labeled pair per ref across every re-form
            # (ISSUE 18 satellite — the cardinality leak)
            if retired:
                from ..diagnostics.clocksync import global_clock_sync

                global_clock_sync().prune(retired)
            self.events.record(
                "mesh_reform_ok", f"epoch={self.epoch} members={len(survivors)}"
            )
            self.beat()
            return world
        raise MeshReformError(
            f"re-form over {survivors} failed after {self.reform_attempts} "
            f"attempts: {last_err}"
        )

    def _attempt_reform(self, survivors: List[str], target_epoch: int) -> object:
        """One ladder rung: elect/read the call, then form. The lowest
        surviving rank publishes; higher ranks poll and TAKE OVER after a
        rank-staggered timeout (the caller-elect may be the dead one)."""
        rank = survivors.index(self.member_id)
        call: Optional[dict] = None
        if rank == 0:
            call = self.board.publish_call(
                target_epoch, survivors, self._pick_address()
            )
        else:
            deadline = self._clock() + self.call_wait_s
            takeover_at = self._clock() + self.call_takeover_s * rank
            while call is None:
                call = self.board.read_call(target_epoch)
                if call is not None:
                    break
                now = self._clock()
                if now >= deadline:
                    raise TimeoutError(
                        f"no call for epoch {target_epoch} within "
                        f"{self.call_wait_s}s"
                    )
                if now >= takeover_at:
                    self.events.record(
                        "mesh_coordinator_takeover",
                        f"epoch={target_epoch} rank={rank}",
                    )
                    call = self.board.publish_call(
                        target_epoch, survivors, self._pick_address()
                    )
                    break
                self._sleep(self.poll_interval_s)
        if sorted(call["members"]) != sorted(survivors):
            raise RuntimeError(
                f"call for epoch {target_epoch} names {call['members']}, "
                f"expected {survivors}"
            )
        return self.ops.form(
            call["members"],
            call["members"].index(self.member_id),
            call["coordinator"],
        )

    # ---------------------------------------------------------------- joins
    def pending_joins(self) -> List[str]:
        return [
            m for m in self.board.pending_joins() if m not in self.members
        ]

    def absorb_joins(self, joiners: Sequence[str]) -> object:
        """Absorb live joiners: re-form to N+k with the joiners appended in
        sorted order (every member derives the same order), then clear the
        requests. The shard rebalance onto the joiner is the caller's
        ShardMap/warm-restore step — membership is what this owns."""
        joiners = sorted(j for j in joiners if j not in self.members)
        if not joiners:
            return self.world
        new_members = self.members + joiners
        if self.state == MeshController.SERVING:
            # graceful path: the old world is healthy, tear it down cleanly
            # (counted as a degrade — serving narrows to local during the
            # re-form window, and that must never be silent)
            self.degrade(f"join-absorb:{','.join(joiners)}")
        world = self.reform(new_members)
        for j in joiners:
            self.events.record("mesh_join_absorbed", j)
            self.joins_absorbed += 1
            self.board.clear_join(j)
            self.board.clear_dead_flag(j)
        return world

    def join(self, timeout_s: float = 60.0) -> object:
        """Joiner side: request membership, then poll for the first call
        that names this member and form into it."""
        self.board.request_join(self.member_id, self._wall())
        self.state = MeshController.REFORMING
        deadline = self._clock() + timeout_s
        while True:
            call = self.board.latest_call(min_epoch=self.epoch + 1)
            if call is not None and self.member_id in call["members"]:
                world = self.ops.form(
                    call["members"],
                    call["members"].index(self.member_id),
                    call["coordinator"],
                )
                self.world = world
                self.epoch = call["epoch"]
                self._epoch_gauge.set(self.epoch)
                self.members = list(call["members"])
                self.state = MeshController.SERVING
                self.events.record("mesh_joined", f"epoch={self.epoch}")
                self.beat()
                return world
            if self._clock() >= deadline:
                raise MeshReformError(
                    f"join of {self.member_id} saw no call within {timeout_s}s"
                )
            self._sleep(self.poll_interval_s)

    # ------------------------------------------------------------- reporting
    def snapshot(self) -> dict:
        return {
            "member": self.member_id,
            "state": self.state,
            "epoch": self.epoch,
            "members": list(self.members),
            "degrades": self.degrades,
            "reforms": self.reforms,
            "joins_absorbed": self.joins_absorbed,
            "evidence": {p: e.snapshot() for p, e in self.evidence.items()},
        }
