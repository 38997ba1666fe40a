"""One engine replica behind the router: a lane group behind a dispatcher.

The paper scales the vector machine by replicating lanes behind a shared
dispatcher; Ara2 replicates whole cores.  The serving analogue is N
independent :class:`~repro.runtime.serving.engine.ServingEngine` instances
— each its own arena, scheduler, dispatch queue, and health ladder —
fronted by :class:`~repro.runtime.serving.router.Router`.  A
:class:`Replica` is the thin per-engine shell the router talks to: the
engine plus its placement signals (cache pressure, unfinished load, health
rung, prefix residency) and the evacuation hook for drain-with-migration.

All replicas are built from the *same* model object and parameter tree, so
the :func:`~repro.runtime.serving.engine._per_model` jit caches are shared
— N replicas on one device compile exactly as many executables as one
(on N devices, each device compiles its own) — and every
replica resolves default seeds from the same ``base_seed``.  Together with
the (seed, absolute position) PRNG contract that makes every stream
placement-invariant: the router can put a request anywhere, or move it
mid-flight, without changing a single token.

:class:`StepClock` is the deterministic replica-local clock used by the
benchmarks: each engine step advances it one fixed quantum, so TTFT and
deadline arithmetic are measured in *replica-local steps* — the quantity
that models each replica running on its own ``data``-axis shard — instead
of the host's noisy wall clock.
"""
from __future__ import annotations

from typing import Any, Optional

from repro.runtime.serving.config import EngineConfig
from repro.runtime.serving.engine import ServingEngine
from repro.runtime.serving.health import HealthState
from repro.runtime.serving.request import Request, RequestState


class StepClock:
    """A clock that only moves when its replica steps.

    Injected as the engine's ``clock``: ``submitted_at`` / ``ttft_s`` /
    deadlines are then denominated in steps of *this* replica — exactly
    the service time a request would see with the replica on its own
    device, regardless of how many sibling replicas the driving process
    interleaves.  Deterministic, so step-TTFT percentiles are gateable.
    """

    def __init__(self, dt: float = 1.0):
        if dt <= 0:
            raise ValueError(f"StepClock dt must be > 0, got {dt}")
        self.t = 0.0
        self.dt = dt

    def __call__(self) -> float:
        return self.t

    def tick(self) -> None:
        self.t += self.dt


class Replica:
    """A router-owned engine: placement signals + lifecycle hooks.

    ``devices`` (optional) is this replica's slice of the mesh's ``data``
    axis (see ``launch.mesh.data_shards``): exactly one device, which the
    engine's params and arena are committed to.  When replicas outnumber
    the devices, shards cycle and replicas share a device (the one-device
    test host).  A replica spanning several devices would need its model
    sharded over them, which the engine does not do, so it is refused.
    """

    def __init__(self, rid: int, model, cfg, params, *,
                 config: EngineConfig, clock=None, devices=None):
        self.rid = rid
        self.devices = list(devices) if devices else None
        if self.devices and len(self.devices) > 1:
            raise ValueError(
                f"replica {rid} was given {len(self.devices)} devices; a "
                f"replica runs on one device (build the mesh's data axis "
                f"with one device per replica)")
        self._clock = clock
        self.engine = ServingEngine(
            model, cfg, params, config=config, clock=clock,
            device=self.devices[0] if self.devices else None)

    # -- placement signals ---------------------------------------------------
    @property
    def health(self) -> HealthState:
        return self.engine._health_state

    def pressure(self) -> float:
        """Cache pressure: fraction of the page pool in use."""
        return self.engine.cache_mgr.utilization()

    def unfinished(self) -> int:
        """Requests submitted here and not yet departed (waiting +
        resident) — the submit-time load signal that breaks pressure ties
        before any pages are allocated."""
        sched = self.engine.scheduler
        return len(sched.waiting) + len(sched.running)

    def prefix_len(self, prompt) -> int:
        """Longest prefix of ``prompt`` resident in this replica's prefix
        index (0 when sharing is off) — the affinity probe."""
        eng = self.engine
        if not eng.prefix_sharing:
            return 0
        m = eng.cache_mgr.lookup(prompt, int(prompt.shape[0]) - 1,
                                 require_snapshot=eng._needs_state_snapshot)
        return m.shared_len if m else 0

    # -- service -------------------------------------------------------------
    def submit(self, request: Request) -> RequestState:
        return self.engine.submit(request)

    def step(self) -> None:
        """One engine step; mirrors ``ServingEngine.run``'s forced retire
        when nothing is resident but readbacks are still in flight, and
        advances a :class:`StepClock` if one drives this replica."""
        eng = self.engine
        eng.step()
        if not eng.scheduler.running and eng._pending:
            eng._queue.drain()
            eng._drain_pending(limit=0)
        tick = getattr(self._clock, "tick", None)
        if tick is not None:
            tick()

    def settle(self) -> None:
        """Flush the dispatch queue + lagged readbacks (end of a run)."""
        self.engine._queue.drain()
        self.engine._drain_pending(limit=0)

    @property
    def done(self) -> bool:
        return self.engine.scheduler.all_done

    def evacuate(self) -> list:
        """Engine evacuation (see ``ServingEngine.evacuate``): all
        non-terminal requests leave MIGRATED, returned for re-placement."""
        return self.engine.evacuate()

    def result_state(self, uid) -> Optional[RequestState]:
        return self.engine._results.get(uid)

    def stats_row(self) -> dict:
        """One per-replica stats line (serve.py / bench reporting)."""
        eng = self.engine
        return {
            "replica": self.rid,
            "health": self.health.name,
            "pressure": round(self.pressure(), 3),
            "requests": eng.stats["requests"],
            "tokens_out": eng.stats["tokens_out"],
            "steps": eng._tick,
            "prefills": eng.stats["prefills"],
            "preempted": eng.scheduler.stats["preempted"],
            "migrated": eng.stats["migrated"],
            "failed": eng.stats["failed"],
        }
