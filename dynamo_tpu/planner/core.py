"""LoadPlanner: observe → predict → target → converge.

The decision skeleton of the reference's `planner_core.py:241-318`
specialised to load-based scaling (its SLA variant swaps the target
formula for TTFT/ITL interpolation; same loop)."""

from __future__ import annotations

import asyncio
import logging
import time
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

from dynamo_tpu.fleet.topology import SliceSpec, validate_placement
from dynamo_tpu.llm.kv_router.watcher import LoadMetricsWatcher
from dynamo_tpu.planner.predictor import make_predictor

logger = logging.getLogger(__name__)


@dataclass
class PlannerConfig:
    min_replicas: int = 1
    max_replicas: int = 8
    kv_high: float = 0.8        # predicted usage above → scale up
    kv_low: float = 0.3         # redistributable usage below → scale down
    adjustment_interval: float = 5.0
    metrics_stale_secs: float = 10.0
    predictor: str = "moving_average"
    # Heterogeneous disagg cell (ISSUE 16): non-empty → every scale
    # decision names one of these roles, each spawned with its own mesh
    # (connector role_worker_args, e.g. a big sp-prefill slice and a
    # small tp+int8-decode slice — the DistServe/Splitwise phase-fitted
    # pool shape).  Empty = aggregated fleet, decisions role-less.
    roles: Tuple[str, ...] = ()
    # SLO bias (runtime/slo.py): when a watched /debug/slo reports a
    # fast-window burn rate at or above this, scale up even though KV
    # usage looks fine — latency SLOs burn before memory fills (the
    # AIBrix-style signal the load moving-average can't see).  Scale-
    # DOWN is additionally vetoed while any burn is >= 1.0 (actively
    # consuming budget is the wrong moment to shed capacity).
    slo_burn_scale_up: float = 2.0
    # A /debug/slo payload older than this exerts no pressure: a crashed
    # SLO source must not pin the fleet at max_replicas forever on its
    # last (possibly mid-incident) reading.
    slo_stale_secs: float = 60.0


class LoadPlanner:
    """Watches `load_metrics`, steps a replica target, drives a connector.

    `connector` contract: `replicas() -> int` (current), plus
    `add_worker()` / `remove_worker()` (one step each, async).

    `slo_url`: a /debug/slo endpoint (frontend or worker) polled each
    adjustment interval; its burn rates bias scaling per
    PlannerConfig.slo_burn_scale_up."""

    def __init__(self, cp, connector,
                 config: Optional[PlannerConfig] = None,
                 slo_url: Optional[str] = None,
                 slices_fn: Optional[Callable[[], Dict]] = None) -> None:
        self.cp = cp
        self.connector = connector
        self.config = config or PlannerConfig()
        self.slo_url = slo_url
        # Topology source: worker id → published SliceSpec (or its wire
        # dict), usually wired to the runtime client's instance records.
        # None = no topology view; role decisions fall back to replica
        # counts alone.
        self._slices_fn = slices_fn
        self._slo: Optional[dict] = None       # last /debug/slo payload
        self._slo_ts: float = 0.0              # when it was fetched
        self._watcher = LoadMetricsWatcher(
            cp, stale_secs=self.config.metrics_stale_secs, name="planner")
        self._usage_pred = make_predictor(self.config.predictor)
        self._waiting_pred = make_predictor(self.config.predictor)
        self._tasks = []
        # In-flight scale-down: remove_worker waits out the worker's
        # KV-migrating drain (up to the connector's drain_timeout_s), so
        # it runs as a background task — the adjustment loop must stay
        # responsive to scale-UP pressure mid-drain.
        self._drain_task: Optional[asyncio.Task] = None
        self.decisions: list = []              # (ts, kind, reason) log

    async def start(self) -> None:
        await self._watcher.start()
        self._tasks = [asyncio.create_task(self._loop())]

    async def stop(self) -> None:
        await self._watcher.stop()
        if self._drain_task is not None and not self._drain_task.done():
            # Let an in-flight drain finish (bounded by the connector's
            # own timeout) rather than orphan a half-drained worker.
            try:
                await self._drain_task
            except Exception:
                logger.exception("planner: in-flight drain failed at stop")
        for t in self._tasks:
            t.cancel()
            try:
                await t
            except asyncio.CancelledError:
                pass

    def _observe(self):
        fresh = list(self._watcher.fresh().values())
        if not fresh:
            return None
        usage = sum(m.kv_stats.gpu_cache_usage_perc
                    for m in fresh) / len(fresh)
        waiting = sum(m.worker_stats.num_requests_waiting for m in fresh)
        return len(fresh), usage, waiting

    def slo_pressure(self) -> float:
        """Worst fast-window burn rate from the last /debug/slo poll
        (0.0 with no SLO source configured, monitor disabled, or a
        payload past slo_stale_secs — dead sources stop steering)."""
        from dynamo_tpu.runtime.slo import max_burn

        if (self._slo is not None
                and time.monotonic() - self._slo_ts
                > self.config.slo_stale_secs):
            return 0.0
        return max_burn(self._slo)

    # -- topology reads (ISSUE 16) -----------------------------------------

    def topology(self) -> Dict[object, Optional[SliceSpec]]:
        """Published slice topology: worker id → SliceSpec (None for
        workers that publish nothing).  Tolerant of a failing source —
        the planner must keep scaling a fleet whose discovery hiccups."""
        if self._slices_fn is None:
            return {}
        try:
            raw = self._slices_fn() or {}
        except Exception:
            logger.exception("planner: topology source failed; planning "
                             "topology-blind this step")
            return {}
        return {
            w: (s if isinstance(s, SliceSpec) or s is None
                else SliceSpec.from_dict(s))
            for w, s in raw.items()
        }

    def placement_ok(self, role: str, worker_id=None,
                     spec: Optional[SliceSpec] = None) -> Tuple[bool, str]:
        """Is assigning `role` work to this worker topology-sane?  THE
        planner's SliceSpec consult (fleet.topology.validate_placement):
        a mesh-blind decision — decode role on a dedicated prefill
        slice — is refused here (tests/test_planner.py makes
        exactly that decision to prove the consult happens)."""
        if spec is None and worker_id is not None:
            spec = self.topology().get(worker_id)
        return validate_placement(role, spec)

    def _role_replicas(self, role: str) -> int:
        try:
            return self.connector.replicas(role=role)
        except TypeError:
            # Role-less connector: every replica counts for every role.
            return self.connector.replicas()

    def plan_role(self, decision: Optional[str]) -> Optional[str]:
        """Which role a scale decision targets in heterogeneous-cell
        mode (config.roles): scale-up fills the thinnest pool first
        (declaration order breaks ties — list prefill first to absorb
        ISL pressure); scale-down thins the fattest pool and NEVER
        drops a role's last replica (a cell without a prefill slice
        serves nothing).  None in aggregated mode."""
        if not self.config.roles or decision is None:
            return None
        counts = {r: self._role_replicas(r) for r in self.config.roles}
        if decision == "up":
            order = {r: i for i, r in enumerate(self.config.roles)}
            return min(self.config.roles,
                       key=lambda r: (counts[r], order[r]))
        victims = [r for r in self.config.roles if counts[r] > 1]
        if not victims:
            return None
        return max(victims, key=lambda r: counts[r])

    def plan_step(self) -> Optional[str]:
        """One planning decision from current predictions; returns
        "up" | "down" | None.  Synchronous and side-effect-free on the
        connector (unit-testable; the loop applies it).

        Heterogeneous-cell mode additionally consults the published
        SliceSpecs: a "down" that would leave some role with no
        placeable slice among the survivors is vetoed (plan_role names
        the victim role; `topology()` + `fleet.topology.place_role`
        check the survivors)."""
        decision = self._plan_step_load()
        if decision == "down" and self.config.roles:
            role = self.plan_role("down")
            if role is None:
                return None  # every role at its floor
            top = self.topology()
            if top:
                from dynamo_tpu.fleet.topology import place_role

                survivors = dict(top)
                # Drop ONE published slice of the victim role (the
                # connector pops newest-first; any same-role member is
                # equivalent for the coverage check).
                for w, s in top.items():
                    if s is not None and s.role == role:
                        survivors.pop(w)
                        break
                for r in self.config.roles:
                    if place_role(r, survivors) is None:
                        logger.info(
                            "planner: scale-down of a %s slice vetoed — "
                            "no surviving slice could serve role %r",
                            role, r)
                        return None
        return decision

    def _plan_step_load(self) -> Optional[str]:
        draining = (self._drain_task is not None
                    and not self._drain_task.done())
        replicas = self.connector.replicas()
        if replicas < self.config.min_replicas:
            # Floor check needs no observations — it's how the fleet
            # bootstraps (no worker yet → no metrics yet).
            return "up"
        burn = self.slo_pressure()
        if (burn >= self.config.slo_burn_scale_up
                and replicas < self.config.max_replicas):
            # SLO bias: budget is burning NOW; don't wait for the KV
            # moving-average to catch up.
            return "up"
        obs = self._observe()
        if obs is None:
            return None
        n_reporting, usage, waiting = obs
        self._usage_pred.add_data_point(usage)
        self._waiting_pred.add_data_point(waiting)
        p_usage = self._usage_pred.predict_next()
        p_waiting = self._waiting_pred.predict_next()
        if ((p_usage > self.config.kv_high or p_waiting >= 1.0)
                and replicas < self.config.max_replicas):
            return "up"
        # Scale down only if the survivors could absorb the load under
        # kv_low: usage*n / (n-1) stays below the low-water mark — never
        # while an SLO is actively burning budget, and one drain at a
        # time (a scale-down is committed until its background
        # remove_worker lands; stacking removals would over-shed).
        if (not draining
                and replicas > self.config.min_replicas and p_waiting < 1.0
                and n_reporting > 1 and burn < 1.0
                and p_usage * n_reporting / (n_reporting - 1)
                < self.config.kv_low):
            return "down"
        return None

    async def _fetch_slo(self) -> None:
        """Refresh the /debug/slo view; keeps the last payload on
        transient fetch errors (stale pressure beats none mid-incident)."""
        if not self.slo_url:
            return
        import aiohttp

        try:
            timeout = aiohttp.ClientTimeout(total=2.0)
            async with aiohttp.ClientSession(timeout=timeout) as s:
                async with s.get(self.slo_url) as resp:
                    if resp.status == 200:
                        self._slo = await resp.json()
                        self._slo_ts = time.monotonic()
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError):
            logger.debug("slo poll of %s failed; keeping last payload",
                         self.slo_url)

    async def _loop(self) -> None:
        while True:
            await asyncio.sleep(self.config.adjustment_interval)
            try:
                await self._fetch_slo()
                decision = self.plan_step()
                role = self.plan_role(decision)
                if decision == "up":
                    self.decisions.append((time.monotonic(), "up",
                                           self._reason(role)))
                    logger.info("planner: scaling UP (%s)",
                                self._reason(role))
                    await self._apply_add(role)
                elif decision == "down":
                    self.decisions.append((time.monotonic(), "down",
                                           self._reason(role)))
                    logger.info("planner: scaling DOWN (%s)",
                                self._reason(role))
                    # Background: remove_worker waits out the drain
                    # (plan_step holds further decisions off until it
                    # lands; scale-up pressure still gets polled).
                    self._drain_task = asyncio.create_task(
                        self._apply_remove(role))
            except asyncio.CancelledError:
                raise
            except Exception:
                logger.exception("planner: adjustment failed; continuing")

    async def _apply_add(self, role: Optional[str]) -> None:
        if role is None:
            await self.connector.add_worker()
            return
        try:
            await self.connector.add_worker(role=role)
        except TypeError:
            # Role-less connector under a roles config: spawn the plain
            # worker rather than stall the fleet.
            await self.connector.add_worker()

    async def _apply_remove(self, role: Optional[str]) -> None:
        if role is None:
            await self.connector.remove_worker()
            return
        try:
            await self.connector.remove_worker(role=role)
        except TypeError:
            await self.connector.remove_worker()

    def _reason(self, role: Optional[str] = None) -> str:
        reason = (f"usage~{self._usage_pred.predict_next():.2f} "
                  f"waiting~{self._waiting_pred.predict_next():.1f} "
                  f"replicas={self.connector.replicas()}")
        if role is not None:
            reason += f" role={role}"
        burn = self.slo_pressure()
        if burn > 0:
            reason += f" slo_burn~{burn:.1f}"
        return reason


def planner_metrics_text(planner, connector) -> str:
    """Prometheus text for the planner's status server (`/metrics` on
    `python -m dynamo_tpu.planner --metrics-port`): replica count,
    scaling-decision tallies, and the predictors' next-step view.  Works
    for both LoadPlanner and SlaPlanner (fields read defensively — the
    SLA variant keeps its own predictor names)."""
    lines = []
    try:
        lines.append(f"dynamo_planner_replicas {connector.replicas()}")
    except Exception:
        # dynamo-lint: disable=DL003 best-effort metrics text
        pass  # connector variant without replicas(): omit the series
    # Heterogeneous-cell mode: per-role pool sizes (ISSUE 16).
    for role in (getattr(getattr(planner, "config", None), "roles", ())
                 or ()):
        try:
            lines.append('dynamo_planner_replicas{role="%s"} %d'
                         % (role, connector.replicas(role=role)))
        except Exception:
            # dynamo-lint: disable=DL003 best-effort metrics text
            pass  # role-less connector: omit the per-role series
    decisions = getattr(planner, "decisions", []) or []
    ups = sum(1 for d in decisions if len(d) > 1 and d[1] == "up")
    downs = sum(1 for d in decisions if len(d) > 1 and d[1] == "down")
    lines.append('dynamo_planner_decisions_total{direction="up"} %d' % ups)
    lines.append('dynamo_planner_decisions_total{direction="down"} %d'
                 % downs)
    # Scale-down outcomes (ISSUE 15): clean KV-migrating drains vs
    # drain-timeout force-kills — a rising force_kill count is the
    # "drains are broken" alarm, previously invisible.
    for attr, outcome in (("clean_drains", "clean"),
                          ("force_kills", "force_kill")):
        n = getattr(connector, attr, None)
        if n is not None:
            lines.append(
                'dynamo_planner_drains_total{outcome="%s"} %d'
                % (outcome, n))
    for attr, name in (("_usage_pred", "kv_usage"),
                       ("_waiting_pred", "requests_waiting")):
        pred = getattr(planner, attr, None)
        if pred is None:
            continue
        try:
            lines.append('dynamo_planner_predicted{metric="%s"} %s'
                         % (name, pred.predict_next()))
        except Exception:
            # dynamo-lint: disable=DL003 best-effort metrics text
            pass  # predictor not warmed up yet: omit the series
    return "\n".join(lines) + "\n"
