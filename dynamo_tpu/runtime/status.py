"""Per-process system status server: /health, /live, /metrics.

Role of the reference's `system_status_server.rs` (axum; routes at
:155-176): every long-running process — worker, frontend, aggregator —
exposes liveness, readiness, and Prometheus text on its own port.  The
frontend embeds these in its OpenAI server; this module is the
standalone variant for processes without an HTTP ingress (workers).
"""

from __future__ import annotations

import logging
from typing import Awaitable, Callable, Optional

from aiohttp import web

from dynamo_tpu.runtime.metrics import MetricsRegistry

logger = logging.getLogger(__name__)

# Control-plane prefix where processes advertise their status servers so
# the metrics_aggregator can scrape /metrics from components that are not
# workers (router_service, planner) — the reference's Prometheus
# service-discovery analog, over our own control plane.
STATUS_ENDPOINTS_PREFIX = "status_endpoints"


async def register_status_endpoint(cp, component: str, port: int,
                                   host: str = "127.0.0.1",
                                   extra: Optional[dict] = None) -> str:
    """Advertise a status server for aggregator scraping; returns the
    key written.  Unleased on purpose: the aggregator treats unreachable
    targets as gone — and since ISSUE 14 the registration carries the
    owning PID, so scrapers (`dynamo top`, metrics_aggregator) can REAP
    a kill -9'd worker's stale entry instead of rendering it
    unreachable forever.  `host` must be a cross-host-routable address
    when the aggregator runs on another machine (same rule as the
    worker's --rpc-host).

    `extra`: additional registration fields (ISSUE 16: workers attach
    their SliceSpec wire dict under "slice" so `dynamo top` can render
    a MESH column without scraping anything new).  Reserved keys
    (address/component/pid) cannot be overridden."""
    import os

    key = f"{STATUS_ENDPOINTS_PREFIX}/{component}/{os.getpid()}"
    entry = dict(extra or {})
    entry.update({"address": f"{host}:{port}", "component": component,
                  "pid": os.getpid()})
    await cp.put(key, entry)
    return key


def registration_pid_dead(entry) -> bool:
    """True only when a status-endpoint registration names a pid that is
    PROVABLY gone: the entry carries a pid, its address is loopback
    (pid liveness is only decidable same-host — a loopback address from
    another machine was never scrapeable by us anyway), and signal-0
    probing reports no such process.  Everything ambiguous — foreign
    hosts, permission errors, malformed entries — reads as alive, so
    reaping can never take down a live worker's discovery entry."""
    import os

    if not isinstance(entry, dict):
        return False
    pid = entry.get("pid")
    addr = entry.get("address") or ""
    host = addr.rsplit(":", 1)[0] if ":" in addr else ""
    if not pid or host not in ("127.0.0.1", "localhost", "::1", "[::1]"):
        return False
    try:
        os.kill(int(pid), 0)
        return False
    except ProcessLookupError:
        return True
    except (PermissionError, OSError, ValueError, TypeError):
        return False


def register_status_endpoint_task(cp, component: str, port: int,
                                  host: str = "127.0.0.1",
                                  retry_interval: float = 1.0,
                                  extra: Optional[dict] = None):
    """Best-effort registration as a background task: retries until the
    put lands (the control-plane client reconnects underneath), so a
    control plane that is briefly down at process startup neither
    crashes the process nor silently loses its discovery entry.  Returns
    the task (cancel at shutdown)."""
    import asyncio

    async def register():
        while True:
            try:
                await register_status_endpoint(cp, component, port,
                                               host=host, extra=extra)
                return
            except asyncio.CancelledError:
                raise
            except Exception as e:
                # ANY failure retries (ConnectionError while down,
                # RuntimeError from an error reply mid-restart, …): a
                # dead registration task would silently drop this
                # process from fleet discovery forever.
                logger.warning(
                    "status-endpoint registration for %s failed (%s); "
                    "retrying", component, e)
                await asyncio.sleep(retry_interval)

    return asyncio.get_running_loop().create_task(register())


class StatusServer:
    def __init__(self,
                 registry: Optional[MetricsRegistry] = None,
                 ready_fn: Optional[Callable[[], bool]] = None,
                 extra_text_fn: Optional[Callable[[], str]] = None,
                 slo_fn: Optional[Callable[[], dict]] = None) -> None:
        """`ready_fn`: readiness probe (default: always ready once
        serving).  `extra_text_fn`: extra Prometheus text appended to the
        registry exposition (e.g. the worker's ForwardPassMetrics).
        `slo_fn`: /debug/slo payload provider (an SloMonitor's `payload`;
        None reports the monitor as disabled)."""
        self.registry = registry or MetricsRegistry()
        self.ready_fn = ready_fn or (lambda: True)
        self.extra_text_fn = extra_text_fn
        self.slo_fn = slo_fn
        self._runner: Optional[web.AppRunner] = None
        self.port: Optional[int] = None

    async def start(self, host: str = "127.0.0.1", port: int = 0) -> int:
        app = web.Application()
        app.router.add_get("/health", self._health)
        app.router.add_get("/live", self._live)
        app.router.add_get("/metrics", self._metrics)
        app.router.add_get("/debug/traces", self._debug_traces)
        app.router.add_get("/debug/slo", self._debug_slo)
        app.router.add_get("/debug/flightrecorder",
                           self._debug_flightrecorder)
        app.router.add_get("/debug/deviceprofile",
                           self._debug_deviceprofile)
        self._runner = web.AppRunner(app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, host, port)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]
        logger.info("status server on %s:%d", host, self.port)
        return self.port

    async def stop(self) -> None:
        if self._runner:
            await self._runner.cleanup()

    async def _health(self, _req: web.Request) -> web.Response:
        ok = bool(self.ready_fn())
        return web.json_response({"status": "ready" if ok else "starting"},
                                 status=200 if ok else 503)

    async def _live(self, _req: web.Request) -> web.Response:
        return web.json_response({"status": "live"})

    async def _metrics(self, _req: web.Request) -> web.Response:
        text = self.registry.expose()
        if self.extra_text_fn:
            text += self.extra_text_fn()
        return web.Response(text=text, content_type="text/plain")

    async def _debug_traces(self, req: web.Request) -> web.Response:
        """This process's completed traces (`?n=K`, default 32); same
        payload shape as the frontend's /debug/traces so
        tools/trace_merge.py treats every process uniformly."""
        from dynamo_tpu.runtime import tracing

        try:
            n = int(req.query.get("n", "32"))
        except ValueError:
            return web.json_response({"error": "n must be an integer"},
                                     status=400)
        return web.json_response(tracing.debug_traces_payload(n))

    async def _debug_flightrecorder(self, req: web.Request) -> web.Response:
        """This process's flight-recorder ring (`?n=K`, default 256) —
        same payload shape as the frontend's route, so chaos tooling and
        `tools/trace_merge.py --flight` treat every process uniformly."""
        from dynamo_tpu.runtime import flight_recorder

        try:
            n = int(req.query.get("n", "256"))
        except ValueError:
            return web.json_response({"error": "n must be an integer"},
                                     status=400)
        return web.json_response(
            flight_recorder.get_recorder().debug_payload(n))

    async def _debug_deviceprofile(self, req: web.Request) -> web.Response:
        """Device-truth plane (runtime/device_profiler.py).  Without
        `?ms=` it reports the plane's state (program registry, drift
        band states, capture history); with `?ms=N` it runs one bounded
        jax.profiler capture on this live process — off the event loop
        (asyncio.to_thread: the capture sleeps for its bound while the
        serving threads keep dispatching) — and returns what landed.
        `&python=1` adds every Python frame to the capture (a slowed
        host; the default holds the engine's phases and the runtime's
        own events only)."""
        import asyncio

        from dynamo_tpu.runtime import device_profiler

        prof = device_profiler.get_profiler()
        ms_raw = req.query.get("ms")
        if ms_raw is None:
            return web.json_response(prof.debug_payload())
        try:
            ms = int(ms_raw)
            if ms <= 0:
                raise ValueError
        except ValueError:
            return web.json_response(
                {"error": "ms must be a positive integer"}, status=400)
        python = req.query.get("python", "0") not in ("", "0", "false")
        res = await asyncio.to_thread(prof.capture, ms, python)
        return web.json_response(res, status=200 if res.get("ok") else 503)

    async def _debug_slo(self, _req: web.Request) -> web.Response:
        """Current SLO burn-rate evaluation (runtime/slo.py) — same
        payload shape as the frontend's /debug/slo so `dynamo top`
        treats every process uniformly."""
        from dynamo_tpu.runtime import slo as slo_mod

        if self.slo_fn is None:
            return web.json_response(slo_mod.disabled_payload())
        return web.json_response(self.slo_fn())
