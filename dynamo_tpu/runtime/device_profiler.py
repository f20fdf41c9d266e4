"""Device-truth profiling plane: what the hardware ACTUALLY did.

Every performance claim this repo gates on — per-chip mbu,
`kv_read_bytes_modeled`, `ring_exchange_bytes_modeled`, transfer GB/s —
is modeled arithmetic compared against datasheets.  This module is the
live path from a serving worker to XLA's own accounting, in three legs:

- **ProgramCostRegistry** (cost-analysis harvest) — the engine's
  dispatch sites already classify every jitted program by the same
  (tag, shape-signature) identity the flight recorder stamps on
  recompiles; on a FIRST-SEEN shape (``EngineStepCounters.note_dispatch``
  returning True) the engine hands the about-to-compile callable + its
  args to :meth:`DeviceProfiler.harvest`, which runs
  ``fn.lower(*args).cost_analysis()`` — XLA's flops / bytes-accessed /
  optimal-seconds estimate, available WITHOUT executing or donating
  anything and without a backend compile.  Harvest cost rides the
  compile event (already tens of ms..s); the steady hot path never sees
  it — steady-window `EngineStepCounters` deltas are byte-identical
  plane-on vs plane-off (tests/test_device_profiler.py::
  test_steady_window_profiler_on_is_byte_identical, the same discipline
  as the flight recorder).
- **DriftAuditor** (modeled-vs-measured audit) — folds the registry's
  XLA bytes-accessed per dispatch class against the engine's modeled
  per-chip KV bytes, and XLA's roofline time against the measured
  window-interval EWMA, as `dynamo_modeled_vs_measured_ratio{series=}`.
  The invariant is ONE-SIDED: modeled KV bytes are a *component* of
  what XLA sweeps (weights ride every dispatch too), so ratio =
  modeled/measured must stay ≤ band_hi (default 1.25) — a modeled
  series that CLAIMS more bytes than the hardware touched is lying
  (the PR 16 int8 scale-pack double-count class of bug).  Three
  consecutive out-of-band observations PAGE: a `drift_page` event via
  ``FlightRecorder.record_always`` + an async ring dump, same trigger
  shape as the SLO monitor.
- **On-demand device capture** — a bounded ``jax.profiler``
  start/stop_trace on a LIVE worker (``/debug/deviceprofile?ms=500`` on
  the StatusServer, frontend proxy route, and the control-plane
  ``profile/<pid>`` command key — same shape as ``drain/<pid>``),
  writing xplane + Chrome-trace output under ``--flight-dump-dir`` in a
  ``deviceprofile_<service>_<pid>`` directory that
  ``tools/trace_merge.py --device <dir>`` merges onto the owning
  worker's host-span lanes.

Surfaces: `dynamo_program_flops{program=}` /
`dynamo_program_bytes_accessed{program=}` /
`dynamo_program_registry_size` /
`dynamo_modeled_vs_measured_ratio{series=}` on worker `/metrics`,
`dynamo top`'s DRIFT column, `--once --json` rows (so the
metrics_aggregator pre-sums the fleet ratio), and
`/debug/deviceprofile` on every status surface.

Stdlib-only at import time by design (jax is imported lazily inside
harvest/capture): the engine and worker main import this module
unconditionally, mirroring flight_recorder.
"""

from __future__ import annotations

import glob as _glob
import logging
import os
import threading
import time
import weakref
from typing import Dict, List, Optional, Tuple

from dynamo_tpu.runtime import flight_recorder
from dynamo_tpu.runtime.logutil import warn_rate_limited

logger = logging.getLogger(__name__)

# Capture bound: a device trace buffers on-device and in host RAM; an
# unbounded capture on a serving worker is an incident, not a feature.
DEFAULT_MAX_CAPTURE_MS = 2000

# Drift band (modeled / measured).  The invariant is one-sided: modeled
# KV bytes can legitimately be a small fraction of XLA's total
# bytes-accessed (weights dominate tiny models), so the low edge
# defaults to 0 (disabled); the HIGH edge is the honesty gate — modeled
# traffic claiming more than the hardware touched (plus estimator
# headroom) means the accounting double-counts.
DEFAULT_BAND_HI = 1.25
DEFAULT_BAND_LO = 0.0
# Consecutive out-of-band observations before a series PAGEs — one
# scrape-time blip (e.g. a registry mid-warmup) must not dump the ring.
PAGE_STRIKES = 3

# Control-plane capture command prefix: `profile/{pid}` or
# `profile/instance/{instance_id}` (value: optional capture ms, then
# optionally the word `python` for Python frames in the capture).
PROFILE_PREFIX = "profile/"

# How long a capture waits, past its bound, for the engine threads to
# close the `engine.<phase>` event each holds open (a decode window in
# flight keeps `wait_device` open for its ~80 ms).
PHASE_CLOSE_WAIT_S = 0.1


def parse_profile_command(value) -> Tuple[int, bool]:
    """(capture ms, python frames?) of a `profile/...` command's value:
    `"500"`, `"500 python"`; anything unreadable is 500 ms, no frames."""
    words = str(value or "").split()
    try:
        ms = int(words[0])
    except (IndexError, ValueError):
        ms = 500
    return ms, "python" in words[1:]


def profile_key_pid(pid: int) -> str:
    return f"{PROFILE_PREFIX}{pid}"


def profile_key_instance(instance_id: int) -> str:
    return f"{PROFILE_PREFIX}instance/{instance_id}"


def program_label(tag: str, sig: Tuple) -> str:
    """The registry/metrics identity of a compiled program — the same
    (tag, shape-signature) key note_dispatch/flight stamps use."""
    return tag + ":" + ",".join(str(x) for x in sig)


class ProgramCostRegistry:
    """Host-side map of compiled-program label → XLA cost analysis.

    Written only at compile time (first-seen shapes — a handful per
    process lifetime), read at scrape time; plain dict under the GIL,
    iterated via snapshot."""

    def __init__(self) -> None:
        self._programs: Dict[str, Dict[str, Optional[float]]] = {}

    def record(self, label: str, *, flops: float, bytes_accessed: float,
               optimal_s: Optional[float] = None) -> None:
        self._programs[label] = {
            "flops": float(flops),
            "bytes_accessed": float(bytes_accessed),
            "optimal_s": (float(optimal_s)
                          if optimal_s is not None else None),
        }

    def get(self, label: str) -> Optional[Dict[str, Optional[float]]]:
        return self._programs.get(label)

    def size(self) -> int:
        return len(self._programs)

    def items(self) -> List[Tuple[str, Dict[str, Optional[float]]]]:
        return sorted(self._programs.items())

    def tag_values(self, key: str, *tags: str) -> List[float]:
        """All recorded `key` values for programs whose tag is one of
        `tags` (label prefix before the first ':')."""
        out: List[float] = []
        for label, costs in list(self._programs.items()):
            if label.split(":", 1)[0] in tags:
                v = costs.get(key)
                if v is not None:
                    out.append(v)
        return out

    def mean_for_tags(self, key: str, *tags: str) -> Optional[float]:
        vals = self.tag_values(key, *tags)
        return sum(vals) / len(vals) if vals else None

    def top_by(self, key: str, k: int = 10
               ) -> List[Tuple[str, Dict[str, Optional[float]]]]:
        """Top-K programs by a cost column (profile_trace's summary)."""
        rows = [(label, costs) for label, costs in self.items()
                if costs.get(key) is not None]
        rows.sort(key=lambda r: r[1][key], reverse=True)
        return rows[:k]

    def reset(self) -> None:
        self._programs.clear()


class DriftAuditor:
    """Band state machine over modeled/measured ratios, one per series.

    `observe` is called at SCRAPE time (worker_metrics_text →
    audit_engine), never on the engine hot path.  A series that stays
    out of band for PAGE_STRIKES consecutive observations transitions
    to PAGE: one `drift_page` flight event (record_always — drift
    evidence must land even on a recorder that never opted in) plus an
    async ring dump; returning in band resets the episode."""

    def __init__(self, band_hi: float = DEFAULT_BAND_HI,
                 band_lo: float = DEFAULT_BAND_LO) -> None:
        self.band_hi = band_hi
        self.band_lo = band_lo
        self._series: Dict[str, Dict] = {}

    def observe(self, series: str, modeled: float,
                measured: float) -> Optional[float]:
        """Fold one modeled/measured pair; returns the ratio, or None
        when the pair is unobservable (no measured denominator yet)."""
        if measured <= 0 or modeled < 0:
            return None
        ratio = modeled / measured
        st = self._series.setdefault(
            series, {"ratio": None, "state": "ok", "strikes": 0})
        st["ratio"] = ratio
        in_band = self.band_lo <= ratio <= self.band_hi
        if in_band:
            if st["state"] == "page":
                rec = flight_recorder.get_recorder()
                rec.record_always("drift_ok", series=series,
                                  ratio=round(ratio, 4))
            st["state"] = "ok"
            st["strikes"] = 0
            return ratio
        st["strikes"] += 1
        if st["strikes"] >= PAGE_STRIKES and st["state"] != "page":
            st["state"] = "page"
            rec = flight_recorder.get_recorder()
            rec.record_always(
                "drift_page", series=series, ratio=round(ratio, 4),
                band_lo=self.band_lo, band_hi=self.band_hi,
                strikes=st["strikes"])
            logger.error(
                "modeled-vs-measured drift PAGE: series=%s ratio=%.4f "
                "outside [%s, %s] for %d consecutive observations — "
                "modeled accounting is over-claiming; dumping flight "
                "recorder", series, ratio, self.band_lo, self.band_hi,
                st["strikes"])
            rec.dump_async("drift_page")
        return ratio

    def ratios(self) -> Dict[str, float]:
        return {s: st["ratio"] for s, st in self._series.items()
                if st["ratio"] is not None}

    def states(self) -> Dict[str, Dict]:
        return {s: dict(st) for s, st in self._series.items()}

    def paged(self) -> bool:
        return any(st["state"] == "page"
                   for st in self._series.values())

    def reset(self) -> None:
        self._series.clear()


class DeviceProfiler:
    """The per-process device-truth plane: registry + auditor + capture.

    Disabled by default (module singleton — tests and libraries that
    import the engine must not pay for it); the worker flag
    ``--device-profiler on`` enables it at process startup."""

    def __init__(self, service: str = "dynamo", *, enabled: bool = False,
                 max_capture_ms: int = DEFAULT_MAX_CAPTURE_MS,
                 dump_dir: Optional[str] = None,
                 band_hi: float = DEFAULT_BAND_HI,
                 band_lo: float = DEFAULT_BAND_LO) -> None:
        self.service = service
        self.enabled = enabled
        self.max_capture_ms = max_capture_ms
        self.dump_dir = dump_dir
        self.registry = ProgramCostRegistry()
        self.auditor = DriftAuditor(band_hi=band_hi, band_lo=band_lo)
        self.harvests = 0
        self.harvest_failures = 0
        self.captures = 0
        self.last_capture_dir: Optional[str] = None
        # One capture at a time: jax.profiler keeps process-global trace
        # state; a second start_trace mid-capture raises.
        self._capture_lock = threading.Lock()
        # Phase clocks (EngineStepCounters) of the engines in this
        # process: a capture turns their annotation sink on and off.
        self._phase_clocks: "weakref.WeakSet" = weakref.WeakSet()

    def watch_phases(self, counters) -> None:
        """Called by an engine as it is built: a capture will trace its
        phase clock."""
        self._phase_clocks.add(counters)

    def _trace_phases(self, on: bool) -> None:
        clocks = list(self._phase_clocks)
        for counters in clocks:
            counters.trace_phases = on
        if on:
            return
        # Each engine thread closes its open event at its next `enter`
        # (an idle loop re-enters `idle` every 5 ms, a blocked one moves
        # on when its window lands); an event still open at stop_trace
        # is lost, so give them a moment.
        deadline = time.monotonic() + PHASE_CLOSE_WAIT_S
        while (any(c.phase_event_open for c in clocks)
               and time.monotonic() < deadline):
            time.sleep(0.001)

    # -- configuration -----------------------------------------------------

    def configure(self, *, service: Optional[str] = None,
                  enabled: Optional[bool] = None,
                  max_capture_ms: Optional[int] = None,
                  dump_dir: Optional[str] = None,
                  band_hi: Optional[float] = None,
                  band_lo: Optional[float] = None) -> "DeviceProfiler":
        """In-place reconfiguration — the module singleton is shared by
        reference (the engine captured it at __init__); identity must
        survive, same contract as FlightRecorder.configure."""
        if service is not None:
            self.service = service
        if enabled is not None:
            self.enabled = enabled
        if max_capture_ms is not None:
            self.max_capture_ms = int(max_capture_ms)
        if dump_dir is not None:
            self.dump_dir = dump_dir
        if band_hi is not None:
            self.auditor.band_hi = band_hi
        if band_lo is not None:
            self.auditor.band_lo = band_lo
        return self

    def reset(self) -> None:
        """Drop all state (test isolation)."""
        self.registry.reset()
        self.auditor.reset()
        self.harvests = 0
        self.harvest_failures = 0
        self.captures = 0
        self.last_capture_dir = None

    # -- leg 1: cost-analysis harvest (compile-time only) ------------------

    def harvest(self, tag: str, sig: Tuple, fn, args: Tuple) -> bool:
        """Capture XLA's cost analysis for a program about to compile.

        Called from the engine's dispatch sites ONLY on first-seen
        (tag, sig) shapes — the cost rides the compile event, never the
        steady window.  ``fn.lower(*args)`` traces without executing or
        donating (safe alongside donate_argnums buffers) and
        ``Lowered.cost_analysis()`` answers off the StableHLO without a
        backend compile.  A program served from the program store
        (``StoredProgram.cost_analysis``) answers with the same analysis
        from its entry, so the dispatch that follows lowers nothing a
        second time.  Returns True when a record landed.  MUST
        never break serving: sharded/pp step makers may hand back plain
        callables without ``.lower``, and cost analysis availability
        varies by backend — every failure path degrades to a
        rate-limited warning."""
        if not self.enabled:
            return False
        stored_cost = getattr(fn, "cost_analysis", None)
        lower = getattr(fn, "lower", None)
        if stored_cost is None and lower is None:
            return False
        label = program_label(tag, sig)
        try:
            ca = (stored_cost(*args) if stored_cost is not None
                  else lower(*args).cost_analysis())
            # Older jax returns a per-partition list; newer a plain dict.
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else None
            if not isinstance(ca, dict):
                return False
            self.registry.record(
                label,
                flops=float(ca.get("flops", 0.0)),
                # XLA's key really does contain a space.
                bytes_accessed=float(ca.get("bytes accessed", 0.0)),
                optimal_s=ca.get("optimal_seconds"))
            self.harvests += 1
            return True
        except Exception as e:
            self.harvest_failures += 1
            warn_rate_limited(
                logger, "device_profiler.harvest", 60.0,
                "cost-analysis harvest failed for %s: %s: %s",
                label, type(e).__name__, e)
            return False

    # -- leg 2: drift audit (scrape-time only) -----------------------------

    def audit_engine(self, core) -> Dict[str, float]:
        """Fold the engine's modeled counters against the registry's
        XLA-measured costs; returns the current ratios.  Scrape-time
        only (worker_metrics_text / dynamo top) — reads counters the
        engine thread increments, never blocks it.

        Series:
        - ``kv_decode`` — modeled per-chip KV bytes swept
          (kv_read_bytes_modeled) vs XLA bytes-accessed summed over the
          decode dispatch classes (window × window_dispatches, decode1
          mean × single_step_dispatches, spec mean × spec_dispatches).
          One-sided: modeled is a component of measured, so the ratio
          must stay ≤ band_hi.
        - ``window_time`` — XLA's roofline optimal-seconds per window
          (TPU backends only) vs the measured window-interval EWMA;
          absent where the backend reports no optimal_seconds (CPU).
        """
        if not self.enabled:
            return {}
        c = getattr(core, "counters", None)
        if c is None:
            return {}
        reg = self.registry
        measured = 0.0
        win_bytes = reg.mean_for_tags("bytes_accessed", "window")
        if win_bytes is not None:
            measured += win_bytes * c.window_dispatches
        d1_bytes = reg.mean_for_tags("bytes_accessed",
                                     "decode1", "decode1g")
        if d1_bytes is not None:
            measured += d1_bytes * c.single_step_dispatches
        spec_bytes = reg.mean_for_tags("bytes_accessed", "spec")
        if spec_bytes is not None:
            measured += spec_bytes * c.spec_dispatches
        if measured > 0:
            self.auditor.observe("kv_decode",
                                 float(c.kv_read_bytes_modeled), measured)
        opt_s = reg.mean_for_tags("optimal_s", "window")
        if opt_s is not None and c.window_s is not None:
            self.auditor.observe("window_time", opt_s, c.window_s)
        return self.auditor.ratios()

    # -- leg 3: on-demand bounded device capture ---------------------------

    def capture_dir(self) -> str:
        import tempfile

        d = self.dump_dir or tempfile.gettempdir()
        return os.path.join(
            d, "deviceprofile_"
               f"{self.service.replace('/', '_')}_{os.getpid()}")

    def capture(self, ms: int, python: bool = False) -> dict:
        """Bounded jax.profiler capture on the live process: start the
        trace, sleep `ms` (clamped to max_capture_ms) while the serving
        threads keep dispatching, stop, and report what landed.  Runs
        OFF the engine thread (status-server executor / control-plane
        watcher); serialized — jax's profiler state is process-global.

        The host plane holds the runtime's own events and the engine
        thread's phases (`engine.<phase>`, EngineStepCounters.enter);
        the trace stops up to PHASE_CLOSE_WAIT_S after `ms`, once the
        phase open at the end has closed.  A chip that ran nothing all
        through leaves no device plane in the trace.
        `python=True` adds every Python frame of every thread, for an
        unannotated stall: the tracer hooks each call while it runs, so
        the capture then shows a slowed host."""
        ms = max(1, min(int(ms), self.max_capture_ms))
        python = bool(python)
        if not self.enabled:
            return {"ok": False, "error": "device profiler disabled "
                                          "(--device-profiler off)"}
        if not self._capture_lock.acquire(blocking=False):
            return {"ok": False, "error": "capture already in progress"}
        try:
            import jax

            out_dir = self.capture_dir()
            os.makedirs(out_dir, exist_ok=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 1 if python else 0
            jax.profiler.start_trace(out_dir, profiler_options=options)
            wall_start = time.time()
            self._trace_phases(True)
            try:
                time.sleep(ms / 1000.0)
            finally:
                self._trace_phases(False)
                jax.profiler.stop_trace()
            # Sidecar for tools/trace_merge.py --device: the profiler's
            # Chrome-trace timestamps are RELATIVE to trace start; the
            # wall anchor here is what lets device activity land
            # time-aligned under this worker's host spans.
            import json as _json

            with open(os.path.join(out_dir, "capture_meta.json"),
                      "w") as f:
                _json.dump({"service": self.service, "pid": os.getpid(),
                            "ms": ms, "python": python,
                            "wall_start": wall_start,
                            "wall_end": time.time()}, f)
            files = sorted(
                os.path.relpath(p, out_dir)
                for pat in ("**/*.xplane.pb", "**/*.trace.json.gz")
                for p in _glob.glob(os.path.join(out_dir, pat),
                                    recursive=True))
            self.captures += 1
            self.last_capture_dir = out_dir
            logger.warning("device capture: %d ms → %s (%d file(s))",
                           ms, out_dir, len(files))
            return {"ok": bool(files), "ms": ms, "python": python,
                    "dir": out_dir, "files": files, "pid": os.getpid(),
                    "service": self.service,
                    **({} if files else
                       {"error": "capture produced no trace output"})}
        except Exception as e:
            logger.warning("device capture failed: %s: %s",
                           type(e).__name__, e)
            return {"ok": False, "ms": ms,
                    "error": f"{type(e).__name__}: {e}"}
        finally:
            self._capture_lock.release()

    # -- surfaces ----------------------------------------------------------

    def metrics_lines(self) -> List[str]:
        """Prometheus text lines for worker /metrics (scrape-time)."""
        out = [
            "# HELP dynamo_program_registry_size compiled programs with "
            "harvested XLA cost analysis",
            "# TYPE dynamo_program_registry_size gauge",
            f"dynamo_program_registry_size {self.registry.size()}",
        ]
        items = self.registry.items()
        if items:
            out.append("# HELP dynamo_program_flops XLA-estimated flops "
                       "per compiled program dispatch")
            out.append("# TYPE dynamo_program_flops gauge")
            for label, costs in items:
                out.append(f'dynamo_program_flops{{program="{label}"}} '
                           f'{costs["flops"]}')
            out.append("# HELP dynamo_program_bytes_accessed "
                       "XLA-estimated bytes accessed per compiled "
                       "program dispatch")
            out.append("# TYPE dynamo_program_bytes_accessed gauge")
            for label, costs in items:
                out.append(
                    f'dynamo_program_bytes_accessed{{program="{label}"}} '
                    f'{costs["bytes_accessed"]}')
        ratios = self.auditor.ratios()
        if ratios:
            out.append("# HELP dynamo_modeled_vs_measured_ratio modeled "
                       "accounting vs XLA-measured truth per series "
                       "(honest: <= band_hi)")
            out.append("# TYPE dynamo_modeled_vs_measured_ratio gauge")
            for series in sorted(ratios):
                out.append(
                    "dynamo_modeled_vs_measured_ratio"
                    f'{{series="{series}"}} {round(ratios[series], 6)}')
        return out

    def debug_payload(self) -> dict:
        """The `/debug/deviceprofile` GET (no ms param) / status body."""
        return {
            "service": self.service,
            "enabled": self.enabled,
            "pid": os.getpid(),
            "max_capture_ms": self.max_capture_ms,
            "registry_size": self.registry.size(),
            "programs": dict(self.registry.items()),
            "drift": self.auditor.states(),
            "harvests": self.harvests,
            "harvest_failures": self.harvest_failures,
            "captures": self.captures,
            "last_capture_dir": self.last_capture_dir,
        }


# ---------------------------------------------------------------------------
# Process singleton (same pattern as flight_recorder.get_recorder)

_profiler = DeviceProfiler()


def get_profiler() -> DeviceProfiler:
    return _profiler


def configure(**kwargs) -> DeviceProfiler:
    return _profiler.configure(**kwargs)


def add_device_profiler_args(parser) -> None:
    """The shared --device-profiler CLI surface (worker)."""
    parser.add_argument("--device-profiler", choices=("on", "off"),
                        default="on",
                        help="device-truth plane: XLA cost-analysis "
                             "harvest at compile time "
                             "(dynamo_program_* metrics), "
                             "modeled-vs-measured drift audit, and "
                             "on-demand bounded jax.profiler capture "
                             "(/debug/deviceprofile?ms=N, control-plane "
                             "profile/<pid>); the capture holds the "
                             "engine thread's phases as engine.<phase> "
                             "events and, with &python=1 (command value "
                             "'N python'), every Python frame too")
    parser.add_argument("--device-profile-max-ms", type=int,
                        default=DEFAULT_MAX_CAPTURE_MS,
                        help="upper bound on one on-demand device "
                             "capture (requests above it are clamped)")
    parser.add_argument("--drift-band-hi", type=float,
                        default=DEFAULT_BAND_HI,
                        help="modeled/measured ratio above which the "
                             "drift auditor strikes (3 consecutive "
                             "out-of-band scrapes PAGE + dump the "
                             "flight recorder)")
    parser.add_argument("--drift-band-lo", type=float,
                        default=DEFAULT_BAND_LO,
                        help="modeled/measured ratio below which the "
                             "drift auditor strikes (default 0: "
                             "under-claiming is not an error — modeled "
                             "series are components of XLA totals)")


def configure_from_args(args, service: str) -> DeviceProfiler:
    """Apply the add_device_profiler_args flags (plus the shared
    --flight-dump-dir capture destination) to the process profiler."""
    return configure(
        service=service,
        enabled=getattr(args, "device_profiler", "on") != "off",
        max_capture_ms=getattr(args, "device_profile_max_ms",
                               DEFAULT_MAX_CAPTURE_MS),
        dump_dir=getattr(args, "flight_dump_dir", None),
        band_hi=getattr(args, "drift_band_hi", DEFAULT_BAND_HI),
        band_lo=getattr(args, "drift_band_lo", DEFAULT_BAND_LO))
