"""A store of compiled step programs, found again without a trace.

JAX's persistent compilation cache is keyed by the lowered module, so a
restarted worker traces and lowers every step program again only to
compute the name of a file it then reads (16 unrolled layers of Python
and 16 Pallas calls a program: 194 s of a 272 s start for Mistral-7B's
107 step programs, PERF.md §6 PR 24).  This store keeps the compiled
executable itself (`jax.experimental.serialize_executable`) under a key
that needs neither: what the call site says built the closure
(`build_key`), the arguments' tree, shapes, dtypes, weak types and
devices, and a fingerprint of everything else a program depends on.

    fn = stored(jax.jit(step, donate_argnums=(1,)), "step", build_key, store)
    out = fn(params, cache, tokens, ...)     # dict -> disk -> the jit object

A disk hit loads the executable.  A miss calls the jit object itself
(JAX's cache still serves the compile), then writes what that call
compiled, one loaded copy either way.  Anything unreadable is a miss that
is logged, counted and overwritten: the store never fails a call that the
jit object would have served.

Where it lives: `<compile cache dir>/program_store/<fingerprint>/`, one
file an entry.  JAX's own eviction looks at `*-cache` files of the top
level only, so it neither sees nor counts these.  The four most recently
used fingerprints are kept (a machine that alternates two checkouts keeps
both warm); deleting any of it is always safe.

What a wrong hit would cost is a wrong program served silently, so the
fingerprint holds the content of every `.py` under `dynamo_tpu/`, the
versions of jax, jaxlib and the backend, the device kind,
`LIBTPU_INIT_ARGS`, `XLA_FLAGS` and the JAX options that change a
lowering, and every entry repeats its whole key in its header and is
refused if that differs from the key it was asked for.

Read-ahead.  A restart used to load its executables one after another,
each at the first call of its shape, on the thread that was warming up (34
of a 71 s start: the TPU runtime's deserialize-and-load, PERF.md §6 PR 30).
An engine now says what its step programs are built from as soon as it
knows (`read_ahead(store, family)`: the part of the build key all its
programs share), and the store loads, on a few background threads and
oldest first (the order a cold start first needed them in), every entry
of this fingerprint whose header carries that family and the engine's
device; no other model's, no other device's.  It is the same
`deserialize_and_load` of the same bytes under the same header check.  A
shape's first call takes the loaded program, or loads an entry that is
still queued itself, or, where a thread has its entry under way, loads
queued entries beside the threads until that one is there: never a second
load of one entry.  An entry that does not load ahead is one `errors`, and its
shape is compiled by the jit object and written again.  Whoever reports
the worker ready calls `join_read_ahead` first: it waits for the loads,
releases what was loaded for an engine of another geometry (other params
or cache: this engine can never ask for it) and keeps the rest for first
calls to take, so that no load runs on any thread once the worker serves.
"""

from __future__ import annotations

import collections
import contextlib
import hashlib
import json
import logging
import os
import pickle
import platform
import shutil
import threading
import time
import zlib
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jaxlib
from jax import tree_util

from dynamo_tpu.runtime import compile_cache
from dynamo_tpu.runtime.logutil import warn_rate_limited

try:
    import zstandard
except ImportError:
    zstandard = None

logger = logging.getLogger(__name__)

SUBDIR = "program_store"
KEPT_FINGERPRINTS = 4
# Loads the read-ahead runs side by side, at most.  On a v5e host of 13
# cores 112 loads took 60-84 s of wall time on 1 thread, 38 on 2, 21 on 4
# and 17 on 8, the last at 1.6 times the thread-seconds (PERF.md §5, PR
# 30): the runtime loads side by side only in part, and more threads than
# four mostly wait for each other.
READ_AHEAD_THREADS = 4
_MAGIC = b"dynamo-program-store-1\n"
_SUFFIX = ".prog"
_PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# JAX options that change what a program lowers to.
_JAX_OPTIONS = ("jax_enable_x64", "jax_default_matmul_precision",
                "jax_default_prng_impl", "jax_threefry_partitionable")


def source_digest(root: str = _PACKAGE) -> str:
    """sha256 over every `.py` under the package, by content and by the
    path inside the package: any edit costs one cold start, moving the
    checkout costs nothing."""
    h = hashlib.sha256()
    for base, dirs, files in os.walk(root):
        dirs.sort()
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(base, name)
            h.update(os.path.relpath(path, root).encode() + b"\0")
            with open(path, "rb") as f:
                h.update(f.read())
            h.update(b"\0")
    return h.hexdigest()


def environment_fingerprint() -> Dict[str, str]:
    """Everything but the program's own key that decides what an
    executable is and whether this process may load it."""
    device = jax.local_devices()[0]
    host = ""
    if device.platform == "cpu":
        # XLA:CPU compiles for the host's instruction set, and loading
        # code built for another host can end in SIGILL.
        try:
            with open("/proc/cpuinfo") as f:
                host = next((line.split(":", 1)[1].strip() for line in f
                             if line.startswith("flags")), "")
        except OSError:
            host = platform.processor()
    return {
        "host_cpu": hashlib.sha256(host.encode()).hexdigest()[:16],
        "source": source_digest(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": device.platform,
        "platform_version": device.client.platform_version,
        "device_kind": device.device_kind,
        "LIBTPU_INIT_ARGS": os.environ.get("LIBTPU_INIT_ARGS", ""),
        "XLA_FLAGS": os.environ.get("XLA_FLAGS", ""),
        **{opt: repr(getattr(jax.config, opt)) for opt in _JAX_OPTIONS},
    }


def _digest(obj) -> str:
    return hashlib.sha256(
        json.dumps(obj, sort_keys=True).encode()).hexdigest()


def split_entry(blob: bytes) -> Tuple[dict, bytes]:
    """An entry file as (header, payload); ValueError if it is none."""
    if not blob.startswith(_MAGIC):
        raise ValueError("not a program-store entry")
    at = len(_MAGIC) + 8
    n = int.from_bytes(blob[len(_MAGIC):at], "little")
    return json.loads(blob[at:at + n]), blob[at + n:]


def entry_header(path: str) -> dict:
    """The header of the entry file at `path`; its payload is not read."""
    with open(path, "rb") as f:
        head = f.read(len(_MAGIC) + 8)
        if len(head) != len(_MAGIC) + 8 or not head.startswith(_MAGIC):
            raise ValueError("not a program-store entry")
        return json.loads(f.read(int.from_bytes(head[len(_MAGIC):],
                                                "little")))


class _Entry:
    """One program in memory: what to call, and XLA's cost analysis of
    its lowering: None until somebody asks, {} where there is none."""

    __slots__ = ("program", "cost")

    def __init__(self, program: Callable, cost: Optional[dict]):
        self.program, self.cost = program, cost


class _Ahead:
    """One entry the read-ahead covers, under the key its header spells:
    `queued`, then `loading` on one thread, then `done` with `entry` (None
    where it did not load); `taken` by a first call that came before a
    thread did, `dropped` at the join."""

    __slots__ = ("key", "state", "entry", "done")

    def __init__(self, key: dict):
        self.key, self.state, self.entry = key, "queued", None
        self.done = threading.Event()


class ProgramStore:
    """One directory of serialized executables for one fingerprint.

    Created by the entry points that serve, beside their
    `enable_compile_cache()` call, and handed to the engine
    (`EngineConfig.program_store`)."""

    def __init__(self, cache_dir: str):
        self.root = os.path.join(cache_dir, SUBDIR)
        self.fingerprint = environment_fingerprint()
        self.dir = os.path.join(self.root, _digest(self.fingerprint)[:16])
        with contextlib.suppress(OSError):
            os.utime(self.dir)          # most recently used: kept longest
        # The read-ahead: what it covers by the entry's path, what no
        # thread has begun yet, and the threads.  One lock for all three.
        self._ahead: Dict[str, _Ahead] = {}
        self._queued: collections.deque = collections.deque()
        self._threads: List[threading.Thread] = []
        self._ahead_lock = threading.Lock()
        self._ahead_began = 0.0

    # -- entries ----------------------------------------------------------

    def path_for(self, key: dict) -> str:
        return os.path.join(self.dir, _digest(key)[:40] + _SUFFIX)

    def read(self, key: dict) -> Optional[Tuple[bytes, Optional[dict]]]:
        """(payload, cost analysis) of the entry under `key`, or None.  An
        entry that is there but cannot be trusted (truncated, garbled,
        written under another key or fingerprint) counts as an error."""
        path = self.path_for(key)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except FileNotFoundError:
            return None
        except OSError as e:
            self.error("cannot read %s: %s", path, e)
            return None
        try:
            header, payload = split_entry(blob)
            if header["fingerprint"] != self.fingerprint:
                raise ValueError("written under another fingerprint")
            if header["key"] != key:
                raise ValueError("written under another key")
            if (len(payload) != header["payload_bytes"]
                    or hashlib.sha256(payload).hexdigest()
                    != header["payload_sha256"]):
                raise ValueError("payload truncated or altered")
        except (ValueError, KeyError, TypeError) as e:
            self.error("entry %s refused: %s", path, e)
            return None
        return payload, header.get("cost")

    def write(self, key: dict, payload: bytes,
              cost: Optional[dict]) -> None:
        """One whole file or none: a temporary name of this writer's own,
        then `os.replace`."""
        header = json.dumps({
            "fingerprint": self.fingerprint, "key": key, "cost": cost,
            "payload_bytes": len(payload),
            "payload_sha256": hashlib.sha256(payload).hexdigest(),
        }).encode()
        path = self.path_for(key)
        tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
        try:
            if not os.path.isdir(self.dir):
                os.makedirs(self.dir, exist_ok=True)
                self._evict_old_fingerprints()
            with open(tmp, "wb") as f:
                f.write(_MAGIC + len(header).to_bytes(8, "little") + header)
                f.write(payload)
            os.replace(tmp, path)
        except OSError as e:
            self.error("cannot write %s: %s", path, e)
            with contextlib.suppress(OSError):
                os.unlink(tmp)

    def _evict_old_fingerprints(self) -> None:
        """Called when this fingerprint's directory is first made: keep it
        and the most recently used others, `KEPT_FINGERPRINTS` in all."""
        try:
            others = [e.path for e in os.scandir(self.root)
                      if e.is_dir() and e.path != self.dir]
            others.sort(key=os.path.getmtime, reverse=True)
        except OSError:
            return
        for path in others[KEPT_FINGERPRINTS - 1:]:
            logger.info("program store: dropping fingerprint %s",
                        os.path.basename(path))
            shutil.rmtree(path, ignore_errors=True)

    # -- read-ahead -------------------------------------------------------

    def load(self, key: dict, devices: list) -> Optional[_Entry]:
        """The entry under `key` read, checked and loaded on `devices`, on
        the calling thread, its seconds added to `stage="store_read"`;
        None where there is none, or (one `errors`) none that loads."""
        t0 = time.monotonic()
        found = self.read(key)
        if found is None:
            return None
        payload, cost = found
        try:
            program = _load(payload, devices)
        except Exception as e:
            self.error("entry %s does not load: %s: %s",
                       self.path_for(key), type(e).__name__, e)
            return None
        compile_cache.note_program_store(
            read_seconds=time.monotonic() - t0)
        return _Entry(program, cost)

    def held_for(self, family: dict, device) -> List[dict]:
        """The keys of the entries built from `family` for `device`, as
        their headers spell them, oldest file first.  A file that cannot
        be read here is left to the first call that asks for it."""
        try:
            files = sorted((e for e in os.scandir(self.dir)
                            if e.name.endswith(_SUFFIX)),
                           key=lambda e: e.stat().st_mtime_ns)
        except OSError:
            return []
        keys = []
        for e in files:
            try:
                header = entry_header(e.path)
                key = header["key"]
                build = json.loads(key["build"])
                if (header["fingerprint"] == self.fingerprint
                        and key["device"] == device.id
                        and all(k in build and build[k] == v
                                for k, v in family.items())
                        and self.path_for(key) == e.path):
                    keys.append(key)
            except (OSError, ValueError, KeyError, TypeError):
                continue
        return keys

    def read_ahead(self, family: dict, device,
                   threads: Optional[int] = None) -> int:
        """Begin loading every entry `held_for(family, device)` on
        background threads; returns how many that is.  `threads` is for
        tests and measurements (0: queue only, the caller runs
        `_load_ahead` itself); a served process leaves it to the rule."""
        keys = self.held_for(family, device)
        with self._ahead_lock:
            fresh = {path: _Ahead(key) for key in keys
                     if (path := self.path_for(key)) not in self._ahead}
            self._ahead.update(fresh)
            self._queued.extend(fresh.values())
            n = len(fresh)
            if threads is None:
                threads = min(n, READ_AHEAD_THREADS,
                              max(1, (os.cpu_count() or 1) // 2))
            new = [threading.Thread(
                target=self._load_ahead, args=(device,), daemon=True,
                name=f"program-store-read-ahead-{i}")
                for i in range(threads)]
            self._threads += new
        self._ahead_began = time.monotonic()
        for t in new:
            t.start()
        logger.info("program store: reading %d entries ahead on %d "
                    "thread(s)", n, len(new))
        return n

    def _load_ahead(self, device) -> None:
        """A read-ahead thread: load queued entries until none is left."""
        while self._load_next(device):
            pass

    def _load_next(self, device) -> bool:
        """Load the oldest entry still queued; False if there is none."""
        with self._ahead_lock:
            while self._queued and self._queued[0].state != "queued":
                self._queued.popleft()          # taken or dropped meanwhile
            if not self._queued:
                return False
            rec = self._queued.popleft()
            rec.state = "loading"
        try:
            rec.entry = self.load(rec.key, [device])
        except Exception as e:          # a thread has nobody to raise to
            self.error("read-ahead of %s: %s: %s",
                       self.path_for(rec.key), type(e).__name__, e)
        finally:
            rec.state = "done"
            rec.done.set()
        return True

    def take(self, key: dict, devices: list) -> Optional[_Entry]:
        """The loaded program under `key` for a shape's first call: the
        read-ahead's (waiting for its load if that is under way), else
        loaded here.  One `hits` either way, and one `prefetched` where a
        thread had at least begun it; None is a miss."""
        path = self.path_for(key)
        with self._ahead_lock:
            rec = self._ahead.get(path)
            if rec is not None and rec.key != key:
                rec = None
            if rec is not None:
                del self._ahead[path]
                if rec.state == "queued":
                    rec.state, rec = "taken", None
        if rec is None:
            entry = self.load(key, devices)
        else:
            # Not idle while that load is under way: the caller loads
            # what is queued behind it, which it will ask for next.
            while not rec.done.is_set() and self._load_next(devices[0]):
                pass
            rec.done.wait()
            entry = rec.entry
        if entry is not None:
            compile_cache.note_program_store("hits")
            if rec is not None:
                compile_cache.note_program_store("prefetched")
        return entry

    def join_read_ahead(self, fixed: Optional[str]) -> None:
        """End the read-ahead before the worker serves.  `fixed` spells
        the engine's never-changing leading arguments as the keys do:
        entries keyed for others are not begun any more, and released if
        loaded (`prefetch_unclaimed`: the store holds another geometry's
        programs); the loads of the rest are waited for, and what no first
        call has taken stays loaded for the one that will."""
        t0 = time.monotonic()
        with self._ahead_lock:
            if not self._ahead and not self._threads:
                return
            for rec in self._queued:
                if rec.state == "queued" and rec.key["fixed"] != fixed:
                    rec.state = "dropped"
            threads, self._threads = self._threads, []
        for t in threads:
            t.join()
        with self._ahead_lock:
            others = [path for path, rec in self._ahead.items()
                      if rec.key["fixed"] != fixed]
            released = sum(self._ahead.pop(path).entry is not None
                           for path in others)
            kept = sum(rec.entry is not None
                       for rec in self._ahead.values())
        compile_cache.note_program_store("prefetch_unclaimed", count=released)
        now = time.monotonic()
        logger.info("program store: read-ahead joined %.1f s after it began, "
                    "%.1f s of them here: %d loaded and not yet asked for, "
                    "%d of another geometry released",
                    now - self._ahead_began, now - t0, kept, released)

    def error(self, fmt: str, *args) -> None:
        """Every error is counted; the log gets one line in ten minutes."""
        compile_cache.note_program_store("errors")
        warn_rate_limited(logger, "program_store.error", 600.0,
                          "program store: " + fmt + " (compiling instead; "
                          "further errors are only counted)", *args)


def open_store(cache_dir: str) -> Optional[ProgramStore]:
    """The store under the compile-cache directory in effect, or None
    where it cannot be set up; never raises into an entry point."""
    try:
        store = ProgramStore(cache_dir)
        logger.info("program store: %s (%d entries)", store.dir,
                    len(os.listdir(store.dir))
                    if os.path.isdir(store.dir) else 0)
        return store
    except Exception:
        logger.exception("program store: not available")
        compile_cache.note_program_store("errors")
        return None


def _leaf_signature(leaf) -> tuple:
    dtype = getattr(leaf, "dtype", None)
    return (getattr(leaf, "shape", ()),
            dtype if dtype is not None else type(leaf),
            getattr(leaf, "weak_type", dtype is None),
            getattr(leaf, "sharding", None))


def _signature(args) -> tuple:
    leaves, tree = tree_util.tree_flatten(args)
    return (tree, *map(_leaf_signature, leaves))


def _spell(signature: tuple) -> Tuple[str, Optional[set]]:
    """A signature as text for the key on disk, and the devices its
    arrays live on (None: on more than one device each, not ours)."""
    tree, *leaves = signature
    devices: set = set()
    words = [str(tree)]
    for shape, dtype, weak, sharding in leaves:
        kind = ""
        if sharding is not None:      # numpy and scalars: wherever jit puts them
            if len(sharding.device_set) != 1:
                return "", None
            (device,) = sharding.device_set
            devices.add(device)
            if sharding.memory_kind not in (None,
                                            device.default_memory().kind):
                kind = "@" + sharding.memory_kind
        words.append(f"{getattr(dtype, '__name__', dtype)}"
                     f"{list(shape)}{'w' if weak else ''}{kind}")
    return " ".join(words), devices


class StoredProgram:
    """A `jax.jit` object whose executables come from the store.

    A shape's first call looks on disk.  A hit loads the executable and
    every later call is that `Compiled`'s own.  A miss calls the jit
    object, exactly as a process without a store does (tracing, lowering
    and compiling through JAX's cache at the jit call's own cost: the
    ahead-of-time `lower()` measured a quarter to a third slower on the
    chip), keeps calling it, and writes what it compiled:
    `jitted.lower(*args).compile()` after the call is served from JAX's
    in-memory caches and is the very executable the call loaded, so there
    is one copy of it on the device either way.

    `fixed_argnums` are the leading arguments that one engine never
    changes the form of (params, cache): their signature is taken at the
    first call, so a warm call costs a look at the small arguments and
    one dict look-up.  Both the jit object and `Compiled` check the types
    of what they are called with, so an engine that did change them gets
    another compile or a TypeError, never another program's answer."""

    def __init__(self, jitted, name: str, build_key: str,
                 store: ProgramStore, fixed_argnums: int = 0):
        self._jitted = jitted
        self._name = name
        self._build_key = build_key
        self._store = store
        self._n_fixed = fixed_argnums
        self._fixed: Optional[tuple] = None
        # By the small arguments' signature and by the entry's path: two
        # signatures may spell one key (a numpy array where another call
        # had a jax.Array) and must share one loaded copy.
        self._programs: Dict[tuple, _Entry] = {}
        self._by_path: Dict[str, _Entry] = {}
        # Analyses asked for before a shape's first call (the profiler's
        # harvest runs right before the dispatch): the miss writes them.
        self._asked: Dict[tuple, Optional[dict]] = {}
        self._lock = threading.Lock()
        self.lower = jitted.lower       # what DeviceProfiler and tools use

    def __call__(self, *args):
        small = _signature(args[self._n_fixed:])
        entry = self._programs.get(small)
        if entry is None:
            return self._first_call(small, args)
        return entry.program(*args)

    def cost_analysis(self, *args) -> Optional[dict]:
        """XLA's cost analysis of the lowered program for these
        arguments, as `jitted.lower(*args).cost_analysis()` gives it; from
        the entry when it holds one, so that a start from the store
        lowers nothing for it."""
        small = _signature(args[self._n_fixed:])
        with self._lock:
            entry = self._programs.get(small) or self._from_disk(small, args)
            if entry is not None and entry.cost is not None:
                return entry.cost or None
            # One lowering; the call that follows (or went before) shares
            # it through JAX's own cache.
            cost = _cost_of(self._jitted.lower(*args))
            if entry is None:
                self._asked[small] = cost
            else:
                entry.cost = cost
            return cost or None

    # -- a shape's first call: disk, then the jit object ------------------

    def _first_call(self, small: tuple, args: Sequence):
        with self._lock:
            entry = self._programs.get(small) or self._from_disk(small, args)
            if entry is not None:
                return entry.program(*args)
            key, devices = self._key(small, args)
            compile_cache.note_program_store("misses")
            hits = compile_cache.cache_hits_on_this_thread()
            out = self._jitted(*args)
            entry = self._programs[small] = _Entry(
                self._jitted, self._asked.pop(small, None))
            self._by_path[self._store.path_for(key)] = entry
            if (devices[0].platform == "cpu"
                    and compile_cache.cache_hits_on_this_thread() != hits):
                # XLA:CPU (jaxlib 0.9.0) serializes an executable that it
                # read from JAX's cache without its kernels: the copy loads
                # and then fails at its first dispatch ("Function ... not
                # found").  Only what the compiler itself produced is
                # stored there.
                return out
            try:
                # Shapes and shardings are all lower() reads: the donated
                # arguments, deleted by now, still have theirs.
                payload = _dump(self._jitted.lower(*args).compile(), devices)
            except Exception as e:
                self._store.error("%s cannot be serialized: %s: %s",
                                  self._name, type(e).__name__, e)
            else:
                self._store.write(key, payload, entry.cost)
            return out

    def _key(self, small: tuple, args: Sequence):
        """(the entry's key, [its device]); (None, None) where the
        arguments are sharded or spread over devices: not ours."""
        if self._fixed is None:
            self._fixed = _signature(args[:self._n_fixed])
        fixed_words, fixed_devs = _spell(self._fixed)
        small_words, small_devs = _spell(small)
        if fixed_devs is None or small_devs is None:
            return None, None
        devices = sorted(fixed_devs | small_devs, key=lambda d: d.id) \
            or [jax.local_devices()[0]]
        if len(devices) != 1:
            return None, None
        return {"name": self._name, "build": self._build_key,
                "fixed": fixed_words, "call": small_words,
                "device": devices[0].id}, devices

    def _from_disk(self, small: tuple, args: Sequence) -> Optional[_Entry]:
        """The entry for these arguments if memory (under another
        signature) or the disk holds it; the jit object itself where the
        store has no business.  None is a miss."""
        key, devices = self._key(small, args)
        if key is None:
            entry = _Entry(self._jitted, None)
        else:
            path = self._store.path_for(key)
            entry = self._by_path.get(path)
            if entry is None:
                t0 = time.monotonic()
                entry = self._store.take(key, devices)
                compile_cache.note_program_store(
                    wait_seconds=time.monotonic() - t0)
                if entry is None:
                    return None
                self._by_path[path] = entry
        self._programs[small] = entry
        return entry


def _cost_of(lowered) -> dict:
    """`Lowered.cost_analysis()` as a plain dict; empty where the backend
    has none to give (TPU lowerings with kernels return None), which is an
    answer too: asked once, it is stored and not asked again."""
    try:
        cost = lowered.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else None
        return {k: float(v) for k, v in (cost or {}).items()
                if isinstance(v, (int, float))}
    except Exception:
        return {}


# Executables shrink to a third or less, and the directory shares a disk
# with JAX's cache.  zstandard where the installation has it (JAX's cache
# makes the same choice), zlib otherwise; the first byte says which, and an
# entry this process cannot unpack is one more that is compiled again.
def _compress(data: bytes) -> bytes:
    if zstandard is not None:
        return b"Z" + zstandard.ZstdCompressor(level=3).compress(data)
    return b"z" + zlib.compress(data, 1)


def _decompress(payload: bytes) -> bytes:
    if payload[:1] == b"Z":
        if zstandard is None:
            raise ValueError("entry is zstandard-compressed")
        return zstandard.ZstdDecompressor().decompress(payload[1:])
    return zlib.decompress(payload[1:])


def _dump(compiled, devices) -> bytes:
    from jax.experimental.serialize_executable import serialize

    bound = {d for s in tree_util.tree_leaves(compiled.input_shardings)
             for d in s.device_set}
    if bound - set(devices):
        raise ValueError(f"compiled for {sorted(d.id for d in bound)}, "
                         f"keyed for {[d.id for d in devices]}")
    return _compress(pickle.dumps(serialize(compiled)))


def _load(payload: bytes, devices) -> Callable:
    from jax.experimental.serialize_executable import deserialize_and_load

    # Only bytes this program wrote, under a header it checked.
    blob, in_tree, out_tree = pickle.loads(_decompress(payload))
    return deserialize_and_load(blob, in_tree, out_tree,
                                backend=devices[0].client,
                                execution_devices=devices)


def stored(jitted, name: str, build_key: str,
           store: Optional[ProgramStore], fixed_argnums: int = 0):
    """`jitted` served from `store`, or `jitted` itself where the store
    has no business: no store, not a plain `jax.jit` object (pp stage
    programs and the sharded builders hand back wrappers), or a process
    that is one of several (a multihost mesh)."""
    if (store is None or not hasattr(jitted, "lower")
            or jax.process_count() != 1):
        return jitted
    return StoredProgram(jitted, name, build_key, store, fixed_argnums)


def read_ahead(store: Optional[ProgramStore], family: dict) -> None:
    """Start `store`'s read-ahead for a meshless engine whose step
    programs are all built from `family` (the part of `build_key` they
    share); nothing where `stored()` would hand its programs back
    untouched, and never an exception into an engine's construction."""
    if store is None or jax.process_count() != 1:
        return
    try:
        store.read_ahead(family, jax.local_devices()[0])
    except Exception:
        logger.exception("program store: no read-ahead")
        compile_cache.note_program_store("errors")


def join_read_ahead(store: Optional[ProgramStore], fixed_args: tuple) -> None:
    """`store.join_read_ahead` for the engine whose programs all take
    `fixed_args` first (what `fixed_argnums` counts: params, cache)."""
    if store is not None:
        store.join_read_ahead(_spell(_signature(fixed_args))[0] or None)
