"""Slice topology plane: the fleet's declarative model of WHERE compute
lives (ISSUE 16, ROADMAP item 1 — the paper's "prefill v5p-16 + decode
v5p-16" deployment needs a topology model, not flag soup).

One `SliceSpec` describes a worker's TPU slice the way the planner and
router need to reason about it:

- **mesh shape** — the (dp, pp, sp, ep, tp) degrees the worker's
  `make_sharded_step` runs (parallel/mesh.MeshConfig.shape);
- **plane features** — which serving planes the slice composes
  (parallel/sharding.PlaneSpec: int8 KV, packed prefill, spec decode,
  decode windows);
- **per-chip HBM** — so "free HBM" is a byte quantity, not a
  percentage that reads the same on a v5e-1 and a v5p-16;
- **role** — prefill | decode | both | encode, the disagg cell shape
  (DistServe/Splitwise-style phase-fitted pools);
- **fabric** — the device-transfer plane this slice is reachable on
  (`pjrt`, `local:<pid>`, or empty for host-wire-only builds).

Workers derive their spec from EngineConfig + CLI (`from_parts` /
`worker/main.py --slice`), publish it in their instance records
(`llm/discovery.register_llm` metadata), and the fleet brain reads it:
`KvRouter.find_best_match` and `pick_donor` weigh per-slice free HBM and
fabric reachability, `planner.core.LoadPlanner.plan_step` scales
heterogeneous cells per role, and `validate_placement` refuses
mesh-blind decisions (a decode role on a prefill-only slice fails the
bench gate, not production).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field, replace
from typing import Dict, Mapping, Optional, Sequence, Tuple

AXES = ("dp", "pp", "sp", "ep", "tp")

ROLES = ("prefill", "decode", "both", "encode")

_MESH_TOKEN = re.compile(r"^(?:(?:dp|pp|sp|ep|tp)\d+)(?:x(?:dp|pp|sp|ep|tp)\d+)*$")
_AXIS_DEG = re.compile(r"(dp|pp|sp|ep|tp)(\d+)")


@dataclass(frozen=True)
class SliceSpec:
    """Declarative description of one worker's slice; the instance-record
    schema the fleet brain routes and plans against."""

    mesh: Tuple[int, int, int, int, int] = (1, 1, 1, 1, 1)
    role: str = "both"
    kv_quant: str = "none"
    features: Tuple[str, ...] = ()
    hbm_per_chip_bytes: int = 0
    fabric: str = ""

    def __post_init__(self):
        if len(self.mesh) != len(AXES):
            raise ValueError(
                f"SliceSpec.mesh must carry {len(AXES)} degrees "
                f"{AXES}, got {self.mesh!r}")
        if self.role not in ROLES:
            raise ValueError(
                f"SliceSpec.role must be one of {ROLES}, got {self.role!r}")

    # -- derived geometry --------------------------------------------------

    @property
    def chips(self) -> int:
        n = 1
        for d in self.mesh:
            n *= int(d)
        return n

    @property
    def total_hbm_bytes(self) -> int:
        return self.chips * int(self.hbm_per_chip_bytes)

    def axis(self, name: str) -> int:
        return int(self.mesh[AXES.index(name)])

    def describe(self) -> str:
        """Compact mesh descriptor, `MeshConfig.describe()`-compatible:
        "sp2xtp2", "tp4", or "single"."""
        parts = [f"{a}{n}" for a, n in zip(AXES, self.mesh) if int(n) > 1]
        return "x".join(parts) or "single"

    def mesh_config(self):
        """The parallel/mesh.MeshConfig this spec names (imported lazily:
        the fleet brain must stay importable without jax)."""
        from dynamo_tpu.parallel.mesh import MeshConfig

        return MeshConfig(*(int(d) for d in self.mesh))

    # -- reachability ------------------------------------------------------

    def reachable(self, other: "SliceSpec") -> bool:
        """Can THIS slice pull the OTHER slice's KV over a device fabric?
        pjrt peers interconnect across hosts; the local fabric only spans
        one process.  Anything else rides the host-staged wire — still
        correct, just not device-direct (the router treats it as a
        weaker donor, never an invalid one)."""
        if not self.fabric or not other.fabric:
            return False
        if self.fabric == "pjrt" and other.fabric == "pjrt":
            return True
        return self.fabric == other.fabric  # local:<pid> must match

    def serves_role(self, role: str) -> bool:
        """Can a request phase `role` land on this slice?  "both" serves
        prefill and decode; dedicated slices serve only their phase."""
        if role == "both":
            return self.role == "both"
        return self.role == role or self.role == "both"

    # -- wire codec --------------------------------------------------------

    def to_dict(self) -> dict:
        return {
            "mesh": [int(d) for d in self.mesh],
            "role": self.role,
            "kv_quant": self.kv_quant,
            "features": list(self.features),
            "hbm_per_chip_bytes": int(self.hbm_per_chip_bytes),
            "fabric": self.fabric,
        }

    @staticmethod
    def from_dict(d: Optional[Mapping]) -> Optional["SliceSpec"]:
        """Tolerant decode: an instance record from an older worker (no
        slice published) or a version-skewed one yields None / defaults —
        the fleet brain must keep routing a mixed fleet, never fail it
        over topology metadata."""
        if not isinstance(d, Mapping):
            return None
        try:
            mesh = tuple(int(x) for x in d.get("mesh", (1,) * len(AXES)))
            if len(mesh) != len(AXES):
                return None
            role = str(d.get("role", "both"))
            return SliceSpec(
                mesh=mesh,
                role=role if role in ROLES else "both",
                kv_quant=str(d.get("kv_quant", "none")),
                features=tuple(str(f) for f in d.get("features", ())),
                hbm_per_chip_bytes=int(d.get("hbm_per_chip_bytes", 0)),
                fabric=str(d.get("fabric", "")),
            )
        except (TypeError, ValueError):
            return None

    # -- construction ------------------------------------------------------

    @staticmethod
    def from_parts(mesh_config=None, plane=None, *, role: str = "both",
                   kv_quant: str = "none", hbm_per_chip_bytes: int = 0,
                   fabric: str = "",
                   extra_features: Sequence[str] = ()) -> "SliceSpec":
        """Derive the spec a worker publishes from what it actually runs:
        its MeshConfig (None = meshless single chip) and its PlaneSpec
        (None = bare decode plane)."""
        mesh = tuple(int(d) for d in mesh_config.shape) if mesh_config \
            else (1,) * len(AXES)
        feats = list(extra_features)
        if plane is not None:
            if getattr(plane, "quant", False):
                kv_quant = "int8"
            for attr, name in (("spec", "spec"), ("fused", "fused"),
                               ("use_pallas", "pallas"),
                               ("dp_attention", "dp_attention"),
                               ("dp_local", "dp_local")):
                if getattr(plane, attr, False):
                    feats.append(name)
            if getattr(plane, "window", 1) and plane.window > 1:
                feats.append(f"window{plane.window}")
        if kv_quant == "int8" and "int8" not in feats:
            feats.append("int8")
        return SliceSpec(mesh=mesh, role=role, kv_quant=kv_quant,
                         features=tuple(dict.fromkeys(feats)),
                         hbm_per_chip_bytes=int(hbm_per_chip_bytes),
                         fabric=fabric)


def parse_slice(spec: str) -> SliceSpec:
    """Parse the worker CLI's declarative `--slice` string.

    Comma-separated tokens, order-free:

      mesh descriptor   "tp2", "sp2xtp2", "single"  (axis-degree pairs)
      kv mode           "int8" | "bf16"
      role              "role=prefill" | "role=decode" | "role=both"
      features          "packed" (packed prefill), "spec" (spec decode),
                        "windowN" (decode window N), "dp_attention"

    Example: `--slice "sp2xtp2,int8,packed,role=prefill"` replaces the
    loose `--sp 2 --tp 2 --kv-quant int8 --packed-prefill --role
    prefill` plumbing with the ONE declarative spec `make_sharded_step`
    and the published instance record both derive from.
    """
    mesh = [1] * len(AXES)
    role = "both"
    kv_quant = "none"
    features = []
    for raw in spec.split(","):
        tok = raw.strip().lower()
        if not tok:
            continue
        if tok == "single":
            continue
        if _MESH_TOKEN.match(tok):
            for axis, deg in _AXIS_DEG.findall(tok):
                mesh[AXES.index(axis)] = int(deg)
            continue
        if tok in ("int8", "bf16", "none"):
            kv_quant = "int8" if tok == "int8" else "none"
            continue
        if tok.startswith("role="):
            role = tok.split("=", 1)[1]
            if role not in ROLES:
                raise ValueError(
                    f"--slice role must be one of {ROLES}, got {role!r}")
            continue
        if tok in ("packed", "packed_prefill"):
            features.append("packed_prefill")
            continue
        if tok in ("spec", "dp_attention", "dp_local", "pallas"):
            features.append(tok)
            continue
        m = re.match(r"^window(\d+)$", tok)
        if m:
            features.append(tok)
            continue
        raise ValueError(
            f"unrecognized --slice token {raw.strip()!r} "
            "(want a mesh descriptor like 'sp2xtp2', 'int8', "
            "'role=prefill', or a feature: packed/spec/windowN)")
    return SliceSpec(mesh=tuple(mesh), role=role, kv_quant=kv_quant,
                     features=tuple(dict.fromkeys(features)))


# -- fleet-brain reads -----------------------------------------------------


def free_hbm_bytes(spec: Optional[SliceSpec],
                   metrics=None) -> int:
    """Per-slice free HBM in BYTES: the slice's total capacity scaled by
    the worker's last published cache occupancy (ForwardPassMetrics
    kv_stats.gpu_cache_usage_perc).  A spec without HBM figures (older
    worker, CPU rig) reports 0 — "unknown" must sort below any slice
    that actually advertised headroom, never above."""
    if spec is None or spec.total_hbm_bytes <= 0:
        return 0
    used = 0.0
    kv = getattr(metrics, "kv_stats", None)
    if kv is not None:
        used = min(1.0, max(0.0, float(
            getattr(kv, "gpu_cache_usage_perc", 0.0) or 0.0)))
    return int(spec.total_hbm_bytes * (1.0 - used))


def stable_id_key(worker_id) -> tuple:
    """Total-order key over mixed int/str worker ids: ints compare
    numerically among themselves (lease id 2 beats 10), strings
    lexically, and the type tag keeps a mixed fleet deterministic.  The
    one donor tie-break key — pick_donor's old inline version compared
    `(0, w, "")` against `(1, 0, str(w))`, which ordered ints before
    every string regardless of value and made equal-overlap ties flap
    between replica routers once a fleet minted string instance ids."""
    if isinstance(worker_id, bool) or not isinstance(worker_id, int):
        return (1, 0, str(worker_id))
    return (0, int(worker_id), "")


def donor_preference_key(worker_id, overlap_blocks: int, *,
                         reachable: bool = False,
                         free_hbm: int = 0) -> tuple:
    """Sort key for donor candidates, higher = better: device-fabric
    reachability first (a device pull spares the host copy and the
    wire; the ratio is not measured on the chip), then
    prefix coverage, then free HBM (a donor about to evict under memory
    pressure is a worse bet), with the stable id key breaking exact ties
    ASCENDING so replica routers agree."""
    neg_id = tuple(-x if isinstance(x, int) else _neg_str(x)
                   for x in stable_id_key(worker_id))
    return (1 if reachable else 0, int(overlap_blocks), int(free_hbm),
            neg_id)


def _neg_str(s: str) -> tuple:
    """Lexicographic negation: ascending-id preference inside a max()."""
    return tuple(-ord(c) for c in s)


def validate_placement(role: str, spec: Optional[SliceSpec]) -> Tuple[bool, str]:
    """Is deploying `role` work onto `spec` topology-sane?  The planner
    consults this before spawning/scaling; tests/test_topology.py makes
    a mesh-blind decision (decode role on a prefill slice) and asserts it
    FAILS here.  A worker without a published spec is accepted — the
    mixed-fleet rule again — but a spec that names a different dedicated
    role is a refusal, not a warning."""
    if role not in ROLES:
        return False, f"unknown role {role!r} (want one of {ROLES})"
    if spec is None:
        return True, "no SliceSpec published; placement unconstrained"
    if role in ("prefill", "decode") and spec.role in ("prefill", "decode") \
            and spec.role != role:
        return False, (
            f"role {role!r} cannot be placed on a dedicated "
            f"{spec.role!r} slice ({spec.describe()}); spawn a "
            f"{role} cell with its own mesh instead")
    if role == "both" and spec.role in ("prefill", "decode"):
        return False, (
            f"aggregated (both) serving cannot ride a dedicated "
            f"{spec.role!r} slice ({spec.describe()})")
    return True, "ok"


def place_role(role: str, slices: Dict[object, Optional[SliceSpec]],
               metrics: Optional[Dict[object, object]] = None):
    """Pick the worker whose slice should absorb more `role` work: the
    topology-valid candidate with the most free HBM, stable-id
    tie-broken.  Returns None when no live slice can serve the role —
    the planner's cue to SPAWN a cell for it rather than overload a
    mismatched one."""
    best = None
    best_key = None
    for wid, spec in slices.items():
        ok, _ = validate_placement(role, spec)
        if not ok:
            continue
        if spec is not None and role in ("prefill", "decode") \
                and not spec.serves_role(role):
            continue
        key = (free_hbm_bytes(spec, (metrics or {}).get(wid)),
               tuple(-x if isinstance(x, int) else _neg_str(x)
                     for x in stable_id_key(wid)))
        if best_key is None or key > best_key:
            best, best_key = wid, key
    return best
