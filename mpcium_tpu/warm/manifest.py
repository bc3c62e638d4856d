"""The warm work-list: a pure function of the committed compile surface.

HACCLE's observation (PAPERS.md) is that an MPC protocol's compile
surface is *data* — so ahead-of-time specialization is a table walk,
not a heuristic. ``COMPILE_SURFACE.json`` (mpcshape, drift-gated) is
that table: per engine, the ``compile_watch.begin`` template with every
signature dimension classified constant/knob/bucketed/unbounded. This
module instantiates it into the concrete list of (engine, shape)
signatures a node will ever request in serving:

- serving-reachable templates only (``serving: false`` records — bench
  fabrics with no node path — are excluded);
- the batch dimension ranges over ``engine/buckets.BUCKETS`` (the
  scheduler drains pow-2 chunks, so these are the ONLY B values the
  engines are ever handed);
- knob dimensions (quorum size, key type, MtA backend, new threshold)
  come from :class:`WarmKnobs` — derived from config, finite by
  construction. A knob dim with no configured values is a **gap**,
  reported loudly (``coverage_check`` / ``make warmcheck``), never
  silently skipped;
- entries are ordered hot-first by observed traffic (the node's own
  ``COMPILE_LEDGER.json``), then cheap-first (small B) so a budget-cut
  pre-warm covers the most value.

The manifest is keyed by the ``perf/envfp.py`` host fingerprint plus
jax/jaxlib versions: compiled artifacts are machine-feature- and
toolchain-stamped, and a key mismatch means every cached executable is
stale — skipped and recompiled, never trusted (``key_matches``).

Pure stdlib on purpose (like ``engine/buckets``): building or checking
a manifest must never pay a jax import or a backend bring-up.
"""
from __future__ import annotations

import itertools
import json
import os
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from ..analysis.shape.surface import (
    SURFACE_BASENAME,
    _DIM_RE,
    load_surface,
    shape_predicted,
)
from ..engine.buckets import BUCKETS
from ..perf import envfp

REPORT_BASENAME = "WARM_MANIFEST.json"  # the prewarm report, beside the cache

# engine → scheme family: ``warm_schemes`` selects families, and the
# party-level protocol engines ride along with their scheme
ENGINE_SCHEME = {
    "eddsa.sign": "eddsa",
    "gg18.sign": "ecdsa",
    "party.ecdsa": "ecdsa",
    "party.eddsa": "eddsa",
    "dkg.run": "dkg",
    "party.dkg": "dkg",
    "reshare.run": "reshare",
    "party.reshare": "reshare",
}

ALL_SCHEMES = ("eddsa", "ecdsa", "dkg", "reshare")


@dataclass(frozen=True)
class WarmKnobs:
    """Concrete values for every knob-classed surface dimension. Finite
    by construction: these are configuration, not traffic."""

    q: Tuple[int, ...] = (2,)
    key_type: Tuple[str, ...] = ("ed25519", "secp256k1")
    mta_impl: Tuple[str, ...] = ("paillier", "ot")
    t_new: Tuple[int, ...] = (1,)

    def values_for(self, name: str) -> Tuple[str, ...]:
        vals = getattr(self, name, ())
        return tuple(str(v) for v in vals)

    def to_json(self) -> Dict[str, list]:
        return {
            "q": list(self.q),
            "key_type": list(self.key_type),
            "mta_impl": list(self.mta_impl),
            "t_new": list(self.t_new),
        }


def default_knobs(threshold: Optional[int] = None) -> WarmKnobs:
    """Knob values for a t-of-n deployment: the serving quorum is t+1
    and reshares rotate to the same threshold. The MtA backend axis is
    whatever this process would actually serve (``MPCIUM_MTA``) plus
    ``ot`` — the OT backend's active-security check kernels (ISSUE 16)
    ride the gg18.sign signature, and a node must be able to flip to
    the checked backend without hitting a cold compile."""
    t = 1 if threshold is None else int(threshold)
    if t < 1:
        raise ValueError(f"need threshold >= 1, got {t}")
    mta = os.environ.get("MPCIUM_MTA", "paillier")
    return WarmKnobs(
        q=(t + 1,),
        mta_impl=(mta,) if mta == "ot" else (mta, "ot"),
        t_new=(t,),
    )


def knobs_from_config(cfg) -> WarmKnobs:
    return default_knobs(threshold=cfg.mpc_threshold)


# -- the environment key -----------------------------------------------------


def jaxlib_version() -> Optional[str]:
    """Like envfp.jax_version: read the already-imported module first,
    fall back to package metadata — never import jaxlib here."""
    mod = sys.modules.get("jaxlib")
    if mod is not None:
        v = getattr(mod, "__version__", None)
        if v:
            return v
    try:
        from importlib.metadata import version

        return version("jaxlib")
    except Exception:  # noqa: BLE001 — fingerprinting must never raise
        return None


def manifest_key() -> Dict[str, Optional[str]]:
    """What a compiled executable's validity depends on: the host CPU
    feature set (AOT artifacts are machine-feature-stamped; containers
    live-migrate) and the jax/jaxlib pair that traced and lowered it."""
    return {
        "host": envfp.host_fingerprint(),
        "jax": envfp.jax_version(),
        "jaxlib": jaxlib_version(),
    }


def key_matches(stored: Optional[Dict[str, object]],
                current: Optional[Dict[str, object]] = None
                ) -> Tuple[bool, str]:
    """(ok, reason). A stale key means every artifact under it is
    untrusted — the caller skips and recompiles, loudly."""
    if current is None:
        current = manifest_key()
    if not isinstance(stored, dict):
        return False, "no environment key stored"
    for k in ("host", "jax", "jaxlib"):
        if stored.get(k) != current.get(k):
            return False, (
                f"{k} changed: {stored.get(k)!r} -> {current.get(k)!r}"
            )
    return True, "ok"


# -- traffic priority --------------------------------------------------------


def load_traffic(ledger_path: Optional[str] = None
                 ) -> Dict[Tuple[str, str], float]:
    """Observed-traffic weight per (engine, shape): one for every entry
    of the node's own compile ledger, which records exact signatures.
    Best-effort: a missing or malformed file contributes no weight."""
    entries: List[dict] = []
    if ledger_path:
        try:
            with open(ledger_path) as f:
                entries = list(json.load(f).get("entries") or [])
        except (OSError, ValueError):
            pass
    w: Dict[Tuple[str, str], float] = {}
    for e in entries:
        eng, shape = e.get("engine"), e.get("shape")
        if isinstance(eng, str) and isinstance(shape, str):
            w[(eng, shape)] = w.get((eng, shape), 0.0) + 1.0
    return w


# -- enumeration -------------------------------------------------------------


@dataclass
class WarmEntry:
    engine: str
    shape: str
    B: int
    scheme: str
    dims: Dict[str, str] = field(default_factory=dict)
    priority: float = 0.0

    def to_json(self) -> dict:
        return {
            "engine": self.engine,
            "shape": self.shape,
            "B": self.B,
            "scheme": self.scheme,
            "dims": dict(self.dims),
            "priority": round(self.priority, 3),
        }


def default_surface_path() -> str:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, SURFACE_BASENAME)


def load_default_surface() -> Dict[str, object]:
    path = default_surface_path()
    doc = load_surface(path)
    if doc is None:
        raise FileNotFoundError(
            f"committed compile surface unreadable: {path} "
            f"(regenerate with scripts/mpcshape_surface.py)"
        )
    return doc


def _dim_axis(engine: str, name: str, row: Dict[str, object],
              knobs: WarmKnobs, buckets: Sequence[int],
              gaps: List[str]) -> List[str]:
    cls = row.get("class")
    if cls in ("bucketed", "unbounded"):
        # the batch axis: finite because the scheduler pow-2-snaps it
        return [str(b) for b in buckets]
    if cls == "constant":
        v = row.get("value")
        return [str(v)] if v is not None else [""]
    if cls == "knob":
        vals = knobs.values_for(name)
        if not vals:
            gaps.append(
                f"{engine}: knob dim {name!r} has no warm values "
                f"configured (WarmKnobs gap — the pre-warmer would "
                f"silently never compile this signature)"
            )
        return list(vals)
    gaps.append(f"{engine}: dim {name!r} has unknown class {cls!r}")
    return []


def build_manifest(surface: Dict[str, object],
                   knobs: WarmKnobs,
                   buckets: Sequence[int] = BUCKETS,
                   schemes: Optional[Sequence[str]] = None,
                   max_b: Optional[int] = None,
                   traffic: Optional[Dict[Tuple[str, str], float]] = None,
                   ) -> Dict[str, object]:
    """Instantiate the surface into the concrete warm work-list.

    ``schemes`` filters to scheme families (None = all serving);
    ``max_b`` caps the bucket axis (budget control — the cut is recorded
    in counts, never silent); ``traffic`` orders hot shapes first.
    Returns a JSON-able manifest dict with ``entries`` sorted by
    descending priority then ascending B (cheap compiles early maximize
    coverage inside a deadline).
    """
    if max_b is not None:
        buckets = [b for b in buckets if b <= max_b]
    traffic = traffic or {}
    gaps: List[str] = []
    entries: List[WarmEntry] = []
    n_serving = 0
    engines = surface.get("engines", {})
    for engine in sorted(engines):
        for rec in engines[engine]:
            if not rec.get("serving"):
                continue
            n_serving += 1
            scheme = ENGINE_SCHEME.get(engine, engine.split(".", 1)[0])
            if schemes is not None and scheme not in schemes:
                continue
            template = str(rec.get("template", ""))
            names = _DIM_RE.findall(template)
            dims = rec.get("dims", {})
            axes = [
                _dim_axis(engine, nm, dims.get(nm, {}), knobs, buckets, gaps)
                for nm in names
            ]
            for combo in itertools.product(*axes):
                shape = template
                for nm, val in zip(names, combo):
                    shape = shape.replace("{" + nm + "}", val, 1)
                d = dict(zip(names, combo))
                b = int(d.get("B", "1"))
                prio = traffic.get((engine, shape), 0.0)
                entries.append(WarmEntry(
                    engine=engine, shape=shape, B=b, scheme=scheme,
                    dims=d, priority=prio,
                ))
    entries.sort(key=lambda e: (-e.priority, e.B, e.engine, e.shape))
    return {
        "comment": (
            "Warm work-list derived from COMPILE_SURFACE.json (serving "
            "templates x WarmKnobs x engine/buckets.BUCKETS), hot shapes "
            "first. Valid only under the environment key; a key mismatch "
            "invalidates every cached executable."
        ),
        "key": manifest_key(),
        "knobs": knobs.to_json(),
        "buckets": list(buckets),
        "schemes": list(schemes) if schemes is not None else list(ALL_SCHEMES),
        "gaps": gaps,
        "entries": [e.to_json() for e in entries],
        "counts": {
            "entries": len(entries),
            "serving_templates": n_serving,
            "buckets": len(buckets),
        },
    }


def manifest_entries(manifest: Dict[str, object]) -> List[WarmEntry]:
    out = []
    for e in manifest.get("entries", []):  # type: ignore[union-attr]
        out.append(WarmEntry(
            engine=str(e["engine"]), shape=str(e["shape"]),
            B=int(e["B"]), scheme=str(e.get("scheme", "")),
            dims=dict(e.get("dims", {})),
            priority=float(e.get("priority", 0.0)),
        ))
    return out


# -- the enumeration gate (make warmcheck / check_all / tier-1) --------------


def coverage_check(surface: Dict[str, object],
                   knobs: Optional[WarmKnobs] = None,
                   buckets: Sequence[int] = BUCKETS) -> List[str]:
    """Verify manifest enumeration == serving templates x knob values x
    buckets, with no silent gaps. Returns problem strings (empty =
    clean). This is the ``make warmcheck`` gate, folded into
    scripts/check_all.py off the shared parse and drift-gated in tier-1:
    a new serving engine or knob dim that the warm layer cannot
    enumerate fails the build instead of silently never pre-warming."""
    knobs = knobs or default_knobs()
    manifest = build_manifest(surface, knobs, buckets=buckets)
    problems: List[str] = list(manifest["gaps"])  # type: ignore[arg-type]
    per_engine: Dict[str, int] = {}
    for e in manifest_entries(manifest):
        per_engine[e.engine] = per_engine.get(e.engine, 0) + 1
        if not shape_predicted(surface, e.engine, e.shape):
            problems.append(
                f"{e.engine}: manifest shape {e.shape!r} is not predicted "
                f"by the surface it was derived from (template/matcher "
                f"disagreement)"
            )
    engines = surface.get("engines", {})
    for engine in sorted(engines):  # type: ignore[union-attr]
        serving_recs = [r for r in engines[engine] if r.get("serving")]
        if serving_recs and engine not in ENGINE_SCHEME:
            problems.append(
                f"{engine}: no scheme mapping in "
                f"warm.manifest.ENGINE_SCHEME — warm_schemes cannot "
                f"select it"
            )
        expect = 0
        for rec in serving_recs:
            template = str(rec.get("template", ""))
            names = _DIM_RE.findall(template)
            dims = rec.get("dims", {})
            n = 1
            for nm in names:
                cls = dims.get(nm, {}).get("class")
                if cls in ("bucketed", "unbounded"):
                    n *= len(buckets)
                elif cls == "knob":
                    n *= len(knobs.values_for(nm))
                elif cls != "constant":
                    n = 0
            expect += n
        got = per_engine.get(engine, 0)
        if serving_recs and got != expect:
            problems.append(
                f"{engine}: enumerated {got} signatures, expected "
                f"{expect} (|buckets| x knob values per serving "
                f"template) — the warm work-list has a gap"
            )
    return problems
