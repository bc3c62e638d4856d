"""The pre-warmer: walk the warm manifest before advertising ready.

Each manifest entry names one (engine, shape) compile bucket. The runner
for an entry drives the *real* engine entry point at that exact shape —
the same ``compile_watch.begin`` site live traffic hits — so warming
produces genuine ledger entries and ``compile:*`` spans, and the XLA
persistent cache (``configure_cache``) fills with exactly the
executables the serving set needs. A later fresh-process boot then
classifies its first real request ``cache: hit``: the compile wall is
paid once per host+toolchain, not once per restart
(tests/test_warm_boot.py proves the zero-miss boot on CPU).

The walk is budget-aware and failure-isolated: a deadline miss marks the
remaining entries ``skipped`` (the daemon goes ready anyway — cold, but
alive), and a runner exception marks that entry ``failed`` without
taking boot down. The report lands as ``WARM_MANIFEST.json`` in the
caller's report directory, one verdict per signature.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, List, Optional, Sequence

from ..perf import compile_watch
from ..utils import log
from . import aot
from . import manifest as wm

def _ids(n: int) -> List[str]:
    return [f"warm{i}" for i in range(n)]


def _digests(B: int):
    import numpy as np

    return np.stack(
        [np.frombuffer(bytes([i % 256]) * 32, dtype=np.uint8)
         for i in range(B)]
    )


def _messages(B: int) -> List[bytes]:
    return [bytes([i % 256]) * 32 for i in range(B)]


def _test_preparams(ids: Sequence[str]) -> Dict[str, object]:
    """The committed FIXED Paillier fixtures mapped onto warm party ids —
    fixed keys keep the persistent cache valid across runs (fresh moduli
    would embed new constants into every kernel)."""
    from ..cluster import load_test_preparams

    tp = load_test_preparams(bits=1024)
    pool = [tp[k] for k in sorted(tp)]
    return {pid: pool[i % len(pool)] for i, pid in enumerate(ids)}


# -- per-engine runners ------------------------------------------------------
#
# Each runner compiles the bucket for ONE manifest entry by running the
# engine at that shape with throwaway dealer-keygen material. Dims come
# from the entry (strings, straight from the surface template).


def _run_eddsa_sign(e: wm.WarmEntry) -> None:
    import secrets

    from ..engine import eddsa_batch as eb

    q = int(e.dims["q"])
    ids = _ids(q + 1)
    shares = eb.dealer_keygen_batch(e.B, ids, q - 1, rng=secrets)
    eb.BatchedCoSigners(ids[:q], shares[:q], rng=secrets).sign(
        _messages(e.B)
    )


def _run_dkg_run(e: wm.WarmEntry) -> None:
    import secrets

    from ..engine import dkg_batch as db

    q = int(e.dims["q"])
    db.BatchedDKG(_ids(q), q - 1, e.dims["key_type"], rng=secrets).run(e.B)


def _run_reshare_run(e: wm.WarmEntry) -> None:
    import secrets

    t_new = int(e.dims["t_new"])
    committee = _ids(max(t_new + 1, 2))
    key_type = e.dims["key_type"]
    if key_type == "secp256k1":
        from ..engine import gg18_batch as gb

        old = gb.dealer_keygen_secp_batch(e.B, committee, 1, rng=secrets)
    else:
        from ..engine import eddsa_batch as eb

        old = eb.dealer_keygen_batch(e.B, committee, 1, rng=secrets)
    from ..engine import dkg_batch as db

    db.BatchedReshare(committee[:2], old[:2], committee, t_new,
                      rng=secrets).run()


def _run_gg18_sign(e: wm.WarmEntry) -> None:
    import secrets

    from ..engine import gg18_batch as gb

    q = int(e.dims["q"])
    mta = e.dims["mta_impl"]
    ids = _ids(q + 1)
    shares = gb.dealer_keygen_secp_batch(e.B, ids, q - 1, rng=secrets)
    pre = _test_preparams(ids[:q]) if mta == "paillier" else None
    signer = gb.GG18BatchCoSigners(
        ids[:q], shares[:q], pre, rng=secrets, mta_impl=mta
    )
    signer.sign(_digests(e.B))


def _run_party_dkg(e: wm.WarmEntry) -> None:
    import secrets

    from ..protocol.batch_dkg import BatchedDKGParty
    from ..protocol.runner import run_protocol

    q = int(e.dims["q"])
    key_type = e.dims["key_type"]
    ids = _ids(q)
    pre = _test_preparams(ids) if key_type == "secp256k1" else {}
    parties = {
        pid: BatchedDKGParty(
            "warm-dkg", pid, ids, q - 1, key_type, e.B,
            preparams=pre.get(pid), min_paillier_bits=512, rng=secrets,
        )
        for pid in ids
    }
    run_protocol(parties)


def _run_party_ecdsa(e: wm.WarmEntry) -> None:
    import secrets

    from ..engine import gg18_batch as gb
    from ..protocol.ecdsa.batch_signing import BatchedECDSASigningParty
    from ..protocol.runner import run_protocol

    q = int(e.dims["q"])
    ids = _ids(q)
    pre = _test_preparams(ids)
    shares = gb.dealer_keygen_secp_batch(
        e.B, ids, q - 1, rng=secrets, preparams=pre
    )
    digests = [bytes([i % 256]) * 32 for i in range(e.B)]
    parties = {
        pid: BatchedECDSASigningParty(
            "warm-ecdsa", pid, ids, shares[i], digests, rng=secrets
        )
        for i, pid in enumerate(ids)
    }
    run_protocol(parties)


def _run_party_reshare(e: wm.WarmEntry) -> None:
    import secrets

    from ..protocol.batch_dkg import BatchedReshareParty
    from ..protocol.runner import run_protocol

    # q in the shape is |old ∪ new|: same committee re-deals to itself
    q = int(e.dims["q"])
    t_new = int(e.dims["t_new"])
    key_type = e.dims["key_type"]
    ids = _ids(q)
    if key_type == "secp256k1":
        from ..engine import gg18_batch as gb

        pre = _test_preparams(ids)
        old = gb.dealer_keygen_secp_batch(e.B, ids, t_new, rng=secrets)
    else:
        from ..engine import eddsa_batch as eb

        pre = {pid: None for pid in ids}
        old = eb.dealer_keygen_batch(e.B, ids, t_new, rng=secrets)
    parties = {
        pid: BatchedReshareParty(
            "warm-reshare", pid, key_type, ids, ids, t_new, e.B,
            old_shares=old[i], preparams=pre.get(pid),
            min_paillier_bits=512, rng=secrets,
        )
        for i, pid in enumerate(ids)
    }
    run_protocol(parties)


RUNNERS: Dict[str, Callable[[wm.WarmEntry], None]] = {
    "eddsa.sign": _run_eddsa_sign,
    "dkg.run": _run_dkg_run,
    "reshare.run": _run_reshare_run,
    "gg18.sign": _run_gg18_sign,
    "party.dkg": _run_party_dkg,
    "party.ecdsa": _run_party_ecdsa,
    "party.reshare": _run_party_reshare,
}


# -- cache configuration -----------------------------------------------------


def configure_cache(
    cache_dir: Optional[str], min_compile_s: float = 0.0
) -> Optional[str]:
    """Point the XLA persistent cache at ``cache_dir`` (None: the
    checkout's default) — unless ``JAX_COMPILATION_CACHE_DIR`` is set,
    which wins (utils/jax_cache) — and drop the min-compile-time floor so
    every warmed executable persists (the default floor silently skips
    sub-second compiles — a warm pass wants all of them on disk).
    Returns the directory in effect."""
    from ..utils import jax_cache

    return jax_cache.configure(cache_dir, min_compile_s)


# -- the walk ----------------------------------------------------------------


def prewarm(
    manifest: dict,
    budget_s: float = 300.0,
    *,
    report_dir: Optional[str] = None,
    aot_store: Optional[aot.ArtifactStore] = None,
    now: Callable[[], float] = time.monotonic,
) -> dict:
    """Walk the manifest (hot shapes first) until covered or out of
    budget. Returns — and writes, when ``report_dir`` is given — the
    ``WARM_MANIFEST.json`` report: one verdict per signature plus
    totals. Never raises: a failed entry is a report line, not a boot
    failure."""
    deadline = now() + budget_s
    results: List[dict] = []
    totals = {
        "entries": 0, "warmed": 0, "already": 0, "skipped": 0,
        "failed": 0, "hits": 0, "misses": 0, "unpredicted": 0,
    }
    for e in wm.manifest_entries(manifest):
        totals["entries"] += 1
        row = {"engine": e.engine, "shape": e.shape, "B": e.B,
               "scheme": e.scheme, "priority": e.priority}
        if now() >= deadline:
            row["status"] = "skipped"
            row["reason"] = "budget exhausted"
            totals["skipped"] += 1
            results.append(row)
            continue
        if compile_watch.seen(e.engine, e.shape):
            row["status"] = "already"
            totals["already"] += 1
            results.append(row)
            continue
        runner = RUNNERS.get(e.engine)
        if runner is None:
            row["status"] = "failed"
            row["reason"] = f"no warm runner for engine {e.engine!r}"
            totals["failed"] += 1
            results.append(row)
            continue
        t0 = now()
        try:
            runner(e)
        except Exception as exc:  # noqa: BLE001 — warming must not kill boot
            row["status"] = "failed"
            row["reason"] = repr(exc)
            totals["failed"] += 1
            log.warn("warm: entry failed", engine=e.engine, shape=e.shape,
                     error=repr(exc))
            results.append(row)
            continue
        row["status"] = "warmed"
        row["warm_s"] = round(now() - t0, 3)
        totals["warmed"] += 1
        ledger = next(
            (le for le in reversed(compile_watch.entries())
             if le["engine"] == e.engine and le["shape"] == e.shape),
            None,
        )
        if ledger is not None:
            row["cache"] = ledger["cache"]
            row["compile_s"] = ledger["compile_s"]
            if ledger["cache"] == "hit":
                totals["hits"] += 1
            elif ledger["cache"] == "miss":
                totals["misses"] += 1
            if ledger.get("predicted") is False:
                # a warmed shape the static surface missed — drift that
                # escaped the mpcshape gate; make it impossible to miss
                row["predicted"] = False
                totals["unpredicted"] += 1
                log.warn(
                    "warm: UNPREDICTED compile — shape missing from "
                    "COMPILE_SURFACE.json, regenerate via make shapecheck",
                    engine=e.engine, shape=e.shape,
                )
        if aot_store is not None:
            try:
                row["aot"] = aot.warm_entry_artifacts(aot_store, e)
            except Exception as exc:  # noqa: BLE001
                row["aot_error"] = repr(exc)
        results.append(row)
    report = {
        "comment": "pre-warm report: one verdict per warm-manifest "
                   "signature (mpcium_tpu.warm.prewarm)",
        "key": manifest.get("key", wm.manifest_key()),
        "budget_s": budget_s,
        "totals": totals,
        "results": results,
    }
    if report_dir:
        try:
            os.makedirs(report_dir, exist_ok=True)
            path = os.path.join(report_dir, wm.REPORT_BASENAME)
            with open(path, "w") as f:
                json.dump(report, f, indent=1, sort_keys=True)
                f.write("\n")
            report["path"] = path
        except OSError as exc:
            log.warn("warm: could not write report", error=repr(exc))
    return report


# -- daemon / drill entry points ---------------------------------------------


def prewarm_for_daemon(cfg, node_name: str) -> Optional[dict]:
    """The boot-time warm pass (node/daemon.py, between ``mark_warming``
    and ``mark_ready``). Never raises — a broken warm config degrades to
    a cold-but-serving node, loudly."""
    try:
        # per-node report + AOT artifacts sit beside the compile ledger;
        # the XLA cache is the operator's warm_cache_dir, else
        # <checkout>/.jax_cache, and JAX_COMPILATION_CACHE_DIR wins
        db_dir = os.path.join(cfg.db_dir, node_name)
        cache_dir = configure_cache(cfg.warm_cache_dir or None)
        surface = wm.load_default_surface()
        knobs = wm.knobs_from_config(cfg)
        schemes = tuple(
            s.strip() for s in cfg.warm_schemes.split(",") if s.strip()
        ) or None
        traffic = wm.load_traffic(
            os.path.join(db_dir, compile_watch.LEDGER_BASENAME)
        )
        manifest = wm.build_manifest(
            surface, knobs, schemes=schemes, max_b=cfg.warm_max_b,
            traffic=traffic,
        )
        log.info(
            "warm: pre-warming serving set", node=node_name,
            entries=len(manifest["entries"]), budget_s=cfg.warm_budget_s,
            cache=cache_dir,
        )
        report = prewarm(
            manifest, cfg.warm_budget_s, report_dir=db_dir,
            aot_store=aot.ArtifactStore(os.path.join(db_dir, "aot")),
        )
        t = report["totals"]
        log.info(
            "warm: pre-warm complete", node=node_name, warmed=t["warmed"],
            already=t["already"], skipped=t["skipped"], failed=t["failed"],
            cache_hits=t["hits"], cache_misses=t["misses"],
        )
        return report
    except Exception as exc:  # noqa: BLE001 — boot must survive a bad warm pass
        log.warn("warm: pre-warm pass failed — serving cold",
                 node=node_name, error=repr(exc))
        return None


def warm_for_drill(budget_s: float = 60.0) -> Dict[str, object]:
    """A tiny eddsa-only warm pass for the kill-resume chaos drill: warm
    the drill's own signing bucket so resume latency reflects a warm
    cache, and report ``{warmed, hits, budget_s}`` for the drill report.
    Never raises."""
    try:
        surface = wm.load_default_surface()
        knobs = wm.WarmKnobs(q=(2,), key_type=("ed25519",),
                             mta_impl=("paillier",), t_new=(1,))
        manifest = wm.build_manifest(
            surface, knobs, buckets=(2,), schemes=("eddsa",)
        )
        report = prewarm(manifest, budget_s)
        t = report["totals"]
        return {
            "warmed": t["warmed"] + t["already"],
            "hits": t["hits"],
            "budget_s": budget_s,
        }
    except Exception as exc:  # noqa: BLE001 — a drill must not die warming
        log.warn("warm: drill warm pass failed", error=repr(exc))
        return {"warmed": 0, "hits": 0, "budget_s": budget_s}
