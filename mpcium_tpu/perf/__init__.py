"""mpcperf: the performance observatory (PERFORMANCE.md "perf observatory").

Three coupled parts, each importable on its own so nothing here rides the
hot path unless asked:

- ``compile_watch``: the compile-wall ledger. Engines report every
  first-call-per-shape warmup (the XLA compile) as a ledger entry
  {engine, shape, platform, compile_s, persistent-cache hit/miss},
  persisted as ``COMPILE_LEDGER.json`` beside the XLA cache, emitted as
  mpctrace ``compile:*`` spans, and surfaced through daemon health with
  a warming/ready state — the data surface the ROADMAP-item-4
  warm-start daemon builds on.
- ``ledger`` + ``report``: the bench trajectory. Every committed
  ``BENCH_*`` / ``SOAK_*`` / ``MULTICHIP_*`` artifact normalizes into
  ``PERF_history.jsonl`` grouped by platform/env fingerprint (CPU-
  degraded runs can never average into chip trends), rendered as
  ``PERFORMANCE_dashboard.md`` and a Perfetto counter track.
- ``statcheck`` + ``microbench``: the statistical regression gate.
  Fast CPU-safe micro-benches compared against committed baselines with
  a Mann-Whitney + bootstrap noise band (``scripts/perfcheck.py``,
  ``make perfcheck``, wired into ``make check`` and tier-1).

``envfp`` stamps bench/soak records with the environment fingerprint
(git sha, jax version, device kind/count, MPCIUM_* knobs) the ledger
groups by. Nothing in this package imports jax at module scope.
"""
