"""What the engines and the daemon read about their own compiles and host.

- ``compile_watch``: the compile ledger. Engines report every
  first-call-per-shape warmup (the XLA compile) as an entry
  {engine, shape, platform, compile_s, persistent-cache hit/miss},
  persisted as ``COMPILE_LEDGER.json`` beside the XLA cache, emitted as
  ``compile:*`` spans, and surfaced through daemon health with a
  warming/ready state; the benchmark's zero-compiles-in-the-window check
  and the warm-start work-list read it.
- ``envfp``: the environment fingerprint (git sha, jax version, device
  kind/count, host CPU features, MPCIUM_* knobs) that ``bench.py`` and
  soak records carry and the warm manifest is keyed by.

Nothing in this package imports jax at module scope.
"""
