"""Runtime-side mpclint/mpcflow annotations (zero-cost at runtime).

``@locked_by(lock, *fields)`` declares which instance attributes a class
guards under which lock. mpclint's lock-discipline rule (MPL301) reads
the decorator *statically* and flags any write to a declared field that
is not inside ``with self.<lock>:`` (``__init__`` is exempt — objects
under construction are unpublished). At runtime the decorator only
records the declaration on the class, so annotated and unannotated
builds behave identically.

A method whose whole body runs under the lock (a helper only called from
locked contexts) is marked on its ``def`` line::

    def _checkpoint(self, out):  # mpclint: holds=_lock
        ...

See STATIC_ANALYSIS.md for the full registry.
"""
from __future__ import annotations

from typing import Callable, Dict, Generic, Tuple, TypeVar

T = TypeVar("T", bound=type)
_V = TypeVar("_V")


class Secret(Generic[_V]):
    """Type-annotation marker: the annotated value IS secret material,
    whatever its spelling. mpcflow (analysis/flow/taint.py) reads it
    statically — a parameter or return annotated ``Secret[...]`` seeds
    the MPF7xx taint lattice at every call boundary::

        def load_share(self, ...) -> "Secret[KeygenShare]": ...
        def seal(self, plaintext: "Secret[bytes]") -> bytes: ...

    At runtime it is inert: ``Secret[bytes]`` is just ``bytes`` to every
    type checker via the alias below, and nothing is instantiated. Use
    string-form annotations (as above) so importing modules stay free of
    typing machinery at import time.
    """

    def __class_getitem__(cls, item):
        return item

# thread-name prefixes of the process-lifetime singletons: the OT
# pipeline's host worker pool (mta_ot._host_pool) and the cohort
# pipeline's host worker (engine/pipeline._host_pool), created lazily
# once per process and alive until interpreter exit by design. The one
# list: MPL502 accepts threads named under them as "registered", and the
# tests' leak checks (tests/conftest.py no_leaked_nondaemon_threads,
# tests/test_load_soak.py) exempt them
REGISTERED_THREAD_PREFIXES: Tuple[str, ...] = ("ot-host", "pipe-host")


def locked_by(lock: str, *fields: str) -> Callable[[T], T]:
    """Class decorator: ``fields`` may only be written while holding
    ``self.<lock>``. Stackable for classes with several locks."""

    def wrap(cls: T) -> T:
        reg: Dict[str, Tuple[str, ...]] = dict(
            getattr(cls, "__mpclint_locked_by__", {})
        )
        reg[lock] = tuple(dict.fromkeys(reg.get(lock, ()) + fields))
        cls.__mpclint_locked_by__ = reg  # type: ignore[attr-defined]
        return cls

    return wrap
