"""Runtime sanitizer mode (``FLAGS_sanitize``) for the serving stack.

The static passes (`paddle_tpu.analysis.passes`) prove what they can
about the SOURCE; this module catches the remainder at RUNTIME, turned
on by one flag and near-free when off:

* **use-after-donate** — `inference.serving._JitTracker` tombstones
  every donated argument after the call (`tombstone`), and any later
  host access raises `UseAfterDonateError` naming the donation site.
  On CPU, XLA silently ignores donation, so a read-after-donate bug
  passes every CPU test and corrupts data only on TPU — exactly the
  class a sanitizer must catch before hardware does;
* **lock-order cycles** — the designated telemetry locks are
  `TrackedLock` wrappers; while the sanitizer is active every
  acquisition records (held -> acquiring) edges in a process-wide
  order graph, and the edge that closes a cycle raises
  `LockOrderError` at the acquisition that would have deadlocked;
* **warm retraces raise** — `_JitTracker.check_retrace` raises
  `WarmRetraceError` instead of incrementing
  ``retraces_after_warmup``: the zero-warm-retrace contract becomes an
  assertion, not a counter someone has to read;
* **host-sync sentinel** — the engine's blocking device reads
  (`DecodeEngine._host_fetch`) are counted per serve, so a step that
  silently grew a second sync (an accidental ``int(traced)`` on the
  hot path) shows up in `report()` as ``host_syncs > steps``.

Everything routes through `active()`: ``None`` when the flag is off
(one dict lookup on the hot path), the process `Sanitizer` otherwise.
This module imports only the standard library at import time so the
lock wrappers can be constructed from `core.dispatch` and
`observability.metrics` without ordering constraints; the flag is read
lazily the first time `active()` runs after `core.flags` is populated.
"""
from __future__ import annotations

import threading
from typing import Optional

__all__ = [
    "SanitizerError", "UseAfterDonateError", "LockOrderError",
    "WarmRetraceError", "Sanitizer", "TrackedLock", "active", "get",
    "reset",
]


class SanitizerError(RuntimeError):
    """Base class: every sanitizer failure is loud and typed."""


class UseAfterDonateError(SanitizerError):
    """A donated (device-invalidated) buffer reached a host access or
    was fed back into an executable."""


class LockOrderError(SanitizerError):
    """Two designated locks were acquired in both orders — a latent
    deadlock."""


class WarmRetraceError(SanitizerError):
    """A warm executable recompiled mid-serve (the zero-warm-retrace
    contract, promoted from counter to assertion)."""


# flag wiring: installed lazily because this module must be importable
# before core.flags has defined FLAGS_sanitize (dispatch/metrics build
# their TrackedLocks at import time).  `active()` reads the flag
# REGISTRY directly (one dict lookup, no cached copy): set_flags
# mutates the registry before running its change callbacks, so there
# is no window where a callback (e.g. clear_dispatch_cache taking
# _CACHE_LOCK) observes a stale sanitize state.
_STATE = {"reg": None}


def _install() -> bool:
    if _STATE["reg"] is not None:
        return True
    try:
        from ..core import flags as _flags

        _flags.flag("sanitize")  # KeyError until flags.py has run
        _STATE["reg"] = _flags._REGISTRY
    except Exception:
        return False
    return True


class Sanitizer:
    """Process-wide sanitizer state: the lock-order graph, the donated-
    buffer tombstone registry, and the per-serve counters.  All methods
    are thread-safe (the engine steps on a worker thread under
    `ServingFrontend`)."""

    def __init__(self):
        self._mu = threading.Lock()  # guards graph + tombstones
        self._tls = threading.local()
        self.lock_edges = {}
        self.host_syncs = 0
        self.steps = 0
        self.warm_retraces = 0
        self._tombstones = {}

    def reset(self):
        """Drop all recorded global state (test isolation: edges and
        tombstones from one scenario must not fail the next).  Per-
        thread held-lock stacks are NOT touched — they self-maintain:
        release bookkeeping runs even while the sanitizer is disabled,
        so a flag flip mid-hold cannot leave a phantom entry."""
        with self._mu:
            self.lock_edges = {}
            self.host_syncs = 0
            self.steps = 0
            self.warm_retraces = 0
            self._tombstones = {}

    # -- counters (report() reads under _mu; writers must match) -------------
    def count_step(self):
        with self._mu:
            self.steps += 1

    def count_host_sync(self):
        with self._mu:
            self.host_syncs += 1

    def count_warm_retrace(self, n=1):
        with self._mu:
            self.warm_retraces += n

    def report(self) -> dict:
        with self._mu:
            return {
                "steps": self.steps,
                "host_syncs": self.host_syncs,
                "warm_retraces": self.warm_retraces,
                "lock_edges": sorted(self.lock_edges),
                "tombstoned_buffers": len(self._tombstones),
            }

    # -- lock-order tracking -------------------------------------------------
    def _held(self):
        h = getattr(self._tls, "held", None)
        if h is None:
            h = self._tls.held = []
        return h

    def note_acquire(self, name: str, reentrant: bool = True):
        """Record that this thread is about to acquire ``name``.  Adds
        (held -> name) edges for every currently-held lock; an edge
        that closes a cycle raises BEFORE the acquisition blocks.
        ``reentrant=False`` (a plain Lock): re-acquiring a lock this
        thread already holds is a guaranteed self-deadlock and raises
        immediately."""
        held = self._held()
        if name in held:
            if not reentrant:
                raise LockOrderError(
                    f"self-deadlock: thread already holds non-"
                    f"reentrant lock {name!r} and is acquiring it "
                    f"again — this blocks forever")
            held.append(name)  # RLock: no new ordering info
            return
        if held:
            with self._mu:
                for h in dict.fromkeys(held):
                    if (h, name) not in self.lock_edges:
                        cycle = self._path(name, h)
                        if cycle is not None:
                            # do NOT record the cycle-closing edge: the
                            # next occurrence of this inverted order
                            # must raise again, not sail past the check
                            # into the real deadlock
                            raise LockOrderError(
                                "lock-order cycle: acquiring "
                                f"{name!r} while holding {h!r}, but the "
                                "opposite order was already observed "
                                f"(path {' -> '.join([h, name] + cycle[1:])})"
                            )
                        self.lock_edges[(h, name)] = True
        held.append(name)

    def note_release(self, name: str):
        held = self._held()
        if held and held[-1] == name:
            held.pop()
        elif name in held:  # out-of-order release: still keep stack sane
            held.remove(name)

    def _path(self, start: str, target: str) -> Optional[list]:
        """Path start -> ... -> target in the edge graph, or None.
        Caller holds self._mu."""
        stack = [(start, [start])]
        seen = set()
        while stack:
            node, path = stack.pop()
            if node == target:
                return path
            if node in seen:
                continue
            seen.add(node)
            for (a, b) in self.lock_edges:
                if a == node:
                    stack.append((b, path + [b]))
        return None

    # -- use-after-donate ----------------------------------------------------
    # registry bound: a sanitized soak run tombstones ~2 buffers per
    # engine step; beyond the cap the OLDEST entries are dropped (their
    # pinned array shells become collectable, so the window of
    # site-attributed detection is bounded — jax's own deleted-buffer
    # error still fires on raw reads forever)
    MAX_TOMBSTONES = 4096

    def tombstone(self, arr, site: str):
        """Mark ``arr`` as donated at ``site``.  The array object is
        pinned while the entry lives (so its id cannot alias a newer
        allocation) and its device buffer is deleted when the backend
        supports it — a raw host read afterwards raises jax's own
        deleted-buffer error, while `check_live` raises with the
        donation site."""
        if arr is None:
            return
        with self._mu:
            self._tombstones[id(arr)] = (arr, site)
            while len(self._tombstones) > self.MAX_TOMBSTONES:
                self._tombstones.pop(next(iter(self._tombstones)))
        try:
            delete = getattr(arr, "delete", None)
            if delete is not None:
                delete()
        except Exception:
            pass  # already deleted / backend refuses: registry suffices

    def donation_site(self, arr) -> Optional[str]:
        with self._mu:
            hit = self._tombstones.get(id(arr))
        return None if hit is None else hit[1]

    def check_live(self, arr, context: str = ""):
        """Raise `UseAfterDonateError` if ``arr`` was donated earlier.
        Called by `_JitTracker` on every leaf of every executable
        argument, so feeding a stale pre-donation reference back into a
        step fails at the call."""
        site = self.donation_site(arr)
        if site is not None:
            raise UseAfterDonateError(
                f"use after donate{': ' + context if context else ''} — "
                f"this buffer was donated at {site} and its device "
                f"memory has been reused; rebind to the executable's "
                f"returned arrays instead of holding the input")


_SAN = Sanitizer()


def get() -> Sanitizer:
    """The process sanitizer (state readable even while disabled)."""
    return _SAN


def active() -> Optional[Sanitizer]:
    """The process `Sanitizer` when FLAGS_sanitize is on, else None —
    THE hot-path check (a dict lookup once the flag registry exists)."""
    reg = _STATE["reg"]
    if reg is None:
        if not _install():
            return None
        reg = _STATE["reg"]
    return _SAN if reg["sanitize"] else None


def reset():
    _SAN.reset()


class TrackedLock:
    """Drop-in wrapper over a ``threading.Lock``/``RLock``: delegates
    acquire/release, and while the sanitizer is active records the
    acquisition order into the process-wide graph (cycles raise
    `LockOrderError`).  When the sanitizer is off the cost is one dict
    lookup per acquisition."""

    __slots__ = ("_inner", "name", "_reentrant")

    def __init__(self, inner, name: str):
        self._inner = inner
        self.name = name
        # a plain Lock self-deadlocks on same-thread re-acquisition —
        # note_acquire must raise there instead of treating it as
        # RLock reentrancy
        self._reentrant = isinstance(inner, type(threading.RLock()))

    def acquire(self, *args, **kwargs):
        san = active()
        if san is not None:
            # record (and cycle-check) BEFORE the acquisition can block
            san.note_acquire(self.name, reentrant=self._reentrant)
        try:
            ok = self._inner.acquire(*args, **kwargs)
        except BaseException:
            # interrupted while blocking (KeyboardInterrupt, pytest
            # timeout): the lock was never taken — the held-stack entry
            # must not outlive the failed acquisition
            _SAN.note_release(self.name)
            raise
        if not ok:
            # failed non-blocking try: the lock is not held — undo the
            # held-stack entry (no-op if the sanitizer was off above)
            _SAN.note_release(self.name)
        return ok

    def release(self):
        # held-stack bookkeeping runs UNCONDITIONALLY: if the flag
        # flips off between a thread's acquire and release, the entry
        # must still pop or it would haunt every later sanitized run
        # on this thread (note_release on an absent name is a no-op)
        _SAN.note_release(self.name)
        return self._inner.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def __repr__(self):
        return f"TrackedLock({self.name!r}, {self._inner!r})"
