"""Deterministic fault injection for the artifact store stack.

Robustness claims are only as good as the failures they were tested
against, so this module makes failure a first-class, *seeded* input: a
:class:`FaultPlan` is a reproducible schedule of faults and a
:class:`FaultyStore` wraps any :class:`~repro.core.store.Store` and injects
them at the protocol boundary.  The same ``(plan, workload)`` pair always
injects the same faults at the same call sites, so chaos tests can diff a
faulted run against a fault-free run byte for byte.

Fault kinds
-----------

``io_error``
    Raise :class:`~repro.core.store.TransientStoreError` — a flaky mount or
    mirror blip.  The retry layer should absorb it.
``timeout``
    Raise :class:`~repro.core.store.StoreTimeoutError` — a read deadline
    expiring.  Also transient.
``hard_error``
    Raise :class:`~repro.core.store.StoreError` — a permanent failure the
    retry layer must *not* absorb.
``torn_write``
    On ``write_chunk``: write only a truncated prefix of the payload under
    the full content address, then report success — models a torn write on
    a filesystem without atomic rename.  Read-side digest verification is
    the intended defense.  On ``write_manifest``: drop the write entirely
    (a lost write), which models dying before the rename.
``bit_flip``
    On ``read_chunk``: flip one byte of the returned data (in-flight
    corruption).  On ``write_chunk``: flip one byte *before* handing it to
    the inner store (at-rest corruption under a correct address).
``stale_manifest``
    On ``read_manifest``: serve the payload this key held *before* its most
    recent write through this wrapper — a lagging replica.
``crash``
    Raise :class:`SimulatedCrash` — mid-operation process death.  It
    derives from ``BaseException`` so no ``except Exception`` handler in
    the code under test can accidentally swallow it; only the test harness
    catches it.

Every injected fault is appended to ``plan.log`` as ``(op, key, kind)``.
"""

from __future__ import annotations

import dataclasses
import random

from repro.core.store import (StoreError, StoreTimeoutError,
                              TransientStoreError, _fresh_counters)

FAULT_KINDS = ("io_error", "timeout", "hard_error", "torn_write",
               "bit_flip", "stale_manifest", "crash")

_WRITE_OPS = ("write_manifest", "write_chunk")


class SimulatedCrash(BaseException):
    """Process death at a crash point.  BaseException on purpose: the code
    under test must not be able to catch it, just like a real SIGKILL."""


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """One rule in a fault schedule.

    ``op``           store method to target (``"read_chunk"``, ...) or ``"*"``.
    ``kind``         one of :data:`FAULT_KINDS`.
    ``probability``  chance of firing per matching call (seeded RNG).
    ``times``        stop firing after this many injections (None = forever).
    ``after``        skip this many matching calls first (crash points:
                     ``after=N`` kills the N+1-th write).
    ``match``        only fire when this substring appears in the key/digest.
    """

    op: str
    kind: str
    probability: float = 1.0
    times: int | None = None
    after: int = 0
    match: str | None = None

    def __post_init__(self):
        if self.kind not in FAULT_KINDS:
            raise ValueError(f"unknown fault kind {self.kind!r}; "
                             f"expected one of {FAULT_KINDS}")


class FaultPlan:
    """A seeded, reproducible schedule of :class:`FaultSpec` rules.

    Call counting and the probability RNG are both deterministic: replaying
    the same workload against the same plan injects the same faults.
    """

    def __init__(self, specs: "list[FaultSpec] | tuple[FaultSpec, ...]",
                 seed: int = 0):
        self.specs = list(specs)
        self.seed = seed
        self._rng = random.Random(seed)
        self._calls = [0] * len(self.specs)   # matching calls seen per spec
        self._fired = [0] * len(self.specs)   # injections done per spec
        self.log: list[tuple[str, str, str]] = []

    def draw(self, op: str, key: str = "") -> FaultSpec | None:
        """Return the first spec that fires for this call, if any."""
        for i, spec in enumerate(self.specs):
            if spec.op not in (op, "*"):
                continue
            if spec.match is not None and spec.match not in key:
                continue
            self._calls[i] += 1
            if self._calls[i] <= spec.after:
                continue
            if spec.times is not None and self._fired[i] >= spec.times:
                continue
            if spec.probability < 1.0 and self._rng.random() >= spec.probability:
                continue
            self._fired[i] += 1
            self.log.append((op, key, spec.kind))
            return spec
        return None

    @property
    def injected(self) -> int:
        return len(self.log)

    def flip_position(self, n: int) -> int:
        """Deterministic byte offset for a bit_flip over an n-byte payload."""
        return self._rng.randrange(n) if n else 0


def _flip_byte(data: bytes, pos: int) -> bytes:
    buf = bytearray(data)
    buf[pos] ^= 0xFF
    return bytes(buf)


class FaultyStore:
    """A :class:`~repro.core.store.Store` that injects a :class:`FaultPlan`.

    Wrap any store — a LocalStore, a file:// RemoteStore, or another
    FaultyStore — and pass it wherever a store is accepted (including as a
    LocalStore ``upstream``, which is how a *flaky mirror* is modeled).
    Reads and writes that don't draw a fault delegate unchanged, so a plan
    with no matching specs is a transparent proxy.
    """

    def __init__(self, inner, plan: FaultPlan):
        self.inner = inner
        self.plan = plan
        # stale_manifest support: remember the payload each key held before
        # its latest write through this wrapper.
        self._track_stale = any(s.kind == "stale_manifest" for s in plan.specs)
        self._prior_manifests: dict[str, dict] = {}

    # The Store protocol's data members are real properties: isinstance()
    # against a runtime protocol looks them up on the class, past
    # __getattr__.
    @property
    def readonly(self) -> bool:
        return self.inner.readonly

    @property
    def counters(self) -> dict[str, int]:
        return self.inner.counters

    # Delegate everything else not explicitly intercepted (root, uri,
    # bulk(), retry, ...) so the wrapper is drop-in.
    def __getattr__(self, name):
        return getattr(self.inner, name)

    def _maybe(self, op: str, key: str = "") -> FaultSpec | None:
        spec = self.plan.draw(op, key)
        if spec is None:
            return None
        if spec.kind == "io_error":
            raise TransientStoreError(f"injected io_error on {op}({key[:12]}…)")
        if spec.kind == "timeout":
            raise StoreTimeoutError(f"injected timeout on {op}({key[:12]}…)")
        if spec.kind == "hard_error":
            raise StoreError(f"injected hard_error on {op}({key[:12]}…)")
        if spec.kind == "crash":
            raise SimulatedCrash(f"injected crash at {op}({key[:12]}…)")
        return spec                           # data faults handled by caller

    # -- manifests ----------------------------------------------------------
    def has_manifest(self, key: str) -> bool:
        self._maybe("has_manifest", key)
        return self.inner.has_manifest(key)

    def read_manifest(self, key: str) -> dict:
        spec = self._maybe("read_manifest", key)
        if spec is not None and spec.kind == "stale_manifest":
            if key in self._prior_manifests:
                return self._prior_manifests[key]
        return self.inner.read_manifest(key)

    def write_manifest(self, key: str, payload: dict) -> None:
        spec = self._maybe("write_manifest", key)
        if self._track_stale:
            try:
                self._prior_manifests[key] = self.inner.read_manifest(key)
            except Exception:
                pass
        if spec is not None and spec.kind == "torn_write":
            return                            # lost write: died before rename
        self.inner.write_manifest(key, payload)

    def delete_manifest(self, key: str) -> None:
        self._maybe("delete_manifest", key)
        self.inner.delete_manifest(key)

    def manifest_keys(self) -> list[str]:
        self._maybe("manifest_keys")
        return self.inner.manifest_keys()

    def manifest_bytes(self, key: str) -> int:
        self._maybe("manifest_bytes", key)
        return self.inner.manifest_bytes(key)

    def manifest_mtime_ns(self, key: str) -> int:
        self._maybe("manifest_mtime_ns", key)
        return self.inner.manifest_mtime_ns(key)

    # -- chunks -------------------------------------------------------------
    def has_chunk(self, digest: str) -> bool:
        self._maybe("has_chunk", digest)
        return self.inner.has_chunk(digest)

    def read_chunk(self, digest: str) -> bytes:
        spec = self._maybe("read_chunk", digest)
        data = self.inner.read_chunk(digest)
        if spec is not None and spec.kind == "bit_flip" and data:
            data = _flip_byte(data, self.plan.flip_position(len(data)))
        return data

    def write_chunk(self, digest: str, data: bytes) -> None:
        spec = self._maybe("write_chunk", digest)
        if spec is not None and data:
            if spec.kind == "torn_write":
                data = data[:max(1, len(data) // 2)]
            elif spec.kind == "bit_flip":
                data = _flip_byte(data, self.plan.flip_position(len(data)))
        self.inner.write_chunk(digest, data)

    def delete_chunk(self, digest: str) -> None:
        self._maybe("delete_chunk", digest)
        self.inner.delete_chunk(digest)

    def chunk_keys(self) -> list[str]:
        self._maybe("chunk_keys")
        return self.inner.chunk_keys()

    def chunk_bytes(self, digest: str) -> int:
        self._maybe("chunk_bytes", digest)
        return self.inner.chunk_bytes(digest)
