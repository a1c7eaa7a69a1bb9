"""Runtime lock-order witness: the dynamic half of the concurrency plane.

The static half (``lint/concurrency.py``) proves the declared lock-
acquisition graph acyclic by reading the AST; this module watches the REAL
acquisitions at runtime and records, per thread, every "acquired B while
holding A" edge.  Two uses:

- **Acyclicity at runtime**: ``WITNESS.assert_acyclic()`` fails a test the
  moment two code paths acquire the same pair of locks in opposite orders
  — the deterministic interleave harness (``tests/test_concurrency.py``)
  drives the cross-thread seams and asserts this at the end, so a lock
  inversion that only manifests under a thread schedule nobody ran still
  fails CI.
- **Static-graph completeness**: the witness's observed edge set must be a
  SUBSET of the edges the AST analysis derived (``cross_check``).  The
  static analyzer skips calls it cannot resolve; an observed edge it
  missed means the analyzer (or the registry's attribute bindings) lost
  track of a seam — the mismatch fails loudly instead of silently
  narrowing the lint's coverage.

Arming: ``witness_lock(name)`` returns a recording wrapper only when
``LIG_LOCK_WITNESS`` is set truthy AT CONSTRUCTION TIME (tests arm it in
``tests/conftest.py``); otherwise it returns a plain ``threading.Lock`` /
``RLock`` — zero overhead in production.  Armed, an acquisition costs one
thread-local list append plus, only for a never-seen (held, acquired)
pair, one dict insert.

Naming convention: ``"ClassName._lockattr"`` — the SAME identity the
static analyzer assigns (``concurrency_registry`` declares the classes and
lock attributes), so observed and static edges compare directly.
"""

from __future__ import annotations

import os
import threading

ENV = "LIG_LOCK_WITNESS"


def armed() -> bool:
    return os.environ.get(ENV, "") not in ("", "0", "false", "no")


class LockWitness:
    """Process-global acquisition-order recorder (one edge set, per-thread
    hold stacks)."""

    def __init__(self):
        self._mu = threading.Lock()     # guards the edge dict only; never
        #                                 held while acquiring a user lock
        self._edges: dict[tuple[str, str], int] = {}
        self._tls = threading.local()

    # -- recording (called by _WitnessLock with the user lock HELD) ---------
    def thread_stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def note_acquire(self, name: str) -> None:
        stack = self.thread_stack()
        if stack:
            self.note_edge(stack[-1], name)
        stack.append(name)

    def note_edge(self, held: str, name: str) -> None:
        edge = (held, name)
        if edge not in self._edges:   # racy fast-path miss is fine:
            with self._mu:            # the locked insert is idempotent
                self._edges[edge] = self._edges.get(edge, 0) + 1

    def note_release(self, name: str) -> None:
        stack = getattr(self._tls, "stack", None)
        if not stack:
            return
        if stack[-1] == name:         # with-statements release LIFO
            stack.pop()
            return
        # Tolerate out-of-order manual release: newest matching entry.
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] == name:
                del stack[i]
                return

    # -- inspection ---------------------------------------------------------
    def edges(self) -> frozenset:
        """Every observed (held, then-acquired) pair."""
        with self._mu:
            return frozenset(self._edges)

    def reset(self) -> None:
        with self._mu:
            self._edges.clear()

    def find_cycle(self) -> list[str] | None:
        """A lock cycle in the observed order graph, or None."""
        graph: dict[str, set[str]] = {}
        for a, b in self.edges():
            graph.setdefault(a, set()).add(b)
        return find_cycle(graph)

    def assert_acyclic(self) -> None:
        cycle = self.find_cycle()
        if cycle:
            raise AssertionError(
                "lock-order cycle observed at runtime: "
                + " -> ".join(cycle)
                + " (two code paths acquire these locks in opposite "
                  "orders — a thread schedule exists that deadlocks)")


def find_cycle(graph: dict[str, set]) -> list[str] | None:
    """First cycle in a directed graph as [a, b, ..., a], or None.
    Shared by the witness and the static lock-order rule."""
    WHITE, GRAY, BLACK = 0, 1, 2
    color = {n: WHITE for n in graph}
    for tgt in graph.values():
        for n in tgt:
            color.setdefault(n, WHITE)
    path: list[str] = []

    def dfs(n: str) -> list[str] | None:
        color[n] = GRAY
        path.append(n)
        for m in sorted(graph.get(n, ())):
            if color[m] == GRAY:
                return path[path.index(m):] + [m]
            if color[m] == WHITE:
                found = dfs(m)
                if found:
                    return found
        path.pop()
        color[n] = BLACK
        return None

    for n in sorted(color):
        if color[n] == WHITE:
            found = dfs(n)
            if found:
                return found
    return None


def cross_check(static_edges: frozenset | set,
                observed: frozenset | set) -> list[tuple[str, str]]:
    """Edges the witness observed that the static analyzer did NOT derive
    (analyzer/registry blind spots).  Empty list = the static graph covers
    everything the runtime actually did."""
    return sorted(set(observed) - set(static_edges))


WITNESS = LockWitness()


class _WitnessLock:
    """``threading.Lock`` wrapper recording acquisition order.  API-
    compatible with the subset the tree uses (with-statement, acquire/
    release, locked).  The with-statement path (``__enter__``/``__exit__``)
    inlines the recording — it brackets every pick-seam acquisition."""

    __slots__ = ("_lock", "_name")

    def __init__(self, name: str):
        self._lock = threading.Lock()
        self._name = name

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        ok = self._lock.acquire(blocking, timeout)
        if ok:
            WITNESS.note_acquire(self._name)
        return ok

    def release(self) -> None:
        WITNESS.note_release(self._name)
        self._lock.release()

    def locked(self) -> bool:
        return self._lock.locked()

    def __enter__(self):
        self._lock.acquire()
        stack = WITNESS.thread_stack()
        if stack:
            WITNESS.note_edge(stack[-1], self._name)
        stack.append(self._name)
        return self

    def __exit__(self, *exc) -> None:
        stack = WITNESS.thread_stack()
        if stack and stack[-1] == self._name:
            stack.pop()
        else:
            WITNESS.note_release(self._name)
        self._lock.release()


class _WitnessRLock:
    """``threading.RLock`` wrapper.  Only the OUTERMOST acquisition records
    (reentrant re-acquisition is not an ordering edge — the lock is already
    held by this thread)."""

    __slots__ = ("_lock", "_name", "_tls")

    def __init__(self, name: str):
        self._lock = threading.RLock()
        self._name = name
        self._tls = threading.local()

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        ok = self._lock.acquire(blocking, timeout)
        if ok:
            depth = getattr(self._tls, "depth", 0)
            if depth == 0:
                WITNESS.note_acquire(self._name)
            self._tls.depth = depth + 1
        return ok

    def release(self) -> None:
        depth = getattr(self._tls, "depth", 1) - 1
        self._tls.depth = depth
        if depth == 0:
            WITNESS.note_release(self._name)
        self._lock.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()


def witness_lock(name: str):
    """A lock for the shared-state class field ``name`` ("Class._attr").
    Plain ``threading.Lock`` unless the witness is armed (env, checked at
    construction so tests can arm per-rig)."""
    if armed():
        return _WitnessLock(name)
    return threading.Lock()


def witness_rlock(name: str):
    """Reentrant flavor (Provider/Datastore use RLock)."""
    if armed():
        return _WitnessRLock(name)
    return threading.RLock()
