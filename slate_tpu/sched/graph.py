"""Typed panel-op dependency graphs (ISSUE 17 tentpole, part 1).

A :class:`TaskGraph` is a DAG of :class:`Node`\\ s, each a closure over
the sharded drivers' engines/kernels/broadcaster, labelled
with a node *kind* from the closed set :data:`NODE_KINDS`:

    stage       host->HBM staging of a panel's input
    factor      the in-core panel factor kernel
    solve       a streamed triangular/apply solve step (reserved for
                composed OOC solve policies; no current constructor
                emits one)
    update      a trailing-panel update against a finished panel
    bcast       broadcast issue/completion of a factored panel
    writeback   durable writeback of results (device->host mirrors)

The kind is load-bearing, not cosmetic: :data:`PHASE_OF_KIND` maps
every kind onto the ledger's closed ``PHASES`` attribution column
(obs/ledger.py) — the runtime wraps each node in that frame, so graph
execution fills the flight-recorder columns the single-engine loops
fill — and :data:`FAULT_SITE_OF_KIND` names the registered fault site
(resil/faults.py ``SITES``) covering kinds that perform I/O or comms.
tools/slate_lint's SL7xx analyzer pins both tables complete and
consistent with the live registries; they are deliberately plain
top-level literals so the lint can ``ast.literal_eval`` them.

Edges are declared at construction (``deps=`` or :meth:`TaskGraph.
add_edge`); :meth:`TaskGraph.validate` rejects cycles (Kahn) and
orphans (a node with no edges at all in a multi-node graph is almost
always a forgotten dependency, and would silently run at priority
order only).

Determinism contract: the runtime executes nodes one at a time in
``(key, seq)`` min-order among ready nodes. Policies choose ``key``
tuples so that every panel absorbs its updates in ascending step
order at every lookahead depth — the graphs don't merely compute the
same answer, they run the same kernels on the same operands, which is
what the bitwise pins hold.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..core.exceptions import slate_assert

#: the CLOSED set of node kinds (tools/slate_lint SL701 pins the
#: attribution tables below complete over it)
NODE_KINDS = ("stage", "factor", "solve", "update", "bcast",
              "writeback")

#: node kind -> obs/ledger.py PHASES attribution column. 1:1 onto the
#: ledger's closed phase set: the executor wraps each node's closure
#: in ``_ledger.frame(PHASE_OF_KIND[kind])`` so graph execution fills
#: the same flight-recorder columns as the single-engine loops
#: (bcast completion waits land in ``bcast_wait``; writeback fences
#: are ``cache`` stalls, same as the engines' credit() sites).
PHASE_OF_KIND = {
    "stage": "stage",
    "factor": "factor",
    "solve": "update",
    "update": "update",
    "bcast": "bcast_wait",
    "writeback": "cache",
}

#: node kind -> resil/faults.py SITES entry covering it, for kinds
#: that perform I/O or comms (None = pure compute, no site needed).
#: The stage/writeback sites fire inside StreamEngine (h2d/d2h) and
#: bcast inside dist collectives (ppermute); the per-panel ``step``
#: site fires from the policy's closures once a panel, in ascending
#: order, so seeded-fault runs stay order-identical.
FAULT_SITE_OF_KIND = {
    "stage": "h2d",
    "factor": None,
    "solve": None,
    "update": None,
    "bcast": "ppermute",
    "writeback": "d2h",
}


class Node:
    """One schedulable unit: a closure plus its labels and edges."""

    __slots__ = ("kind", "run", "panel", "step", "owner", "key",
                 "seq", "deps", "_outs", "_nin")

    def __init__(self, kind: str, run: Callable[[], Any], *,
                 panel: Optional[int] = None,
                 step: Optional[int] = None,
                 owner: Optional[int] = None,
                 key: Tuple[int, ...] = (),
                 seq: int = 0) -> None:
        self.kind = kind
        self.run = run
        self.panel = panel
        self.step = step
        self.owner = owner
        self.key = tuple(key)
        self.seq = seq
        self.deps: List["Node"] = []
        self._outs: List["Node"] = []
        self._nin = 0

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return "Node(%s, panel=%r, step=%r, key=%r)" % (
            self.kind, self.panel, self.step, self.key)


class TaskGraph:
    """A DAG of :class:`Node`\\ s with edge-declared dependencies."""

    def __init__(self, op: str = "") -> None:
        self.op = op
        self.nodes: List[Node] = []

    def add(self, kind: str, run: Callable[[], Any], *,
            panel: Optional[int] = None, step: Optional[int] = None,
            owner: Optional[int] = None,
            key: Tuple[int, ...] = (),
            deps: Sequence[Optional[Node]] = ()) -> Node:
        """Append a node; ``deps`` entries that are None are skipped
        (lets policies write ``deps=[maybe_node]`` unconditionally)."""
        slate_assert(kind in NODE_KINDS,
                     "unknown node kind %r (have %s)"
                     % (kind, list(NODE_KINDS)))
        n = Node(kind, run, panel=panel, step=step, owner=owner,
                 key=key, seq=len(self.nodes))
        self.nodes.append(n)
        for d in deps:
            if d is not None:
                self.add_edge(d, n)
        return n

    def add_edge(self, a: Node, b: Node) -> None:
        """Declare ``a`` must complete before ``b`` runs."""
        slate_assert(a is not b, "self-edge on %r" % (a,))
        if a in b.deps:
            return
        b.deps.append(a)
        a._outs.append(b)
        b._nin += 1

    def validate(self) -> None:
        """Reject cycles (Kahn's algorithm) and orphans (a node with
        no edges at all, in a graph of >= 2 nodes)."""
        if len(self.nodes) >= 2:
            for n in self.nodes:
                slate_assert(
                    n.deps or n._outs,
                    "orphan %s node (panel=%r, step=%r) in %r graph: "
                    "no dependencies in either direction — it would "
                    "run at priority order only"
                    % (n.kind, n.panel, n.step, self.op))
        nin = {n: n._nin for n in self.nodes}
        ready = [n for n in self.nodes if nin[n] == 0]
        done = 0
        while ready:
            n = ready.pop()
            done += 1
            for m in n._outs:
                nin[m] -= 1
                if nin[m] == 0:
                    ready.append(m)
        slate_assert(
            done == len(self.nodes),
            "cycle in %r graph: %d of %d nodes unreachable by "
            "topological order" % (self.op, len(self.nodes) - done,
                                   len(self.nodes)))

    def counts(self) -> Dict[str, int]:
        """Node count per kind (bench/report annotation)."""
        out: Dict[str, int] = {}
        for n in self.nodes:
            out[n.kind] = out.get(n.kind, 0) + 1
        return out
