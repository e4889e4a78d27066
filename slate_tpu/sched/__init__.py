"""Task-graph runtime: the issue order of the sharded streams.

A sharded out-of-core factorization (``dist/shard_ooc.py``) has
broadcasts in flight at a lookahead depth while each host sweeps its
trailing panels, so what a host may issue next is a partial order. The
drivers there state it as a graph and this package runs it; the
elastic route (``dist/elastic.py``) builds one graph per re-ownership
segment. The single-engine streams in ``linalg/ooc.py`` have a total
order, keep their ``for`` loop and import nothing from here.

* :mod:`.graph` — typed nodes (``stage``/``factor``/``solve``/
  ``update``/``bcast``/``writeback``) with panel/step/owner labels,
  edge-declared dependencies, and cycle/orphan validation.
* :mod:`.policies` — the graph *constructor*; lookahead is a pure
  graph property (depth d only moves the slot a panel's factor and
  broadcast are keyed at).
* :mod:`.runtime` — a small executor that issues any ready node, one
  at a time, with deterministic tie-breaking, so a run is repeatable
  node for node and BITWISE equal at every depth.
"""

from .graph import (FAULT_SITE_OF_KIND, NODE_KINDS, PHASE_OF_KIND,
                    Node, TaskGraph)
from .policies import sharded_stream
from .runtime import execute

__all__ = ["NODE_KINDS", "PHASE_OF_KIND", "FAULT_SITE_OF_KIND",
           "Node", "TaskGraph", "execute", "sharded_stream"]
