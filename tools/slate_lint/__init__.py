"""slate_lint: the contract-checking static-analysis framework
(ISSUE 13 tentpole).

The codebase's load-bearing invariants live in CROSS-FILE agreements
— a FROZEN tune row in tune/cache.py and its reader in a driver, an
obs counter literal and the bench leg that reads it back, a fault
site name in a plan and the ``check()`` call that makes it fire, a
lock in ``__init__`` and the mutations it is supposed to guard. No
single call site can see a breach; this package checks the
agreements whole-tree, AST-only (no jax import — tier-1 fast), with
per-finding codes, file:line anchors, in-source exemption comments
(``# slate-lint: exempt[SLxxx] <why>``) and a JSON baseline
mechanism (core.py).

CLI::

    python -m tools.slate_lint [--only CODE|NAME] [--baseline PATH]
                               [--write-baseline PATH] [--list]
                               [--timings] [--obs-doc [PATH|-]]

Rule-numbering history (the check_instrumented.py lineage):

* ``tools/check_instrumented.py`` accreted six rules across PRs 5-12
  and is now a thin back-compat shim over :mod:`.legacy` (identical
  problem strings, pinned by tests). The old rule numbers map to:

    check_instrumented rule 1 (PR 5, ISSUE 5: public ``*_batched``
      drivers decorated)                          -> SL101
    rule 2 (PR 5/7: REQUIRED driver-op map + public ``shard_*_ooc``
      naming rule; "unobservable" messages are SL101, map losses /
      missing files SL102)                        -> SL101/SL102
    rule 3 (PR 6, ISSUE 6: KERNEL_REGISTRY gates + FROZEN tune ops)
                                                  -> SL103
    rule 4 (PR 9, ISSUE 9: ESCALATIONS ladder observable/wired/
      tunable)                                    -> SL104
    rule 5 (PR 11, ISSUE 11: shard lookahead + bcast-wait span)
                                                  -> SL105
    rule 6 (PR 12, ISSUE 12: precision arbitration + cast counters)
                                                  -> SL106

* New analyzers (this PR, ISSUE 13):

    SL201/SL202/SL203  tune-arbitration integrity (:mod:`.tune_keys`)
    SL301              lock discipline            (:mod:`.locks`)
    SL401/SL402        obs literal integrity + docs/OBS_REFERENCE.md
                                                  (:mod:`.obs_literals`)
    SL501/SL502/SL503  fault-site coverage        (:mod:`.fault_sites`)

* PR 14 (ISSUE 14):

    SL601/SL602/SL603  flight-recorder contract: step-loop
                       heartbeats, closed ledger phase set, frozen
                       off-state rows          (:mod:`.flight`)

* PR 17 (ISSUE 17):

    SL701/SL702        task-graph runtime contract: node kinds map
                       onto ledger phases and registered fault
                       sites                  (:mod:`.sched_graph`)

* PR 18 (ISSUE 18):

    SL801/SL802/SL803  request-trace context integrity: serve-tier
                       escalations/counters carry trace ids, series
                       literals ride the obs registry, FROZEN
                       reqtrace/metrics gate rows + readers
                                             (:mod:`.reqtrace_ctx`)

* PR 19 (ISSUE 19):

    SL901/SL902/SL903  elastic-mesh ownership contract: the owners
                       table is the single validated source (both
                       schedule primitives read it), remap never
                       relabels the committed prefix, FROZEN mesh/*
                       rows + literal readers (:mod:`.elastic_mesh`)

Extending: add a module with a ``@core.register(name, codes, doc)``
function ``analyze(repo) -> [core.Finding]``, import it below, and
give it one clean + one violating fixture case in
tests/test_slate_lint.py. New analyzers on a dirty tree may land
with a ``--baseline`` file; this tree carries none.
"""

from __future__ import annotations

from .core import (Finding, REGISTRY, RunResult, register, run)  # noqa: F401

# importing the analyzer modules populates the registry (order here
# == report order; legacy first so the shim's numbering leads)
from . import legacy          # noqa: F401,E402
from . import tune_keys       # noqa: F401,E402
from . import locks           # noqa: F401,E402
from . import obs_literals    # noqa: F401,E402
from . import fault_sites     # noqa: F401,E402
from . import flight          # noqa: F401,E402
from . import sched_graph     # noqa: F401,E402
from . import reqtrace_ctx    # noqa: F401,E402
from . import elastic_mesh    # noqa: F401,E402

from .obs_literals import generate_reference  # noqa: F401,E402
