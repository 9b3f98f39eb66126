"""graftlint: JAX trace-safety static analysis + runtime retrace guard.

Shape-bucketed execution (utils/bucketing.py) only pays off
while nothing silently retraces or drags device arrays back to host
mid-step. The paper's ND4J/libnd4j split made host/device boundaries
explicit; the JAX port hides them — so this package makes them visible:

- ``engine``        AST module index, call graph, jit-reachability sets
- ``rules``         the five rule classes (host-sync, retrace-hazard,
                    jit-purity, numpy-on-tracer, lock-discipline)
- ``lint``          CLI: ``python -m deeplearning4j_tpu.analysis.lint PKG``
                    with a checked-in baseline (``baseline.json``) so new
                    violations fail CI while grandfathered ones are frozen
- ``retrace_guard`` runtime companion: compile-count-vs-bucket-ladder
                    checks on the jitted entry points

This module must stay import-light: it is imported by ``nn.model`` for the
retrace guard and must never initialize a JAX backend at import time.
"""

__all__ = ["engine", "rules", "lint", "retrace_guard"]
