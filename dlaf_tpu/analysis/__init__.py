"""dlaf_tpu.analysis — project-specific SPMD/trace-safety linter.

``python -m dlaf_tpu.analysis [paths]`` runs five AST rule families over
the tree.  The analyzer itself is stdlib ``ast`` only (no third-party
deps, nothing is imported or executed from the linted files):

* **DLAF001** cache-key completeness — a ``tune`` knob read at trace time
  by a compiled-kernel builder must be folded into that cache's key.
* **DLAF002** collective symmetry — no collectives under rank-dependent
  Python ``if``; Mosaic ``collective_id`` allocation must go through
  ``collective_id_for`` / the reserved table.
* **DLAF003** trace purity — no host syncs, wall-clock reads or host RNG
  inside ``jit`` / ``shard_map`` / ``pallas_call`` regions.
* **DLAF004** serve lock discipline — no blocking work or future
  completion while holding a serve-layer lock.
* **DLAF005** program names — no ``jax.jit`` of a ``partial`` or a
  ``lambda`` (an unnamed program); ``plan.jit(op, fun)`` names it.

See docs/LINTING.md for the rule catalog, the shipped bugs each rule
encodes, and the suppression / baseline workflow.
"""
from dlaf_tpu.analysis.engine import (  # noqa: F401
    Finding,
    Result,
    load_baseline,
    render_human,
    run,
    write_baseline,
)

__all__ = ["Finding", "Result", "run", "render_human",
           "load_baseline", "write_baseline"]
