"""Rule registry for ``dlaf_tpu.analysis``.

A rule is a module with ``RULE`` (the id, ``DLAF00x``), ``SUMMARY`` (one
line) and ``check(project) -> list[Finding]``.  Order here is report
order; ids are stable across releases (suppressions and the baseline
refer to them).
"""
from dlaf_tpu.analysis.rules import cache_keys, collectives, locks, program_names, purity

RULES = (cache_keys, collectives, purity, locks, program_names)

__all__ = ["RULES", "cache_keys", "collectives", "purity", "locks", "program_names"]
