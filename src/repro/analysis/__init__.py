"""Static + compiled-artifact analysis for the EF-BV reproduction.

Layers (see docs/static_analysis.md for the rule catalog):

  * :mod:`repro.analysis.framework` -- rule registry, ``# repro: noqa``
    suppressions, golden-count pinning, the runner;
  * :mod:`repro.analysis.rules`     -- the six repo-invariant AST rules;
  * :mod:`repro.analysis.hlo`       -- the ``dense_free`` pack-kernel proofs;
  * :mod:`repro.analysis.docs`      -- markdown link check + doctest census;
  * :mod:`repro.analysis.sanitize`  -- the ``--sanitize`` runtime mode.

Entry point: ``python -m repro.analysis`` (or the ``repro-analysis``
console script).
"""

from repro.analysis.framework import (  # noqa: F401
    AnalysisResult,
    Finding,
    Module,
    Rule,
    RULES,
    analyze_paths,
    compare_golden,
    rule,
    write_golden,
)
from repro.analysis import rules as _rules  # noqa: F401  (registers rules)


def main(argv=None) -> int:
    """Console-script entry (``repro-analysis`` in pyproject.toml)."""
    from repro.analysis.__main__ import main as _main

    return _main(argv)
