"""reprolint — stdlib-``ast`` static analysis for the repo's standing
invariants.

Five passes (see DESIGN_LINT.md for the catalog and the rationale):

* ``compat-seam``     — shard_map spellings only inside parallel/compat.py
* ``lock-discipline`` — ``_GUARDED_BY`` attributes touched only under lock
* ``wire-safety``     — link.send() payloads built from plain types
* ``tracer-hygiene``  — no Python control flow / host escapes on tracers
* ``overflow-guard``  — binom_table/unrank_* dominated by a rank guard

Pure stdlib: importing this package must never import jax/numpy, so the
CI lint job runs on a bare Python with no wheel install.
"""

from .core import (Finding, LintError, collect_files, lint_file, lint_paths,
                   main)
from .passes import ALL_PASSES, pass_ids

__all__ = ["Finding", "LintError", "ALL_PASSES", "pass_ids",
           "collect_files", "lint_file", "lint_paths", "main"]
