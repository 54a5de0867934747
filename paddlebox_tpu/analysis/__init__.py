"""pblint — AST-based project-invariant linter.

The reliability arcs of this codebase (PRs 3-7) each ended with a
"review-pass hardening" list: a human reviewer catching violations of
invariants the codebase already believed in — raw writes where the
atomic tmp->fsync->replace discipline was required, donefile lines
written outside the one sanctioned appender, bare ``threading.Thread``
spawns that strip telemetry context, faultpoints outside the closed
kill-matrix registry, flags drifting from the registry. This package
encodes those invariants as machine-checked rules, the same move the
driver's ledger makes for performance: a recorded gate instead of
reviewer memory.

Pieces:

- :mod:`paddlebox_tpu.analysis.core` — the rule framework: per-file AST
  contexts, a cross-file :class:`~paddlebox_tpu.analysis.core.ProjectIndex`
  (flags, faultpoints, test references), the waiver mechanism
  (``# pblint: disable=<rule>[,<rule>] -- <reason>``, reason mandatory),
  and the findings/baseline model.
- :mod:`paddlebox_tpu.analysis.rules` — the rules themselves, each
  grounded in a real prior incident (see docs/INVARIANTS.md).
- :mod:`paddlebox_tpu.analysis.lint` — the CLI::

      python -m paddlebox_tpu.analysis.lint [paths...]

  Exit 0 = clean, 1 = unwaived findings, 2 = usage error; one
  ``file:line rule message`` line per finding.

Deliberately import-light: nothing here touches jax (or any other
package module), so the lint gate runs on a bare CPU box in well under
the tier-1 budget — tests/test_lint_clean.py proves the CLI passes with
jax imports blocked outright.
"""

from paddlebox_tpu.analysis.core import (  # noqa: F401
    Finding,
    Linter,
    Project,
    load_baseline,
)
from paddlebox_tpu.analysis.rules import ALL_RULES  # noqa: F401
