#!/usr/bin/env python
"""Lint: every registered `dllama_*` metric is documented, and vice versa.

This check is now the `metrics-docs` rule inside the dlint framework
(`python -m dllama_tpu.analysis` runs it with everything else); this
script survives as a thin shim so existing invocations and CI steps keep
working. See dllama_tpu/analysis/rules_metrics.py for the semantics.

Usage: python scripts/check_metrics_docs.py  (exit 0 clean, 1 drifted)
"""

from __future__ import annotations

import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from dllama_tpu.analysis.core import collect_repo, run_rules  # noqa: E402
from dllama_tpu.analysis.rules_metrics import MetricsDocsRule  # noqa: E402


def main() -> int:
    repo = collect_repo(REPO, ["dllama_tpu"])
    findings, _ = run_rules(repo, [MetricsDocsRule()])
    for f in findings:
        print(f.render())
    if findings:
        print(
            "\nfix: update the tables in docs/serving_metrics.md to match "
            "the registration sites (grep for the name above)."
        )
        return 1
    print("metrics docs in sync (dlint metrics-docs rule)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
