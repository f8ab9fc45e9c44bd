"""metrics-docs: registered ``dllama_*`` metrics and the operator doc
must agree in both directions.

This is the former ``scripts/check_metrics_docs.py`` lint folded into
the dlint framework so ``python -m dllama_tpu.analysis`` is the one
entrypoint that runs everything; the script survives as a thin shim
over this rule. Semantics are unchanged:

* source side — static scan of ``counter("dllama_...")`` /
  ``gauge(`` / ``histogram(`` registration calls across ``dllama_tpu/``
  (registrations span lines, so the regex runs over whole file
  contents). Dynamically named metrics (the telemetry
  Counter's f-string template) have no literal name at the registration
  site and stay out of scope;
* doc side — every backticked ``dllama_*`` identifier in
  ``docs/serving_metrics.md``. The ``<name>`` placeholder in the
  template breaks the identifier pattern, so it never counts.

A metric registered but undocumented is silent telemetry nobody can
discover; documented but unregistered is a dashboard querying a
phantom.
"""

from __future__ import annotations

import re
from typing import Iterable

from .core import Finding, Repo, Rule

DOC_REL = "docs/serving_metrics.md"

_REGISTRATION = re.compile(
    r"\b(?:counter|gauge|histogram)\(\s*[\"'](dllama_[a-z0-9_]+)[\"']"
)
_DOC_NAME = re.compile(r"`(dllama_[a-z0-9_]+)`")


def registered_names(repo: Repo) -> dict[str, tuple[str, int]]:
    """metric name -> (path, line) of its first registration site."""
    names: dict[str, tuple[str, int]] = {}
    for mod in repo.modules:
        if not mod.rel.startswith("dllama_tpu/"):
            continue
        for m in _REGISTRATION.finditer(mod.text):
            line = mod.text.count("\n", 0, m.start()) + 1
            names.setdefault(m.group(1), (mod.rel, line))
    return names


def documented_names(repo: Repo) -> dict[str, int]:
    doc = repo.root / DOC_REL
    if not doc.exists():
        return {}
    text = doc.read_text()
    names: dict[str, int] = {}
    for m in _DOC_NAME.finditer(text):
        names.setdefault(m.group(1), text.count("\n", 0, m.start()) + 1)
    return names


class MetricsDocsRule(Rule):
    name = "metrics-docs"
    description = (
        "every registered dllama_* metric is documented in "
        "docs/serving_metrics.md, and vice versa"
    )

    def check_repo(self, repo: Repo) -> Iterable[Finding]:
        code = registered_names(repo)
        doc = documented_names(repo)
        for name in sorted(set(code) - set(doc)):
            path, line = code[name]
            yield Finding(
                rule=self.name, path=path, line=line,
                message=(
                    f"metric {name} is registered here but missing from "
                    f"{DOC_REL}"
                ),
            )
        for name in sorted(set(doc) - set(code)):
            yield Finding(
                rule=self.name, path=DOC_REL, line=doc[name],
                message=(
                    f"metric {name} is documented but registered nowhere "
                    f"(dashboards would query a phantom)"
                ),
            )
