#!/usr/bin/env python
"""Doc-drift lint: every metric name registered through
``telemetry.registry()`` must be documented in the README (ISSUE 13
satellite — the metrics twin of ``check_env_docs.py``).

PRs 6–12 grew ~25 counter/gauge/histogram names; each is one rename (or
one new metric) away from silently drifting out of the README's metrics
reference. This lint greps ``sparkdl_tpu/`` (plus ``scripts/``) for
registration call sites —
``.counter("name")`` / ``.gauge("name")`` / ``.histogram("name")`` and
the serving engine's ``_metric("kind", "name", ...)`` helper — and
fails loudly when any literal name is missing from ``README.md``
(``missing_metrics``), and in reverse when a row of the README's metric
tables names a metric no code registers (``stale_metrics``).
(Names built dynamically escape the grep, same limitation as any
source lint; the codebase registers with literals for exactly this
reason.) Stdlib-only, no package import — it must run anywhere, fast,
as a tier-1 test and standalone in CI:

    python scripts/check_metric_docs.py      # exit 1 + list on drift
"""

import os
import re
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Registration call sites: reg.counter("x") / .gauge("x") /
# .histogram("x", ...) and the engine's _metric("gauge", "x", ...)
# indirection. Only literal first-argument names are caught.
_CALL_RE = re.compile(
    r"\.(?:counter|gauge|histogram)\(\s*['\"]([A-Za-z_][A-Za-z0-9_]*)['\"]")
_HELPER_RE = re.compile(
    r"_metric\(\s*['\"](?:counter|gauge|histogram)['\"]\s*,\s*"
    r"['\"]([A-Za-z_][A-Za-z0-9_]*)['\"]")
# A row of a README metric table: | `a` / `b` | counter / histogram | ... |
_TYPE_CELL_RE = re.compile(
    r"(?:counter|gauge|histogram)(?: / (?:counter|gauge|histogram))*")
_NAME_CELL_RE = re.compile(r"`([A-Za-z_][A-Za-z0-9_]*)`")


def _py_files(root: str):
    for top in ("sparkdl_tpu", "scripts"):
        for dirpath, dirnames, filenames in os.walk(os.path.join(root, top)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in filenames:
                if f.endswith(".py"):
                    yield os.path.join(dirpath, f)


def code_metric_names(root: str = _REPO) -> set[str]:
    """Every metric name registered (with a literal) by package/scripts
    code."""
    out: set[str] = set()
    for path in _py_files(root):
        try:
            with open(path, encoding="utf-8", errors="replace") as f:
                src = f.read()
        except OSError:
            continue
        out.update(_CALL_RE.findall(src))
        out.update(_HELPER_RE.findall(src))
    return out


def documented_metric_names(code_names: set[str],
                            readme: str | None = None) -> set[str]:
    """The subset of ``code_names`` that appear verbatim in the
    README."""
    readme = readme or os.path.join(_REPO, "README.md")
    try:
        with open(readme, encoding="utf-8", errors="replace") as f:
            text = f.read()
    except OSError:
        return set()
    return {n for n in code_names if n in text}


def missing_metrics(root: str = _REPO,
                    readme: str | None = None) -> list[str]:
    """Metric names registered in code but absent from the README,
    sorted."""
    code = code_metric_names(root)
    return sorted(code - documented_metric_names(code, readme))


def stale_metrics(root: str = _REPO, readme: str | None = None) -> list[str]:
    """Names in the README's metric tables — the rows whose second cell
    is ``counter``, ``gauge``, ``histogram`` or several of them with
    `` / `` between — that no code registers, sorted."""
    readme = readme or os.path.join(_REPO, "README.md")
    try:
        with open(readme, encoding="utf-8", errors="replace") as f:
            lines = f.read().splitlines()
    except OSError:
        return []
    tabled: set[str] = set()
    for line in lines:
        cells = [c.strip() for c in line.split("|")]
        if len(cells) >= 4 and _TYPE_CELL_RE.fullmatch(cells[2]):
            tabled.update(_NAME_CELL_RE.findall(cells[1]))
    return sorted(tabled - code_metric_names(root))


def main() -> int:
    problems = [
        ("metric names registered through telemetry.registry() but "
         "missing from README.md (document each in the metrics reference, "
         "Live telemetry & bottleneck attribution section)",
         missing_metrics()),
        ("metric names in README.md's tables that no code registers",
         stale_metrics())]
    for what, names in problems:
        if names:
            print(f"check_metric_docs: {what}:", file=sys.stderr)
            for n in names:
                print(f"  {n}", file=sys.stderr)
    if any(names for _, names in problems):
        return 1
    n = len(code_metric_names())
    print(f"check_metric_docs: ok — {n} metric names all documented")
    return 0


if __name__ == "__main__":
    sys.exit(main())
