#!/usr/bin/env python
"""Doc-drift lint: every ``SPARKDL_*`` env var referenced by the package
must be documented in the README (ISSUE 6 satellite), and the README
names no var that nothing reads and no file that is gone.

PRs 1–5 grew ~30 ``SPARKDL_*`` knobs; each is one rename (or one new
knob) away from silently drifting out of the README's env-var tables.
This lint greps ``sparkdl_tpu/`` and ``scripts/`` for the pattern and
fails loudly when any var is missing from ``README.md``
(``missing_vars``). The other direction is what a deletion leaves
behind: a documented var that no code reads any more (``stale_vars``),
and a script, test or root file the README still names after it went
(``missing_paths``). Stdlib-only, no imports of the package — it must
run in any environment, fast, as a tier-1 test
(``tests/test_telemetry.py``) and standalone in CI:

    python scripts/check_env_docs.py          # exit 1 + list on drift
"""

import os
import re
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_VAR_RE = re.compile(r"SPARKDL_[A-Z0-9_]+")
# Trailing fragments the regex over-matches in prose/format strings
# (e.g. "SPARKDL_FLASH_BLOCK_Q``/``_K" documents _K via ellipsis) are
# NOT special-cased: every var must appear verbatim in the README.


_CODE_TOPS = ("sparkdl_tpu", "scripts")
# Who may read a var besides the package and the scripts: the suite's own
# harness (its platform switch) and the entry points at the root. The
# test FILES are not readers — a test that still sets a dead knob must
# not keep its README row alive.
_OTHER_READERS = (os.path.join("tests", "conftest.py"), "chip_smoke.py",
                  "__graft_entry__.py", "benchmark")
# `scripts/x.py` and `tests/x.py` by path; a bare `x.py` (the root, or
# shorthand for a script); a bare `X.json` whose name starts upper-case
# (the root's records — lower-case ones such as `gang_timeline.json` are
# what a run writes, not files of the repo).
_PATH_RE = re.compile(r"`((?:scripts|tests)/[A-Za-z0-9_./-]+\.py"
                      r"|[A-Za-z0-9_-]+\.py|[A-Z][A-Za-z0-9_-]*\.json)`")


def code_env_vars(root: str = _REPO, tops=_CODE_TOPS) -> set[str]:
    """Every SPARKDL_* name referenced by package/scripts code."""
    out: set[str] = set()
    for top in (os.path.join(root, t) for t in tops):
        if os.path.isfile(top):
            files = [top]
        else:
            files = []
            for dirpath, dirnames, filenames in os.walk(top):
                dirnames[:] = [d for d in dirnames
                               if d != "__pycache__"]
                files += [os.path.join(dirpath, f) for f in filenames
                          if f.endswith(".py")]
        for path in files:
            try:
                with open(path, encoding="utf-8", errors="replace") as f:
                    out.update(_VAR_RE.findall(f.read()))
            except OSError:
                continue
    return out


def documented_env_vars(readme: str | None = None) -> set[str]:
    readme = readme or os.path.join(_REPO, "README.md")
    try:
        with open(readme, encoding="utf-8", errors="replace") as f:
            return set(_VAR_RE.findall(f.read()))
    except OSError:
        return set()


def missing_vars(root: str = _REPO, readme: str | None = None) -> list[str]:
    """Vars referenced in code but absent from the README, sorted."""
    return sorted(code_env_vars(root) - documented_env_vars(readme))


def stale_vars(root: str = _REPO, readme: str | None = None) -> list[str]:
    """Vars the README names that nothing reads, sorted. A documented
    name ending in ``_`` (a family written ``..._SLO_*`` in prose) is a
    prefix and must match at least one var that is read."""
    read = code_env_vars(root, _CODE_TOPS + _OTHER_READERS)
    return sorted(
        v for v in documented_env_vars(readme) - read
        if not (v.endswith("_") and any(r.startswith(v) for r in read)))


def missing_paths(root: str = _REPO, readme: str | None = None) -> list[str]:
    """Files the README names in backticks that are not in the tree,
    sorted (see ``_PATH_RE`` for which names count)."""
    readme = readme or os.path.join(root, "README.md")
    try:
        with open(readme, encoding="utf-8", errors="replace") as f:
            named = set(_PATH_RE.findall(f.read()))
    except OSError:
        return []

    def exists(p):
        bare_script = "/" not in p and p.endswith(".py")
        return any(os.path.exists(os.path.join(root, d, p))
                   for d in (("", "scripts") if bare_script else ("",)))
    return sorted(p for p in named if not exists(p))


def main() -> int:
    problems = [
        ("SPARKDL_* env vars referenced in code but missing from README.md "
         "(document each in the env-var tables: Observability / Batch "
         "scoring pipeline / Environment variables)", missing_vars()),
        ("SPARKDL_* names README.md documents that no code reads",
         stale_vars()),
        ("files README.md names that are not in the tree", missing_paths())]
    for what, names in problems:
        if names:
            print(f"check_env_docs: {what}:", file=sys.stderr)
            for v in names:
                print(f"  {v}", file=sys.stderr)
    if any(names for _, names in problems):
        return 1
    n = len(code_env_vars())
    print(f"check_env_docs: ok — {n} SPARKDL_* vars all documented")
    return 0


if __name__ == "__main__":
    sys.exit(main())
