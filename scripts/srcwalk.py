"""Shared source-tree walker for the repo's self-contained QA tools.

`scripts/lint.py` (style/pyflakes-lite) and `scripts/analyze.py`
(concurrency & invariant analysis) check the same file set; this module is
the single definition of what "the source tree" means — the skip-dir list
and the walk order — so the two lanes can never drift apart about which
files are checked.
"""

import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# build outputs, caches, and generated docs are never linted or analyzed
SKIP_DIRS = {".git", "_native", "__pycache__", ".pytest_cache", ".claude",
             "doc"}

SOURCE_SUFFIXES = (".py", ".cc", ".h")


def iter_sources(root: str = None, suffixes=SOURCE_SUFFIXES):
    """Yield every checked source file under `root` (default: the repo),
    sorted within each directory for deterministic reports."""
    base = REPO if root is None else root
    for dirpath, dirs, files in os.walk(base):
        dirs[:] = sorted(d for d in dirs if d not in SKIP_DIRS)
        for f in sorted(files):
            if f.endswith(tuple(suffixes)):
                yield os.path.join(dirpath, f)
