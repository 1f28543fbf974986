"""Docs-site health: markdown link check over README + docs/, and
doctests of the runnable ``>>>`` examples in the public API surface —
so the docs can't silently rot (the CI docs job runs the same checks)."""

import doctest
import importlib
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import check_links  # noqa: E402  (tools/check_links.py)

# CI runs the docs-health suite in its own step (pytest -m docs).
pytestmark = pytest.mark.docs

# Modules whose docstrings carry runnable >>> examples.  Keep these
# cheap: pure-python helpers only, no kernel launches.
DOCTEST_MODULES = [
    "repro.tuning.cache",
    "repro.tuning.space",
    "repro.tuning.dispatch",
    "repro.distributed.cascade",
    "repro.distributed.pack_gemm",
    "repro.serving.scheduler",
    "repro.serving.engine",
    "repro.serving.kvpool",
    "repro.core.hw",
    "repro.obs.efficiency",
    "repro.obs.trace",
    "repro.obs.devtrace",
    "repro.kernels.decode_attention",
    "repro.models.layers",
    "repro.launch.compile_cache",
]


def test_readme_and_docs_links_resolve():
    files = check_links.md_files([os.path.join(REPO, "README.md"),
                                  os.path.join(REPO, "docs")])
    assert files, "README.md / docs/ not found"
    names = {f.name for f in files}
    assert {"README.md", "ARCHITECTURE.md", "TUNING.md",
            "SERVING.md"} <= names
    bad = {str(f): check_links.broken_links(f) for f in files}
    bad = {f: links for f, links in bad.items() if links}
    assert not bad, f"broken markdown links: {bad}"


def test_readme_links_docs_site():
    readme = open(os.path.join(REPO, "README.md")).read()
    assert "docs/ARCHITECTURE.md" in readme
    assert "docs/TUNING.md" in readme


@pytest.mark.parametrize("modname", DOCTEST_MODULES)
def test_doctests(modname):
    mod = importlib.import_module(modname)
    res = doctest.testmod(mod, verbose=False)
    assert res.attempted > 0, f"{modname} lost its >>> examples"
    assert res.failed == 0, f"{modname}: {res.failed} doctest failures"
