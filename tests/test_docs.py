"""Documentation must execute: every ```python block in README.md and
docs/ARCHITECTURE.md runs as-is (blocks within one file share a namespace,
so later snippets may build on earlier ones), and the public-API docstring
examples run under doctest."""

import doctest
import os
import re

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")

_BLOCK = re.compile(r"```python\n(.*?)```", re.DOTALL)


def _python_blocks(path):
    with open(os.path.join(ROOT, path)) as fh:
        return _BLOCK.findall(fh.read())


@pytest.mark.parametrize("path", ["README.md", "docs/ARCHITECTURE.md",
                                  "docs/SERVING.md", "docs/CONFORMANCE.md",
                                  "docs/EXPERIMENTS.md", "docs/MEASURES.md"])
def test_doc_code_blocks_run(path):
    blocks = _python_blocks(path)
    assert blocks, f"{path} has no python blocks?"
    ns = {}
    for i, block in enumerate(blocks):
        try:
            exec(compile(block, f"{path}:block{i}", "exec"), ns)
        except Exception as e:  # pragma: no cover - the assertion message
            raise AssertionError(
                f"{path} block {i} failed: {e}\n---\n{block}") from e


@pytest.mark.parametrize("module_name", [
    "repro.core.evaluator",
    "repro.core.registry",
    "repro.core.trec",
    "repro.serve",
    "repro.serve.service",
    "repro.serve.cache",
    "repro.serve.batcher",
    "repro.serve.wire",
    "repro.serve.testing",
    "repro.serve.cluster.ring",
    "repro.serve.cluster.breaker",
    "repro.serve.cluster.journal",
    "repro.serve.cluster.chaos",
    "repro.client",
    "repro.client.aio",
    "repro.client.sync",
    "repro.kernels.ops",
    "repro.kernels.bucketing",
    "repro.obs",
    "repro.stats",
    "repro.stats.significance",
    "repro.stats.corrections",
    "repro.core.sweep",
])
def test_docstring_examples(module_name):
    import importlib

    module = importlib.import_module(module_name)
    results = doctest.testmod(module, verbose=False, report=True)
    assert results.attempted > 0, f"{module_name}: no doctests collected"
    assert results.failed == 0, f"{module_name}: {results.failed} doctest(s) failed"


def test_readme_documents_required_sections():
    with open(os.path.join(ROOT, "README.md")) as fh:
        readme = fh.read()
    for needle in ("python -m repro", "make verify", "Module map",
                   "tokenize_run", "ShardedEvaluator", "repro.serve",
                   "EvaluationService", "REPRO_INTERPRET",
                   "kernels/bucketing.py"):
        assert needle in readme, needle


def test_benchmark_segment_names_match_docs():
    """`benchmarks.run --list` and the docs must name the same segments.

    The registry (``benchmarks.run.SEGMENTS``) is the single source of
    truth; the run.py module docstring and the README's segment list must
    mention every name, so ``--only`` help, docs, and CI never drift.
    """
    import sys

    sys.path.insert(0, ROOT)
    try:
        from benchmarks.run import SEGMENTS
    finally:
        sys.path.pop(0)
    names = list(SEGMENTS)
    assert len(names) == len(set(names))

    import benchmarks.run as run_mod

    for name in names:
        assert f"``{name}``" in run_mod.__doc__, (
            f"segment {name!r} missing from benchmarks/run.py docstring")
    with open(os.path.join(ROOT, "README.md")) as fh:
        readme = fh.read()
    m = re.search(r"Full segment list: (.*?)\.\n", readme, re.DOTALL)
    assert m, "README.md lost its 'Full segment list:' line"
    readme_names = re.findall(r"`([a-z0-9_]+)`", m.group(1))
    assert readme_names == names, (
        f"README segment list {readme_names} != registry {names}")
