"""Documentation snippets are executable — every fenced ``python`` block
in docs/*.md runs top-to-bottom in a per-file namespace (the reference's
documentation module compiled its snippet sources the same way; ref:
documentation/ — reconstructed, mount empty; SURVEY.md §2)."""
from __future__ import annotations

import functools
import importlib.util
import os
import re

import pytest

DOCS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "docs")

_FENCE = re.compile(r"```python\n(.*?)```", re.DOTALL)


def _doc_files():
    return sorted(f for f in os.listdir(DOCS) if f.endswith(".md"))


def test_docs_exist():
    assert _doc_files(), DOCS


@pytest.mark.parametrize("fname", _doc_files())
def test_doc_snippets_run(fname):
    text = open(os.path.join(DOCS, fname)).read()
    blocks = _FENCE.findall(text)
    assert blocks, f"{fname} has no python snippets"
    ns: dict = {}
    for i, block in enumerate(blocks):
        try:
            exec(compile(block, f"{fname}[snippet {i}]", "exec"), ns)
        except Exception as ex:  # pragma: no cover
            raise AssertionError(
                f"{fname} snippet {i} failed: {ex}\n---\n{block}") from ex


# -- what a document names exists ---------------------------------------------

REPO = os.path.dirname(DOCS)

_NAMING_DOCS = ["README.md", "docs/guide.md", "docs/tpu.md",
                "docs/metrics.md", "benchmarks/README.md",
                ".claude/skills/verify/SKILL.md"]

_COMMAND = re.compile(r"\bpython3?\s+(?:-m\s+([\w.]+)|([\w./-]+\.py)\b)")
_CONFIG_CALL = re.compile(r"\b(EngineConfig|ServerConfig)\(")
_KEYWORD = re.compile(r"(?:^|[(,])\s*(\w+)\s*=(?!=)")
_ENV_NAME = re.compile(r"\bCAPS_TPU_[A-Z0-9_]+\b")


def _module_exists(module: str) -> bool:
    top = module.split(".")[0]
    if not os.path.isdir(os.path.join(REPO, top)):  # pytest, not ours
        return importlib.util.find_spec(top) is not None
    path = os.path.join(REPO, *module.split("."))
    return os.path.isfile(path + ".py") \
        or os.path.isfile(os.path.join(path, "__main__.py"))


def _top_level_keywords(text: str, start: int):
    """Keyword names of the call whose ``(`` ends at ``start``; nested
    calls' keywords are theirs, not this call's."""
    depth, args = 1, []
    for ch in text[start:]:
        depth += ch in "([{"
        depth -= ch in ")]}"
        if depth == 0:
            break
        args.append(ch if depth == 1 else " ")
    return _KEYWORD.findall("(" + "".join(args))


@functools.lru_cache(maxsize=None)
def _env_names_the_package_reads():
    names = set()
    for dirpath, _dirs, files in os.walk(os.path.join(REPO, "caps_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(dirpath, f)) as fh:
                    names.update(_ENV_NAME.findall(fh.read()))
    return names


@pytest.mark.parametrize("doc", _NAMING_DOCS)
def test_doc_names_exist_in_the_tree(doc):
    """Every ``python <path>.py`` / ``python -m <module>`` command, every
    ``EngineConfig(<field>=`` / ``ServerConfig(<field>=`` keyword and every
    ``CAPS_TPU_*`` name a document mentions exists in the tree."""
    import dataclasses
    from caps_tpu.okapi.config import EngineConfig
    from caps_tpu.serve import ServerConfig
    fields = {"EngineConfig": {f.name for f in dataclasses.fields(EngineConfig)},
              "ServerConfig": {f.name for f in dataclasses.fields(ServerConfig)}}
    with open(os.path.join(REPO, doc)) as fh:
        text = fh.read()
    missing = []
    for module, path in _COMMAND.findall(text):
        if module and not _module_exists(module):
            missing.append(f"python -m {module}")
        if path and not os.path.isfile(os.path.join(REPO, path)):
            missing.append(f"python {path}")
    for m in _CONFIG_CALL.finditer(text):
        for kw in _top_level_keywords(text, m.end()):
            if kw not in fields[m.group(1)]:
                missing.append(f"{m.group(1)}({kw}=")
    known_env = _env_names_the_package_reads()
    missing += [n for n in sorted(set(_ENV_NAME.findall(text)))
                if n not in known_env]
    assert not missing, f"{doc} names what the tree does not have: {missing}"
