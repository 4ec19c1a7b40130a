"""Every relative markdown link of ``README.md`` and ``docs/*.md``, and
every backticked path they write from the repository's root, leads to a
file of the repository."""

import os
import re

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DOCS = ["README.md"] + sorted(
    os.path.join("docs", f)
    for f in os.listdir(os.path.join(_REPO, "docs"))
    if f.endswith(".md")
)
_LINK = re.compile(r"\[[^\]\n]*\]\(([^)\s]+)\)")
_ROOT_PATH = re.compile(
    r"`((?:docs|scripts|tests|examples|benchmark|horovod_tpu|csrc)/"
    r"[A-Za-z0-9_./-]+\.(?:py|md|sh|json|cc|h))`"
)


@pytest.mark.parametrize("doc", _DOCS)
def test_relative_links_resolve(doc):
    with open(os.path.join(_REPO, doc), encoding="utf-8") as f:
        text = f.read()
    missing = [
        p for p in _ROOT_PATH.findall(text)
        if not os.path.exists(os.path.join(_REPO, p))
    ]
    # fenced code is not prose: `a[i](x)` there is no link
    text = re.sub(r"```.*?```", "", text, flags=re.S)
    base = os.path.dirname(os.path.join(_REPO, doc))
    for target in _LINK.findall(text):
        if re.match(r"[a-z][a-z0-9+.-]*:", target) or target.startswith("#"):
            continue  # another site, or an anchor of this page
        path = os.path.normpath(os.path.join(base, target.split("#")[0]))
        if not os.path.exists(path):
            missing.append(target)
    assert not missing, f"{doc} links to what is not there: {missing}"
