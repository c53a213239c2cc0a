"""The documents name files that exist.

README.md, PERF.md and the package's own docstrings and comments send a
reader to `scripts/`, `tests/`, `raft_stereo_tpu/` and `benchmark/` paths; a
path that is gone sends them nowhere, and nothing else reads the documents.
ROADMAP.md and CHANGES.md are history and are not read.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# A path under one of the four trees, up to a file extension. A template
# (`benchmark/configs/<config>.json`), a glob or a brace list stops the match
# before any extension and is not a claim about one file.
PATH = re.compile(r"(?<![\w/.-])((?:scripts|tests|raft_stereo_tpu|benchmark)/[\w/.-]*?\.(?:py|sh|json|md))(?![\w/*{<-])")


def _texts(document):
    if document == "package docstrings":
        return sorted(glob.glob(os.path.join(REPO, "raft_stereo_tpu", "**", "*.py"), recursive=True))
    return [os.path.join(REPO, document)]


@pytest.mark.parametrize("document", ["README.md", "PERF.md", "package docstrings"])
def test_documents_name_files_that_exist(document):
    missing, named = [], 0
    for path in _texts(document):
        with open(path, encoding="utf-8") as f:
            text = f.read()
        for name in sorted(set(PATH.findall(text))):
            named += 1
            if not os.path.exists(os.path.join(REPO, name)):
                missing.append(f"{os.path.relpath(path, REPO)} names {name}")
    assert named, f"{document} names no path at all: the pattern has gone blind"
    assert not missing, "\n".join(missing)
