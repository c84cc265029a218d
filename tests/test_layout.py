"""Collection roots: a bare ``python -m pytest`` collects the repository's
own test trees and nothing else.

``pytest.ini`` names those trees (``testpaths``). A test module outside
them would silently never run; a tree inside a directory ``.gitignore``
names (an unpacked checkout of another commit kept for a side-by-side
comparison, say) must never be collected beside them, since its modules
would shadow the real ones by name.
"""

import configparser
import fnmatch
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TEST_FILES = ("test_*.py", "*_test.py")  # pytest's default python_files


def _testpaths():
    ini = configparser.ConfigParser()
    ini.read(os.path.join(REPO, "pytest.ini"))
    return ini["pytest"]["testpaths"].split()


def _ignored_names():
    """The .gitignore patterns (each matches a name at any depth), plus
    .git itself."""
    with open(os.path.join(REPO, ".gitignore")) as f:
        lines = [ln.strip() for ln in f]
    return [ln.rstrip("/") for ln in lines
            if ln and not ln.startswith("#")] + [".git"]


def _ignored(name, patterns):
    return any(fnmatch.fnmatch(name, p) for p in patterns)


def test_every_test_module_lies_under_a_testpath():
    roots = [os.path.join(REPO, p) for p in _testpaths()]
    patterns = _ignored_names()
    stray = []
    for dirpath, dirnames, files in os.walk(REPO):
        dirnames[:] = [d for d in dirnames if not _ignored(d, patterns)]
        for name in files:
            if not any(fnmatch.fnmatch(name, t) for t in TEST_FILES):
                continue
            path = os.path.join(dirpath, name)
            if not any(path.startswith(r + os.sep) for r in roots):
                stray.append(os.path.relpath(path, REPO))
    assert not stray, stray


def test_no_testpath_lies_under_an_ignored_directory():
    patterns = _ignored_names()
    paths = _testpaths()
    assert paths
    for p in paths:
        assert os.path.isdir(os.path.join(REPO, p)), p
        assert not any(_ignored(part, patterns) for part in p.split("/")), p
