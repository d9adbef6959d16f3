"""A document may not name what the tree no longer has.

``scripts/check_env_contract.py`` checks code -> documents (every knob the
code reads is written down).  This is the other direction, a case a
document: every path under one of the repository's directories and every
``python <file>`` exists, every ``anomod <sub-command>`` line of a code
block parses with the CLI's own parser, and every ``ANOMOD_*`` name is read
by the program.  No document is imported or run.
"""

import functools
import re
import shlex
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
DOCS = ("README.md", "docs/ARCHITECTURE.md", "docs/CONTRACTS.md",
        "docs/GOLDEN_REPORT.md", "docs/MIGRATION.md",
        "docs/OBSERVABILITY.md", "docs/QUALITY.md", "docs/SERVING.md")
#: the directories git commits at the root of this repository
TOP_DIRS = ("anomod", "bench_runs", "benchmark", "docs", "native",
            "scripts", "tests", "tpu_tests")
#: where the program reads its environment
KNOB_SOURCES = ("anomod/**/*.py", "scripts/*.py", "chip_smoke.py",
                "benchmark/**/*.py")

_PATH = re.compile(r"(?<![\w./-])(`?)((?:%s)/[^\s`'\"()\[\],;|]*)"
                   % "|".join(TOP_DIRS))
_PYTHON_FILE = re.compile(r"\bpython3?\s+([\w./-]+\.py)\b")
_KNOB = re.compile(r"ANOMOD_[A-Z0-9_]+")
_FENCE = re.compile(r"^```.*?$(.*?)^```\s*$", re.M | re.S)
_INLINE = re.compile(r"`anomod\s+([a-z][\w-]*)")
#: a pattern, not a path: ``tests/test_*.py``, ``bench_runs/<stamp>_...``
_PATTERN_MARKS = ("*", "{", "<", "…", "...")


def missing_paths(text: str) -> list:
    out = []
    for quoted, tok in _PATH.findall(text):
        if any(m in tok for m in _PATTERN_MARKS):
            continue
        # ``file.py::test``, ``file.py:120``, a sentence's full stop
        tok = re.sub(r":.*$", "", tok).rstrip(".")
        path = ROOT / tok
        if not (quoted or path.suffix or tok.endswith("/")):
            continue            # prose: "the native/python staging"
        # a record named by its stamp alone (``bench_runs/20260731T070532Z``)
        stem_of_one = not path.suffix and not tok.endswith("/") \
            and any(path.parent.glob(path.name + "*"))
        if not path.exists() and not stem_of_one:
            out.append(tok)
    out += [f for f in _PYTHON_FILE.findall(text) if not (ROOT / f).exists()]
    return sorted(set(out))


def _command_lines(text: str):
    """The ``anomod ...`` command lines of the fenced blocks, joined over
    ``\\`` continuations, without prompt, environment, pipe and comment."""
    for block in _FENCE.findall(text):
        for line in block.replace("\\\n", " ").splitlines():
            line = re.sub(r"^\s*\$\s*", "", line.strip())
            line = re.sub(r"^(?:[A-Z][A-Z0-9_]*=\S*\s+)+", "", line)
            line = re.sub(r"^python3?\s+-m\s+anomod\.cli\s+", "anomod ", line)
            if line.startswith("anomod "):
                yield re.split(r"\s+#|\s+\||\s+>|\s+&&", line)[0]


def unparsable_commands(text: str) -> list:
    from anomod.cli import build_parser
    parser = build_parser()
    known = set(parser._subparsers._group_actions[0].choices)
    out = [f"anomod {cmd}" for cmd in _INLINE.findall(text)
           if cmd not in known]
    for line in _command_lines(text):
        if any(m in line for m in ("…", "...")):
            continue
        try:
            parser.parse_args(shlex.split(line)[1:])
        except SystemExit:
            out.append(line)
    return sorted(set(out))


@functools.lru_cache(maxsize=None)
def _knobs_read() -> frozenset:
    names = set()
    for pattern in KNOB_SOURCES:
        for p in ROOT.glob(pattern):
            names.update(_KNOB.findall(p.read_text(errors="replace")))
    return frozenset(names)


def unread_knobs(text: str) -> list:
    read = _knobs_read()
    return sorted({k for k in _KNOB.findall(text)
                   if not k.endswith("_") and k not in read})


def _read(doc: str) -> str:
    return (ROOT / doc).read_text()


@pytest.mark.parametrize("doc", DOCS)
def test_every_path_a_document_names_exists(doc):
    assert missing_paths(_read(doc)) == []


@pytest.mark.parametrize("doc", DOCS)
def test_every_command_a_document_shows_parses(doc, capsys):
    assert unparsable_commands(_read(doc)) == []


@pytest.mark.parametrize("doc", DOCS)
def test_every_knob_a_document_names_is_read(doc):
    assert unread_knobs(_read(doc)) == []


DOCTORED = """
Gate a capture with `scripts/old_gate.py` (its records: `bench_runs/` and
`docs/SPEEDS.md`; `anomod/obs/timeline.py:12` records).

```bash
python old_bench.py --mode serve
ANOMOD_TIMELINE=1 anomod serve --timeline --duration 5   # the timeline
anomod timeline diff a.json b.json | tail -1
anomod census record --out c.json
```

`anomod timeline history` reads them; `ANOMOD_TIMELINE_NOISE` is the hedge,
`ANOMOD_SERVE_SHARDS` and the `ANOMOD_CENSUS_` family stay.
"""


@pytest.mark.parametrize("check, caught", [
    (missing_paths, ["anomod/obs/timeline.py", "docs/SPEEDS.md",
                     "old_bench.py", "scripts/old_gate.py"]),
    (unparsable_commands, ["anomod serve --timeline --duration 5",
                           "anomod timeline",
                           "anomod timeline diff a.json b.json"]),
    (unread_knobs, ["ANOMOD_TIMELINE", "ANOMOD_TIMELINE_NOISE"]),
], ids=["paths", "commands", "knobs"])
def test_a_doctored_document_is_caught(tmp_path, check, caught, capsys):
    """What this module is for, shown once: a copy of the README that
    names a file, a sub-command, a flag and a knob the tree does not have
    fails each check, and only by those."""
    doc = tmp_path / "README.md"
    doc.write_text(_read("README.md") + DOCTORED)
    assert check(doc.read_text()) == caught
