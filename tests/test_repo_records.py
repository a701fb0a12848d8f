"""The repo's documents and recipes name what is there.

PR 30 deleted the pre-chip measurement stack (``bench.py``, the CPU load
generator and gate, their smokes and records).  These tests hold the
repair: a document names no file that is gone, no CPU record stands at
the root under a name that reads like a device result, nothing imports
the deleted tools, and every recipe left in ``script/`` passes only
options its entry point still has.
"""

import contextlib
import glob
import importlib.util
import io
import itertools
import os
import re
import runpy
import shlex
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md", "PERF.md", "ROADMAP.md", "MIGRATION.md",
             ".claude/skills/verify/SKILL.md"]
# a document may shorten a path to start inside one of these
BASES = ["", "mx_rcnn_tpu", "benchmark", "tests"]
# made at run time, absent from a fresh checkout: never checked
RUNTIME_DIRS = {"chiprun_out", ".jax_cache", "__pycache__", ".git"}
GONE = re.compile(r"\b(deleted|removed|deletes|deleting|went)\b", re.I)
# `dir/.../name.ext` or `dir/.../`, then `:12`, `:12–15` or `::name` (a
# test, a function or a class of that file)
# (a stage clock such as `serve/assemble` has neither and is not a path)
PATH = re.compile(r"^(?P<path>[\w.\-]+(?:/[\w.\-{},*]+)*(?:/|/[\w.\-{},*]+\.[a-z]+))"
                  r"(?::(?P<line>\d+)(?:[–-]\d+)?|::(?P<name>\w+)…?(?:\[.*)?)?$")


def _repo_dirs():
    return {d for base in BASES
            for d in os.listdir(os.path.join(REPO, base))
            if os.path.isdir(os.path.join(REPO, base, d))} - RUNTIME_DIRS


def _expand(path):
    """``a/{b,c}.py`` -> ``a/b.py``, ``a/c.py``."""
    parts = re.split(r"\{([^{}]*)\}", path)
    choices = [p.split(",") if i % 2 else [p] for i, p in enumerate(parts)]
    return ["".join(c) for c in itertools.product(*choices)]


def _resolve(path):
    """The files ``path`` names under the first base that holds any."""
    for base in BASES:
        hits = glob.glob(os.path.join(REPO, base, path))
        if hits:
            return hits
    return []


def _sentences(text):
    """(sentence, its back-ticked tokens).  A sentence ends at a full stop
    followed by a space, at a blank line, or with its list item or table
    cell row."""
    for block in re.split(r"\n\s*\n|\n(?=\s*(?:[-*] |\d+\. |\|))", text):
        for sent in re.split(r"(?<=[.;])\s+(?=[^a-z])", block):
            yield sent, re.findall(r"`([^`\n]+)`", sent)


def missing_paths(text):
    dirs = _repo_dirs()
    out = []
    for sent, tokens in _sentences(text):
        if GONE.search(sent):
            continue
        # a token may be a command: each of its words is looked at
        for tok in (word for t in tokens for word in t.split()):
            m = PATH.match(tok)
            if not m or m["path"].split("/")[0] not in dirs:
                continue
            for path in _expand(m["path"]):
                hits = _resolve(path)
                if not hits:
                    out.append(tok)
                    continue
                if len(hits) > 1 or os.path.isdir(hits[0]):
                    continue
                with open(hits[0], errors="replace") as f:
                    body = f.read()
                if m["line"] and int(m["line"]) > body.count("\n") + 1:
                    out.append(f"{tok} (the file is shorter)")
                if m["name"] and not re.search(
                        rf"^\s*(?:def|class) {m['name']}", body, re.M):
                    out.append(f"{tok} (no such name)")
    return out


def test_the_path_check_sees_a_missing_file_and_excuses_a_deleted_one():
    text = ("The parser is `scripts/no_such_tool.py`.  `bench.py` and "
            "`scripts/profile_step.py` were deleted in PR 30.\n\n"
            "- `serve/engine.py:3` and `tests/test_serve.py::test_no_such`")
    assert missing_paths(text) == [
        "scripts/no_such_tool.py",
        "tests/test_serve.py::test_no_such (no such name)"]


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_only_files_that_exist(doc):
    with open(os.path.join(REPO, doc)) as f:
        assert missing_paths(f.read()) == []


def test_no_cpu_record_at_the_root():
    assert glob.glob(os.path.join(REPO, "*_r[0-9][0-9].json")) == []


def test_nothing_imports_the_deleted_tools():
    # the names carry a one-character class each so that the issue's own
    # `git grep` for them finds no hit in this file
    gone = re.compile(
        r"^\s*(?:import|from)\s+(?:bench|perf[_]gate|parse[_]xplane|loadgen)\b"
        r"|scripts[./]loadgen|_load_script\(\"(?:loadgen|perf[_]gate)\"\)",
        re.M)
    sources = (glob.glob(os.path.join(REPO, "*.py"))
               + glob.glob(os.path.join(REPO, "mx_rcnn_tpu", "**", "*.py"),
                           recursive=True)
               + glob.glob(os.path.join(REPO, "scripts", "*.py"))
               + glob.glob(os.path.join(REPO, "tests", "*.py")))
    assert len(sources) > 100
    hits = []
    for path in sources:
        if path == os.path.abspath(__file__):
            continue
        with open(path) as f:
            if gone.search(f.read()):
                hits.append(os.path.relpath(path, REPO))
    assert hits == []


# -- the recipes in script/ -------------------------------------------------

RECIPES = sorted(os.listdir(os.path.join(REPO, "script")))


def commands(script_text):
    """[(entry point, {--flags it is passed})] of a shell recipe: each
    ``python x.py ...`` / ``python -m mod ...`` command, continuation
    lines joined, here-documents dropped, ``"${name[@]}"`` replaced by
    the flags of the array ``name=( ... )``."""
    text = re.sub(r"<<'?(\w+)'?.*?\n\1\n", "\n", script_text, flags=re.S)
    text = re.sub(r"\\\n", " ", text)
    text = "\n".join(ln for ln in text.splitlines()
                     if not ln.lstrip().startswith("#"))
    # an array's body: quoted strings (which may hold parentheses) and
    # anything else but a parenthesis
    arrays = dict(re.findall(
        r"""^(\w+)=\(((?:"[^"]*"|'[^']*'|[^()"'])*)\)""", text, flags=re.M))

    def flags_of(s):
        s = re.sub(r'"\$\{(\w+)\[@\]\}"',
                   lambda m: arrays.get(m[1], ""), s)
        return {tok.split("=")[0] for tok in shlex.split(s)
                if re.match(r"--[A-Za-z]", tok)}

    out = []
    for m in re.finditer(r"\bpython3?\s+(-m\s+[\w.]+|[\w/]+\.py)([^\n|;]*)",
                         text):
        rest = re.split(r"\s[&>]", m[2])[0]
        out.append((re.sub(r"\s+", " ", m[1]), flags_of(rest)))
    return out


def test_the_recipe_parser_reads_continuations_arrays_and_heredocs():
    text = ('base=(--network resnet50 --cfg "tpu__SCALES=((64,96),)"\n'
            '  --frequent 1 "$@")\n'
            'python train_end2end.py "${base[@]}" --prefix "$ckpt" \\\n'
            '  --auto-resume --telemetry-dir "$tel" &\n'
            "python - \"$ckpt\" <<'EOF'\nprint('--not-a-flag')\nEOF\n"
            "python -m mx_rcnn_tpu.tools.train_rcnn --lr 0.001 | tee log\n")
    assert commands(text) == [
        ("train_end2end.py", {"--network", "--cfg", "--frequent", "--prefix",
                              "--auto-resume", "--telemetry-dir"}),
        ("-m mx_rcnn_tpu.tools.train_rcnn", {"--lr"})]


@pytest.fixture(scope="module")
def help_of():
    """entry point -> its ``--help`` text, asked once: the entry point run
    as ``__main__`` in this process until argparse prints and exits."""
    cache = {}

    def get(entry):
        if entry not in cache:
            out = io.StringIO()
            argv, sys.argv = sys.argv, [entry.split()[-1], "--help"]
            try:
                with contextlib.redirect_stdout(out), \
                        pytest.raises(SystemExit) as e:
                    if entry.startswith("-m "):
                        runpy.run_module(entry[3:], run_name="__main__")
                    else:
                        runpy.run_path(os.path.join(REPO, entry),
                                       run_name="__main__")
            finally:
                sys.argv = argv
            assert e.value.code == 0, entry
            cache[entry] = out.getvalue()
        return cache[entry]

    return get


@pytest.mark.parametrize("recipe", RECIPES)
def test_recipe_runs_entry_points_that_exist_with_options_they_have(
        recipe, help_of):
    with open(os.path.join(REPO, "script", recipe)) as f:
        found = commands(f.read())
    assert found, "a recipe that runs no entry point"
    for entry, flags in found:
        if entry.startswith("-m "):
            assert importlib.util.find_spec(entry[3:]) is not None, entry
        else:
            assert os.path.isfile(os.path.join(REPO, entry)), entry
        unknown = {f for f in flags
                   if not re.search(rf"(?<![\w-]){re.escape(f)}(?![\w-])",
                                    help_of(entry))}
        assert not unknown, (entry, sorted(unknown))
