"""One judge (PR 45): a change is judged by the benchmark of
``BENCHMARK.json`` on the chip and by ONE pytest command on the CPU.  What
judged a change before them was deleted whole; these cases keep it out:
no record file of the old scoreboard comes back, no file of the tree
points an operator at a command or a module that is gone, and the
command line that remains still runs."""

import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# what a session writes beside the tree, and the documents that ARE the
# history (they name what went, by design)
_SKIP_DIRS = {".git", ".checkout", ".scratch", ".jax_cache", "chiprun_out",
              ".chipbench_trace", ".pytest_cache", ".hypothesis",
              "__pycache__", "_build"}
_HISTORY = {"CHANGES.md", "PERF.md", "ROADMAP.md", "SURVEY.md", "ISSUE.md"}

# the names are spelled in pieces so that this file passes its own case
_GONE = {
    "the scoreboard script": r"(?<![\w/.])" + "bench" + r"\.py\b",
    "the test script": "tools/" + "tier1" + r"\.sh",
    "the artifact reader": "bench" + "_history",
    "a self-test command": "-self" + "test",
}


def _tree_files():
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in _SKIP_DIRS]
        for name in files:
            if name.endswith((".py", ".md", ".sh", ".toml")):
                yield os.path.join(root, name)


def test_no_scoreboard_record_at_the_root():
    stale = [n for n in os.listdir(REPO)
             if re.fullmatch(r"(BENCH|MULTICHIP)_.*\.json", n)]
    assert stale == []


def test_nothing_names_what_was_deleted():
    hits = []
    for path in _tree_files():
        rel = os.path.relpath(path, REPO)
        if rel in _HISTORY:
            continue
        with open(path, encoding="utf-8", errors="replace") as fh:
            for lineno, line in enumerate(fh, 1):
                for what, pattern in _GONE.items():
                    if re.search(pattern, line):
                        hits.append(f"{rel}:{lineno}: {what}: "
                                    f"{line.strip()[:80]}")
    assert hits == [], "\n".join(hits[:20])


def test_no_test_module_imports_a_test_module():
    """A family's tiny model is an entry of ``tests/tiny.py`` and what
    test files share lives in a helper module (``tiny``, ``op_test``,
    ``kernel_cases``): an importer builds the imported file's fixtures
    again in another worker and knows its sizes by name (PR 59)."""
    tests = os.path.join(REPO, "tests")
    hits = []
    for name in sorted(os.listdir(tests)):
        if not re.fullmatch(r"test_.*\.py", name):
            continue
        with open(os.path.join(tests, name), encoding="utf-8") as fh:
            hits += [f"tests/{name}:{lineno}: {line.strip()}"
                     for lineno, line in enumerate(fh, 1)
                     if re.match(r"(import|from) test_", line)]
    assert hits == [], "\n".join(hits)


def test_the_command_line_that_remains_runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.run([sys.executable, "-m", "paddle_tpu", "version"],
                          env=env, cwd=REPO, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.startswith("paddle_tpu ")
