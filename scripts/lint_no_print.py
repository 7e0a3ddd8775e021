#!/usr/bin/env python
"""Lint: no bare ``print()`` inside the package outside the stdout sink.

Every human-facing line the framework emits must flow through
``observe.sinks.StdoutSink`` so the console and the structured JSONL log
can never drift apart. This walks the package AST and fails (exit 1) on
any other ``print`` call site.

The default run also lints ``scripts/``: new tooling there must write
human lines to stderr (``print(..., file=sys.stderr)`` is permitted) and
machine output via ``sys.stdout.write`` so piped JSON stays clean. A few
legacy stdout-printing scripts are grandfathered in ``SCRIPT_ALLOWED``.

It also enforces the observability clock discipline: ``time.time()``
inside ``observe/`` is flagged except at the two sanctioned wall-clock
sites (``MONO_ALLOWED``). Span and step durations must come from
``time.monotonic()`` — the wall clock steps under NTP slew, and a span
whose duration went negative once poisons every share/idle figure
downstream. Wall-clock belongs only where events are *stamped* for
cross-rank joining.

Usage::

    python scripts/lint_no_print.py            # lint package + scripts/
    python scripts/lint_no_print.py path [..]  # lint specific trees
"""

from __future__ import annotations

import ast
import os
import sys

# the one sanctioned print site (see observe/sinks.py docstring)
ALLOWED = {os.path.join("observe", "sinks.py")}

# legacy scripts that print reports/artifacts straight to stdout; new
# scripts must not join this list (stderr for humans, stdout for JSON)
SCRIPT_ALLOWED = {
    "accuracy_study.py",
    "bandwidth_artifact.py",
}

# the sanctioned wall-clock call sites inside observe/ (everything else
# there must use time.monotonic() for durations):
# - telemetry.py: Telemetry.emit stamps ``ts`` — the cross-rank join key
#   the runlog merger aligns shards by, which MUST be wall clock
# - runlog.py: the manifest's ``created_unix`` provenance stamp
# Every other observe/ module is covered by the path rule below with NO
# carve-out — observe/memory.py in particular is deliberately clock-free
# (MemoryEvents are stamped by Telemetry.emit like everything else, and
# the sampler keys off step indices, not timers), so adding a timer there
# fails this lint by design. observe/fidelity.py is held to the same
# bar: fidelity stats are keyed by step index and joined to the wire
# ledger by tag, never by timestamp, so it earns no entry here either.
MONO_ALLOWED = {"telemetry.py", "runlog.py"}

# function-scoped allowances: files covered by the clock lint where ONE
# named function may stamp wall clock. live.py's Prometheus exposition
# formatter publishes ``live_scrape_unix_time`` (a wall-clock gauge by
# definition); everything else in live.py/health.py — windows, detectors,
# follower pacing — must be monotonic or clock-free.
MONO_FUNC_ALLOWED = {"live.py": {"render_prometheus"}}

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "network_distributed_pytorch_tpu")
SCRIPTS = os.path.join(REPO, "scripts")


def _is_stderr_print(node: ast.Call) -> bool:
    """True for ``print(..., file=sys.stderr)`` — stderr chatter is fine."""
    for kw in node.keywords:
        if (
            kw.arg == "file"
            and isinstance(kw.value, ast.Attribute)
            and kw.value.attr == "stderr"
        ):
            return True
    return False


def _parse(path: str) -> ast.AST:
    with open(path, "rb") as f:
        return ast.parse(f.read(), filename=path)


def print_calls(path: str, permit_stderr: bool = False):
    for node in ast.walk(_parse(path)):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "print"
        ):
            if permit_stderr and _is_stderr_print(node):
                continue
            yield node.lineno


def wallclock_calls(path: str, allowed_funcs=frozenset()):
    """Line numbers of ``time.time()`` calls (the attribute form only —
    a ``from time import time`` alias would dodge this, and observe/
    deliberately never imports it that way). Calls lexically inside a
    function named in ``allowed_funcs`` are sanctioned (the
    ``MONO_FUNC_ALLOWED`` exposition-formatter carve-out)."""

    def _walk(node, inside_allowed):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            inside_allowed = inside_allowed or node.name in allowed_funcs
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "time"
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id == "time"
            and not inside_allowed
        ):
            yield node.lineno
        for child in ast.iter_child_nodes(node):
            yield from _walk(child, inside_allowed)

    yield from _walk(_parse(path), False)


def lint_tree(root: str, allowed, permit_stderr: bool = False):
    violations = []
    for dirpath, _dirnames, filenames in os.walk(root):
        for fname in sorted(filenames):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            rel = os.path.relpath(path, root)
            if rel not in allowed:
                for lineno in print_calls(path, permit_stderr=permit_stderr):
                    violations.append(f"{path}:{lineno} bare print()")
            # clock discipline applies to observe/ wherever the lint was
            # rooted (package walk or an explicit path argument)
            if "observe" in path.split(os.sep) and fname not in MONO_ALLOWED:
                funcs = MONO_FUNC_ALLOWED.get(fname, frozenset())
                for lineno in wallclock_calls(path, allowed_funcs=funcs):
                    violations.append(
                        f"{path}:{lineno} time.time() in observe/ "
                        "(use time.monotonic() for durations)"
                    )
    return violations


def lint(roots) -> int:
    if roots:
        violations = []
        for root in roots:
            violations.extend(lint_tree(root, ALLOWED))
    else:
        violations = lint_tree(PACKAGE, ALLOWED)
        violations.extend(
            lint_tree(SCRIPTS, SCRIPT_ALLOWED, permit_stderr=True)
        )
    if violations:
        sys.stderr.write(
            "lint violations (bare print() must route through an observe "
            "event/sink or sys.stderr in scripts/; observe/ durations must "
            "use time.monotonic()):\n"
        )
        for v in violations:
            sys.stderr.write(f"  {v}\n")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(lint(sys.argv[1:]))
