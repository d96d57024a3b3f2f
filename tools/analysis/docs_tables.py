"""Shared markdown-table and code-literal extraction for the
registry-sync checkers.

This is the single home of the docs-table parsing that used to be
duplicated between ``tools/check_phase_docs.py`` and
``tools/check_event_docs.py`` (both are now thin shims over this
module): find the markdown table whose header row matches, take every
backticked name from its FIRST column.

The code-side extractors are regex over raw text rather than AST on
purpose — the emit/phase calls span lines freely and a regex with
``\\s*`` crossing newlines is exactly as precise here, at a fraction of
the cost (these run inside the tier-1 lint test).
"""
from __future__ import annotations

import re
from typing import Iterable, Set

# literal phase("name") — telemetry.recorder per-iteration phases
PHASE_CALL = re.compile(r"\bphase\(\s*[\"']([a-z0-9_]+)[\"']")
# literal *.emit("kind" ... — flight-recorder event kinds (the call may
# span lines; findall over whole-file text lets \s* cross newlines)
EMIT_CALL = re.compile(r"\.emit\(\s*[\"']([a-z0-9_]+)[\"']")
# literal counters.incr("name") / set_gauge / add_seconds on any
# receiver whose name ends in "counters" (counters., telem_counters.),
# and spans.stage("name", ...) — a set-up stage's seconds counter
COUNTER_CALL = re.compile(
    r"(?:counters\s*\.\s*(?:incr|set_gauge|add_seconds)"
    r"|spans\s*\.\s*stage)\(\s*"
    r"[\"']([a-z0-9_]+)[\"']")

# the fault grammar's verb registry: the _KNOWN tuple in
# resilience/faults.py (single source of truth for accepted verbs)
FAULT_VERB_TUPLE = re.compile(r"_KNOWN\s*=\s*\(([^)]*)\)")

# emitted via events.iteration_record(), not a literal emit() call
EVENT_EXEMPT = {"iteration"}
# gauges injected by counters.snapshot() itself rather than a literal
# set_gauge call — still part of the documented surface
COUNTER_IMPLICIT = {"peak_rss_bytes"}


def code_literals(texts: Iterable[str], pattern: re.Pattern) -> Set[str]:
    names: Set[str] = set()
    for text in texts:
        names.update(pattern.findall(text))
    return names


def doc_first_column(doc_text: str, header_pattern: str) -> Set[str]:
    """Backticked names from the first column of the markdown table
    whose header row matches ``header_pattern`` (a regex applied to the
    stripped line). The table ends at the first non-``|`` line."""
    names: Set[str] = set()
    header = re.compile(header_pattern)
    in_table = False
    for line in doc_text.splitlines():
        stripped = line.strip()
        if header.match(stripped):
            in_table = True
            continue
        if in_table:
            if not stripped.startswith("|"):
                break
            first_col = stripped.split("|")[1]
            names.update(re.findall(r"`([a-z0-9_]+)`", first_col))
    return names


def fault_verbs(faults_text: str) -> Set[str]:
    """Verb names out of the ``_KNOWN = (...)`` tuple in
    resilience/faults.py."""
    m = FAULT_VERB_TUPLE.search(faults_text)
    if not m:
        return set()
    return set(re.findall(r"[\"']([a-z0-9_]+)[\"']", m.group(1)))


PHASE_HEADER = r"^\|\s*Phase\s*\|\s*Where\s*\|"
EVENT_HEADER = r"^\|\s*kind\s*\|\s*emitted by\s*\|"
COUNTER_HEADER = r"^\|\s*counter / gauge\s*\|\s*meaning\s*\|"
FAULT_VERB_HEADER = r"^\|\s*verb\s*\|\s*effect\s*\|"
