#!/usr/bin/env python
"""HBM bytes-per-image regression gate for the training step.

Compares a bench.py JSON record against the checked-in budget
(docs/bytes_budget.json) and exits nonzero when
``xla_bytes_accessed_per_image`` (or any budgeted breakdown category)
regresses more than the budget's tolerance on this device kind.

Usage:
    python bench.py | python scripts/check_bytes_budget.py -
    python scripts/check_bytes_budget.py tests/fixtures/bench/BENCH_r05.json
    python bench.py --enforce-budget          # same gate, in-process

Budget file semantics (docs/bytes_budget.json):

- ``budgets`` maps a device-kind substring (matched case-insensitively
  against the record's ``device_kind``) to its accepted measurement:
  ``xla_bytes_accessed_per_image`` (bytes) and optionally
  ``breakdown`` ({category: bytes} from ``bytes_per_image_breakdown``;
  keys starting with ``_`` are annotations, not categories). Budgeted
  categories make a regression ATTRIBUTABLE, not just detectable —
  the verdict names the category that moved.
- The gate FAILS when measured > budget * (1 + tolerance_pct/100).
  The budget is the ACCEPTED bytes number for the CURRENT tree, not
  an aspiration: a PR that improves bytes/image ratchets the budget
  down (and bumps the entry's ``as_of_round``) in the same change.
  ``as_of_round`` is metadata for the artifact-drift test in
  tests/test_hbm_bytes.py (BENCH_rN measures the tree after PR N-1,
  so only artifacts with N > as_of_round are gated against this
  entry); this script gates whatever record it is handed.
- A device kind with no budget entry passes with a note (the CPU
  backend's fusion behavior is not byte-comparable to TPU's, so no
  CPU budget is checked in). A budgeted category missing from the
  record's breakdown (or a record with no breakdown at all) passes
  with a note — the gate catches regressions, not plumbing gaps.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict, List, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BUDGET = os.path.join(REPO, "docs", "bytes_budget.json")

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _gate_cli import find_budget, load_record_argv  # noqa: E402


def load_budget(path: str = DEFAULT_BUDGET) -> Dict:
    with open(path) as fp:
        return json.load(fp)


def check_record(record: Dict, budget: Dict) -> Tuple[bool, List[str]]:
    """-> (ok, messages). ok is False only on a real regression; a
    missing budget entry or missing measurement passes with a note
    (a broken measurement already shows as null in the bench JSON —
    the gate's job is catching byte REGRESSIONS, not re-checking the
    bench's plumbing)."""
    tol = float(budget.get("tolerance_pct", 5.0)) / 100.0
    key, entry = find_budget(budget.get("budgets", {}),
                             record.get("device_kind", ""))
    if entry is None:
        return True, [f"no bytes budget for device kind "
                      f"{record.get('device_kind')!r}; nothing to enforce"]
    msgs, ok = [], True

    def gate(name: str, measured, budgeted) -> None:
        nonlocal ok
        if budgeted is None:
            return
        if measured is None:
            msgs.append(f"{name}: no measurement in record (budget "
                        f"{budgeted:.0f}); skipping")
            return
        limit = budgeted * (1.0 + tol)
        verdict = "OK" if measured <= limit else "REGRESSION"
        msgs.append(
            f"{name}: measured {measured / 1e6:.1f} MB vs budget "
            f"{budgeted / 1e6:.1f} MB (+{100 * tol:.0f}% tolerance -> "
            f"limit {limit / 1e6:.1f} MB) [{verdict}]")
        if measured > limit:
            ok = False

    gate(f"{key}: xla_bytes_accessed_per_image",
         record.get("xla_bytes_accessed_per_image"),
         entry.get("xla_bytes_accessed_per_image"))
    bd = record.get("bytes_per_image_breakdown") or {}
    cats = {cat: budgeted
            for cat, budgeted in (entry.get("breakdown") or {}).items()
            if not cat.startswith("_")}   # "_"-keys are annotations
    if cats and not bd:
        msgs.append(f"{key}: record carries no bytes_per_image_breakdown; "
                    f"skipping {len(cats)} category budgets")
    else:
        for cat, budgeted in cats.items():
            gate(f"{key}: breakdown[{cat}]", bd.get(cat), budgeted)
    return ok, msgs


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        return 0
    loaded = load_record_argv(argv, DEFAULT_BUDGET)
    if isinstance(loaded, int):
        return loaded
    record, budget_path = loaded
    ok, msgs = check_record(record, load_budget(budget_path))
    for m in msgs:
        print(m)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
