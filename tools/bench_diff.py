#!/usr/bin/env python
"""Bench regression sentinel: diff two ``BENCH_r*.json`` rounds.

The r05 MoE regression (0.92x) sat unnoticed for two bench rounds
because nothing diffs consecutive ``BENCH_r*.json`` files — a human has
to remember last round's numbers. This tool is that diff:

    python tools/bench_diff.py                  # two latest rounds in .
    python tools/bench_diff.py --dir /path      # ... in /path
    python tools/bench_diff.py rounds/BENCH_r04.json rounds/BENCH_r05.json

Per-metric relative delta against a configurable noise band (default
±3%); any regression beyond the band prints a human table and exits
nonzero — wire it after ``bench.py`` in CI and the next 0.92x pages
someone at the round it lands, not two rounds later.

Failed rounds are first-class: a round whose ``parsed`` block is empty
(the bench crashed, e.g. r04's RESOURCE_EXHAUSTED) cannot anchor a
diff, so the OLD side walks back to the newest earlier round that has
metrics (noted in the output). A NEW side without metrics is itself
reported as a regression — a bench that stopped producing numbers is
the worst kind of slowdown.

``--check ROUND.json`` is the CI arming of the sentinel: validate ONE
named round against the newest earlier usable round in its directory.
A round file that does not exist yet exits 0 ("pending") — so a tier-1
test can commit ``--check BENCH_r06.json`` today and the check arms
itself the moment that round lands; a landed round that regressed then
fails the suite at the round it happens, not two rounds later::

    python tools/bench_diff.py --check BENCH_r06.json

Exit codes: 0 ok (within band / pending / first round), 1 regression
(or unusable new round), 2 usage/IO error.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

_ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")


def round_number(path: str) -> Optional[int]:
    m = _ROUND_RE.search(os.path.basename(path))
    return int(m.group(1)) if m else None


def load_round(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def metric_rows(doc: Dict) -> Dict[str, Dict]:
    """``{metric_name: row}`` of one round's usable rows. Rows that are
    failure markers (``*_failed`` placeholders, non-positive values)
    carry no comparable number and are skipped."""
    parsed = doc.get("parsed") or {}
    rows = parsed.get("metrics")
    if rows is None:
        rows = [parsed] if parsed.get("metric") else []
    out = {}
    for row in rows:
        name = row.get("metric")
        try:
            value = float(row.get("value"))
        except (TypeError, ValueError):
            continue
        if not name or name.endswith("_failed") or value <= 0:
            continue
        out[name] = row
    return out


def find_rounds(directory: str) -> List[str]:
    """BENCH_r*.json in ``directory``, round-ordered."""
    paths = [p for p in glob.glob(os.path.join(directory, "BENCH_r*.json"))
             if round_number(p) is not None]
    return sorted(paths, key=round_number)


def newest_earlier_usable(path: str) -> Tuple[Optional[str], Dict[str, Dict]]:
    """The newest round in ``path``'s directory with a LOWER round
    number and usable metrics — the shared walk-back behind the failed-
    round anchoring and ``--check``. Unreadable candidate rounds are
    skipped (one corrupt old file must not kill the sentinel)."""
    n = round_number(path)
    if n is None:
        return None, {}
    for prev in reversed(find_rounds(os.path.dirname(path) or ".")):
        pn = round_number(prev)
        if pn is None or pn >= n:
            continue
        try:
            rows = metric_rows(load_round(prev))
        except (OSError, json.JSONDecodeError):
            continue
        if rows:
            return prev, rows
    return None, {}


def resolve_old(old_path: str, notes: List[str]) -> Tuple[str, Dict[str, Dict]]:
    """The old anchor: ``old_path`` itself when it has metrics, else the
    newest EARLIER round in the same directory that does (a failed round
    cannot anchor a diff — exactly the r04 case)."""
    doc = load_round(old_path)
    rows = metric_rows(doc)
    if rows:
        return old_path, rows
    notes.append(
        f"note: {os.path.basename(old_path)} has no parsed metrics "
        f"(rc={doc.get('rc')}) — walking back to an earlier round")
    prev, rows = newest_earlier_usable(old_path)
    if prev is not None:
        notes.append(f"note: baseline round = {os.path.basename(prev)}")
        return prev, rows
    return old_path, {}


def diff_rows(old_rows: Dict[str, Dict], new_rows: Dict[str, Dict],
              band: float) -> List[Dict]:
    """One entry per metric in either round: relative delta + status
    (``ok`` / ``regressed`` / ``improved`` / ``added`` / ``removed``)."""
    out = []
    for name in sorted(set(old_rows) | set(new_rows)):
        o, n = old_rows.get(name), new_rows.get(name)
        if o is None:
            out.append({"metric": name, "old": None,
                        "new": float(n["value"]), "delta": None,
                        "status": "added"})
            continue
        if n is None:
            # a metric that stopped reporting is flagged, not failed:
            # rounds legitimately rename rows (r04 serving rows split
            # into bf16/int8 variants at r05)
            out.append({"metric": name, "old": float(o["value"]),
                        "new": None, "delta": None, "status": "removed"})
            continue
        ov, nv = float(o["value"]), float(n["value"])
        delta = nv / ov - 1.0
        status = "ok"
        if delta < -band:
            status = "regressed"
        elif delta > band:
            status = "improved"
        out.append({"metric": name, "old": ov, "new": nv,
                    "delta": delta, "status": status})
    return out


def render_table(entries: List[Dict], old_name: str, new_name: str,
                 band: float, out=sys.stdout) -> None:
    w = max([len(e["metric"]) for e in entries] + [len("metric")])
    out.write(f"bench diff: {old_name} -> {new_name} "
              f"(noise band ±{band:.1%})\n")
    out.write(f"{'metric':{w}}  {'old':>12}  {'new':>12}  "
              f"{'delta':>8}  status\n")
    out.write("-" * (w + 48) + "\n")
    for e in entries:
        old = f"{e['old']:.1f}" if e["old"] is not None else "-"
        new = f"{e['new']:.1f}" if e["new"] is not None else "-"
        delta = f"{e['delta']:+.1%}" if e["delta"] is not None else "-"
        mark = " <-- REGRESSION" if e["status"] == "regressed" else ""
        out.write(f"{e['metric']:{w}}  {old:>12}  {new:>12}  "
                  f"{delta:>8}  {e['status']}{mark}\n")


def check_round(path: str, band: float) -> int:
    """``--check``: validate one round against its newest earlier usable
    round. Missing file = pending (0); no earlier usable round = first
    round (0); regression beyond the band = 1."""
    name = os.path.basename(path)
    if round_number(path) is None:
        # a misnamed target would stay 'pending' forever — a sentinel
        # that can never arm is a config error, not a pass
        print(f"bench_diff: --check target {name!r} does not match "
              "BENCH_r<N>.json", file=sys.stderr)
        return 2
    if not os.path.exists(path):
        print(f"check: {name} not produced yet — pending (the check "
              "arms itself when the round lands)")
        return 0
    try:
        new_doc = load_round(path)
    except (OSError, json.JSONDecodeError) as e:
        print(f"bench_diff: {e}", file=sys.stderr)
        return 2
    new_rows = metric_rows(new_doc)
    if not new_rows:
        print(f"REGRESSION: {name} has no parsed metrics "
              f"(rc={new_doc.get('rc')}) — the bench itself failed")
        return 1
    old_path, old_rows = newest_earlier_usable(path)
    if not old_rows:
        print(f"check: {name} is the first usable round under "
              f"{os.path.dirname(path) or '.'!r} — nothing to diff")
        return 0
    entries = diff_rows(old_rows, new_rows, band)
    render_table(entries, os.path.basename(old_path), name, band)
    regressed = [e for e in entries if e["status"] == "regressed"]
    if regressed:
        names = ", ".join(e["metric"] for e in regressed)
        print(f"\nREGRESSION: {len(regressed)} metric(s) beyond the "
              f"-{band:.1%} band: {names}")
        return 1
    print("\nok: no regression beyond the noise band")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="diff two bench rounds; nonzero exit on regression")
    ap.add_argument("old", nargs="?", default=None,
                    help="old round JSON (default: second-latest in --dir)")
    ap.add_argument("new", nargs="?", default=None,
                    help="new round JSON (default: latest in --dir)")
    ap.add_argument("--dir", default=".",
                    help="directory scanned for BENCH_r*.json (auto mode)")
    ap.add_argument("--band", type=float, default=3.0,
                    help="noise band in percent (default 3.0): deltas "
                         "inside ±band%% are ok")
    ap.add_argument("--check", default=None, metavar="ROUND.json",
                    help="validate ONE round against the newest earlier "
                         "usable round in its directory; a round not "
                         "produced yet is 'pending' (exit 0) — the "
                         "tier-1 sentinel mode")
    args = ap.parse_args(argv)
    band = args.band / 100.0

    if args.check is not None:
        if args.old is not None or args.new is not None:
            ap.error("--check takes no positional rounds")
        return check_round(args.check, band)

    if (args.old is None) != (args.new is None):
        ap.error("pass both OLD and NEW, or neither (auto mode)")
    if args.old is None:
        rounds = find_rounds(args.dir)
        if len(rounds) < 2:
            print(f"bench_diff: need >= 2 BENCH_r*.json under "
                  f"{args.dir!r}, found {len(rounds)}", file=sys.stderr)
            return 2
        args.old, args.new = rounds[-2], rounds[-1]

    notes: List[str] = []
    try:
        old_path, old_rows = resolve_old(args.old, notes)
        new_doc = load_round(args.new)
    except (OSError, json.JSONDecodeError) as e:
        print(f"bench_diff: {e}", file=sys.stderr)
        return 2
    new_rows = metric_rows(new_doc)

    for note in notes:
        print(note)
    if not new_rows:
        print(f"REGRESSION: {os.path.basename(args.new)} has no parsed "
              f"metrics (rc={new_doc.get('rc')}) — the bench itself "
              "failed")
        return 1
    if not old_rows:
        print(f"bench_diff: no usable baseline round for "
              f"{os.path.basename(args.old)}", file=sys.stderr)
        return 2

    entries = diff_rows(old_rows, new_rows, band)
    render_table(entries, os.path.basename(old_path),
                 os.path.basename(args.new), band)
    regressed = [e for e in entries if e["status"] == "regressed"]
    if regressed:
        names = ", ".join(e["metric"] for e in regressed)
        print(f"\nREGRESSION: {len(regressed)} metric(s) beyond the "
              f"-{band:.1%} band: {names}")
        return 1
    print("\nok: no regression beyond the noise band")
    return 0


if __name__ == "__main__":
    sys.exit(main())
