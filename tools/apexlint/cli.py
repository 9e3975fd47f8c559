"""CLI: ``python -m tools.apexlint [--json] [--rule APXnnn]``.

Exit codes follow the checker convention (tools/check_bench_labels.py):
0 clean, 1 findings, 2 crash-as-finding — a linter that dies must
surface as a loud failure, never a silent pass.
"""

import argparse
import json
import os
import sys


def main(argv=None):
    from tools.apexlint.core import run

    ap = argparse.ArgumentParser(
        prog="python -m tools.apexlint",
        description="AST-level invariant checker for the repo's own "
                    "rules (APX001-APX006; see tools/apexlint).")
    ap.add_argument("--root", default=None,
                    help="repo root (default: the tree this tool "
                         "lives in)")
    ap.add_argument("--rule", action="append", metavar="APXnnn",
                    help="run only these rules (repeatable)")
    ap.add_argument("--reference", default=None,
                    help="reference tree for APX005 (default "
                         "/root/reference; absent = rule skipped)")
    ap.add_argument("--json", action="store_true",
                    help="one machine-readable line (findings per "
                         "rule, pragma account) for window_report/CI "
                         "trending")
    ap.add_argument("--verbose", action="store_true",
                    help="also list every pragma with its hit count")
    args = ap.parse_args(argv)

    from tools.apexlint.rules import RULES

    unknown = sorted(set(args.rule or ()) - set(RULES) - {"APX000"})
    if unknown:
        # an explicit request names rules that exist — a typo'd filter
        # must not select zero rules and report a green gate
        ap.error(f"unknown rule id(s): {' '.join(unknown)} "
                 f"(known: APX000 {' '.join(sorted(RULES))})")

    root = args.root or os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    report = run(root, rules=args.rule, reference_root=args.reference)
    if args.json:
        print(json.dumps(report.as_json(), sort_keys=True))
    else:
        print(report.render(verbose=args.verbose))
    return 0 if report.ok else 1


def cli():
    try:
        sys.exit(main())
    except SystemExit:
        raise
    except Exception as e:  # crash-as-finding: rc 2, message, no
        # traceback — tier-1 sees a loud structured failure either
        # way. Under --json the stdout contract stays
        # one parseable line; otherwise the crash goes to stderr.
        msg = f"CRASH: apexlint error: {type(e).__name__}: {e}"
        if "--json" in sys.argv[1:]:
            print(json.dumps({"ok": False, "crash": msg}))
        else:
            print(msg, file=sys.stderr)
        sys.exit(2)
