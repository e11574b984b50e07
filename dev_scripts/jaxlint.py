#!/usr/bin/env python3
"""jaxlint CLI — the JAX-aware static analysis gate.

Sits next to dev_scripts/lint.py in tests.sh's lint phase (one shared
file walk): lint.py keeps the tree tidy, jaxlint keeps it fast. Rules
(photon_ml_tpu/analysis/rules.py, catalog in docs/ANALYSIS.md):

  retrace-hazard            per-call recompilation patterns
  host-sync                 device->host syncs inside jit-reachable code
  dtype-drift               f32-parity-unsafe dtypes on device paths
  nondeterministic-pytree   set-ordered pytree leaves / cache keys

The gate is "no NEW violations": pre-existing accepted findings live in
dev_scripts/jaxlint_baseline.txt (fingerprints are line-number-free, so
the baseline survives unrelated edits). Inline escape hatch, on the
violating line:  # jaxlint: disable=<rule>[,<rule>...]

Usage:
    python dev_scripts/jaxlint.py [paths...]
    python dev_scripts/jaxlint.py --baseline-update   # regenerate baseline
    python dev_scripts/jaxlint.py --with-style        # + lint.py checks
    python dev_scripts/jaxlint.py --list-rules

Exit 0 = no new violations (and, with --with-style, no style problems).
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

from photon_ml_tpu import analysis  # noqa: E402

try:
    from dev_scripts import lint as style_lint
except ImportError:  # run as a script: dev_scripts/ itself is sys.path[0]
    import lint as style_lint

# jaxlint's default scope: the package + tooling. tests/ is style-checked
# (via --with-style) but exempt from jaxlint rules — tests legitimately
# jit per call and host-sync eagerly.
ANALYSIS_PATHS = ["photon_ml_tpu", "dev_scripts", "__graft_entry__.py"]
DEFAULT_BASELINE = REPO_ROOT / "dev_scripts" / "jaxlint_baseline.txt"


def _resolve(paths, root: Path, strict: bool = False):
    """Default paths that don't exist are skipped (a test's tree holds
    only the package); EXPLICIT paths that don't exist are an error — a typo'd
    path silently analyzing 0 files would pass the gate vacuously."""
    out = []
    for p in paths:
        q = Path(p)
        q = q if q.is_absolute() else root / q
        if not q.exists():
            if strict:
                raise SystemExit(f"jaxlint: path not found: {p}")
            continue
        out.append(q)
    return out


def run(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="jaxlint", description=__doc__)
    ap.add_argument("paths", nargs="*",
                    help=f"files/dirs to analyze (default: "
                         f"{' '.join(ANALYSIS_PATHS)})")
    ap.add_argument("--baseline", type=Path, default=DEFAULT_BASELINE)
    ap.add_argument("--root", type=Path, default=REPO_ROOT,
                    help="tree root for path-relative fingerprints and "
                         "default-path resolution (tests use tmp trees)")
    ap.add_argument("--baseline-update", action="store_true",
                    help="rewrite the baseline from current findings "
                         "(sorted, path-relative, deterministic)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every violation, ignoring the baseline")
    ap.add_argument("--with-style", action="store_true",
                    help="also run dev_scripts/lint.py checks over one "
                         "shared file walk (tests.sh's lint phase)")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)

    if args.list_rules:
        for rule in analysis.ALL_RULES:
            print(f"{rule.id}: {rule.doc}")
        return 0

    root = args.root.resolve()
    explicit = bool(args.paths)
    if args.baseline_update and explicit:
        print("jaxlint: --baseline-update regenerates the FULL baseline "
              "and must not be scoped to a path subset (accepted entries "
              "outside the subset would be silently dropped); run it "
              "without explicit paths")
        return 2
    jax_paths = _resolve(args.paths or ANALYSIS_PATHS, root,
                         strict=explicit)

    # ONE walk, ONE read per file; each tool consumes its subset
    # (lint.py takes the preloaded source via lint_file(..., src)).
    # Style-only paths (tests/, ...) join the walk only when style
    # checks actually run.
    if args.with_style:
        style_paths = jax_paths if explicit else _resolve(
            style_lint.DEFAULT_PATHS, root)
    else:
        style_paths = []
    all_files = analysis.iter_py_files(sorted(set(style_paths)
                                              | set(jax_paths)))
    sources = {f: f.read_text() for f in all_files}
    jax_roots = tuple(p.resolve() for p in jax_paths)
    jax_files = [f for f in all_files
                 if any(f.resolve() == r or r in f.resolve().parents
                        for r in jax_roots)]

    style_problems = []
    if args.with_style:
        style_set = {f.resolve() for f in analysis.iter_py_files(
            style_paths)}
        for f in all_files:
            if f.resolve() in style_set:
                style_problems += style_lint.lint_file(f, src=sources[f])
        for path, line, msg in style_problems:
            print(f"{path}:{line}: {msg}")

    modules = []
    for f in jax_files:
        try:
            rel = f.resolve().relative_to(root).as_posix()
        except ValueError:
            rel = f.as_posix()
        mod = analysis.core.parse_module(rel, sources[f])
        if mod is not None:
            modules.append(mod)
    violations = analysis.analyze_modules(modules)

    if args.baseline_update:
        analysis.write_baseline(args.baseline, violations)
        print(f"jaxlint: baseline updated — {len(violations)} accepted "
              f"finding(s) in {args.baseline.name}")
        return 0

    baseline = (analysis.load_baseline(args.baseline)
                if not args.no_baseline else None)
    if baseline is not None:
        new, stale = analysis.apply_baseline(violations, baseline)
    else:
        new, stale = list(violations), {}

    for v in new:
        print(v.render())
    if stale:
        print(f"jaxlint: note — {sum(stale.values())} stale baseline "
              "entry(ies) no longer match any finding; run "
              "--baseline-update to tidy:")
        for fp in sorted(stale):
            print(f"  stale: {fp}")
    print(f"jaxlint: {len(jax_files)} files, {len(violations)} finding(s),"
          f" {len(new)} new"
          + (f"; style: {len(style_problems)} problem(s)"
             if args.with_style else ""))
    return 1 if (new or style_problems) else 0


if __name__ == "__main__":
    sys.exit(run(sys.argv[1:]))
