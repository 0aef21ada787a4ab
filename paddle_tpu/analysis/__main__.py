"""CLI: python -m paddle_tpu.analysis [models...] [--all] [--json] ...

Runs the static analyzer over zoo models and exits non-zero when any
diagnostic reaches --fail-on severity (default: error) — the CI gate
that keeps the model zoo honest without TPU time. Run under
JAX_PLATFORMS=cpu; tracing never touches a device.

``--runtime`` switches to the runtime-code lint
(paddle_tpu.analysis.runtime): AST rules over the package sources —
lock discipline, RPC verb conformance, metric/flag catalog
consistency, thread-shared-state heuristic — gated by the checked-in
waiver file. Exit codes match the zoo path: 0 clean (or fully
waived), 1 findings at/above --fail-on, 2 usage error (including a
malformed waiver file).
"""

import argparse
import sys

# Runtime-only packages the jaxpr analyzer cannot see into: a broken
# import here (a bad refactor, a missing stub) would sail straight past
# the zoo lint, so the CLI gate import-checks them too. Keep in sync
# with the package layout.
IMPORT_CHECK_PACKAGES = (
    "paddle_tpu.resilience",
    "paddle_tpu.resilience.faults",
    "paddle_tpu.resilience.retry",
    "paddle_tpu.resilience.driver",
    "paddle_tpu.monitor",
    "paddle_tpu.monitor.watch",
    "paddle_tpu.monitor.collector",
    "paddle_tpu.monitor.goodput",
    "paddle_tpu.monitor.signals",
    "paddle_tpu.serving",
    "paddle_tpu.serving.engine",
    "paddle_tpu.serving.fleet",
    "paddle_tpu.serving.autoscale",
    "paddle_tpu.serving.rollout",
    "paddle_tpu.serving.kvpool",
    "paddle_tpu.serving.sampling",
    "paddle_tpu.serving.spec",
    "paddle_tpu.serving.sparse",
    "paddle_tpu.serving.sparse.cache",
    "paddle_tpu.serving.sparse.scoring",
    "paddle_tpu.serving.sparse.online",
    "paddle_tpu.ops.paged_attention",
    "paddle_tpu.reader",
    "paddle_tpu.reader.device_loader",
    "paddle_tpu.slo",
    "paddle_tpu.transform",
    "paddle_tpu.transform.passes",
    "paddle_tpu.transform.fusion",
    "paddle_tpu.transform.infer",
    "paddle_tpu.transform.memory",
    "paddle_tpu.transform.calibrate",
    "paddle_tpu.transform.autoparallel",
    "paddle_tpu.serving.artifact",
    "paddle_tpu.trace",
    "paddle_tpu.trace.runtime",
    "paddle_tpu.trace.clock",
    "paddle_tpu.trace.merge",
    "paddle_tpu.distributed",
    "paddle_tpu.distributed.master",
    "paddle_tpu.distributed.membership",
    "paddle_tpu.analysis.runtime",
    "paddle_tpu.analysis.runtime.rules",
)


def import_check(packages=IMPORT_CHECK_PACKAGES):
    """Import every runtime-only package; returns [(name, error), ...]
    (empty = all clean). Part of the --all CI gate."""
    import importlib
    failures = []
    for name in packages:
        try:
            importlib.import_module(name)
        except Exception as e:        # any failure mode is a gate fail
            failures.append((name, repr(e)))
    return failures


def main(argv=None):
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.analysis",
        description="jaxpr static analyzer over the paddle_tpu model "
                    "zoo")
    p.add_argument("models", nargs="*",
                   help="zoo model names (see --list-models)")
    p.add_argument("--all", action="store_true",
                   help="analyze every model in the zoo")
    p.add_argument("--json", action="store_true",
                   help="emit a JSON report instead of text")
    p.add_argument("--rules",
                   help="comma-separated rule names to run "
                        "(default: all)")
    p.add_argument("--fail-on", default="error",
                   choices=["error", "warning", "info"],
                   help="exit 1 if any diagnostic reaches this "
                        "severity (default: error)")
    p.add_argument("--verbose", "-v", action="store_true",
                   help="include info-level diagnostics in text "
                        "output")
    p.add_argument("--list-rules", action="store_true")
    p.add_argument("--list-models", action="store_true")
    p.add_argument("--runtime", action="store_true",
                   help="run the runtime-code lint (locks, RPC verbs, "
                        "metric/flag catalog, shared state) instead "
                        "of the jaxpr zoo analyzer")
    p.add_argument("--root",
                   help="repository root to lint (--runtime only; "
                        "default: this checkout)")
    p.add_argument("--waivers",
                   help="waiver file for --runtime ('none' disables; "
                        "default: analysis/runtime/waivers.json)")
    args = p.parse_args(argv)

    if args.runtime:
        return _runtime_main(p, args)

    from . import registered_rules, zoo_names
    from .zoo import analyze_zoo

    if args.list_rules:
        for name, cls in sorted(registered_rules().items(),
                                key=lambda kv: kv[1].id):
            print("%-6s %-18s %s" % (cls.id, name, cls.doc))
        return 0
    if args.list_models:
        for name in zoo_names():
            print(name)
        return 0

    failures = import_check()
    for name, err in failures:
        print("import-check FAILED: %s (%s)" % (name, err),
              file=sys.stderr)
    if failures:
        return 1

    names = zoo_names() if args.all or not args.models else args.models
    unknown = set(names) - set(zoo_names())
    if unknown:
        p.error("unknown model(s) %s; --list-models for the zoo"
                % ", ".join(sorted(unknown)))
    rules = args.rules.split(",") if args.rules else None
    if rules:
        bad = set(rules) - set(registered_rules())
        if bad:
            p.error("unknown rule(s) %s; --list-rules for the catalog"
                    % ", ".join(sorted(bad)))

    def progress(name, report, dt):
        if not args.json:
            c = report.counts()
            print("analyzed %-18s %5.1fs  %d error(s) %d warning(s)"
                  % (name, dt, c["error"], c["warning"]),
                  file=sys.stderr)

    report = analyze_zoo(names, rules=rules, progress=progress)
    if args.json:
        print(report.to_json())
    else:
        print(report.render_text(verbose=args.verbose))
    return 1 if report.at_least(args.fail_on) else 0


def _runtime_main(p, args):
    from .runtime import (run_runtime, registered_runtime_rules,
                          WaiverError)

    if args.list_rules:
        for name, cls in sorted(registered_runtime_rules().items(),
                                key=lambda kv: kv[1].id):
            print("%-6s %-20s %s" % (cls.id, name, cls.doc))
        return 0
    rules = None
    if args.rules:
        table = registered_runtime_rules()
        names = args.rules.split(",")
        bad = set(names) - set(table)
        if bad:
            p.error("unknown runtime rule(s) %s; --runtime "
                    "--list-rules for the catalog"
                    % ", ".join(sorted(bad)))
        rules = [table[n]() for n in names]
    try:
        report = run_runtime(root=args.root, rules=rules,
                             waivers_path=(args.waivers
                                           if args.waivers is not None
                                           else ""))
    except WaiverError as e:
        p.error(str(e))                   # argparse exits 2
    if args.json:
        print(report.to_json())
    else:
        print(report.render_text())
    return 1 if report.at_least(args.fail_on) else 0


if __name__ == "__main__":
    sys.exit(main())
