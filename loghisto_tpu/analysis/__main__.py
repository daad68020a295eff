"""CLI gate: ``python -m loghisto_tpu.analysis [--pass NAME ...]``.

Runs the three static passes (jaxpr contract audit, import-graph lint,
concurrency lint), applies the reviewed baseline, prints one
``file:line [pass] scope: reason`` line per surviving finding, and
exits nonzero if any survive.  The jaxpr pass traces every registered
program on CPU abstract shapes — safe to run anywhere, including as
bench.py's preflight on a TPU host (it forces the CPU platform in its
own process).
"""

from __future__ import annotations

import argparse
import os
import sys

PASSES = ("jaxpr", "imports", "locks")


def _force_cpu_devices() -> None:
    """Must run before jax is imported anywhere in this process: the
    jaxpr pass needs 8 virtual CPU devices for the mesh contracts (the
    same bootstrap tests/conftest.py performs)."""
    flag = "--xla_force_host_platform_device_count=8"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {flag}".strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _run_jaxpr_pass(programs_file: str | None = None):
    import jax

    # the audit traces on the CPU, whatever accelerator the host has
    jax.config.update("jax_platforms", "cpu")
    from loghisto_tpu.analysis import jaxpr_audit

    if programs_file is None:
        return jaxpr_audit.audit_all()
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_loghisto_audit_programs", programs_file
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    findings = []
    for program in module.PROGRAMS:
        findings.extend(jaxpr_audit.audit_spec(program))
    return findings


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m loghisto_tpu.analysis",
        description="static contract analyzer (jaxpr audit, import "
                    "lint, lock lint)",
    )
    parser.add_argument(
        "--pass", dest="passes", action="append", choices=PASSES,
        help="run only the named pass (repeatable; default: all)",
    )
    parser.add_argument(
        "--list", action="store_true",
        help="list the audited programs and their contracts, then exit",
    )
    # Fixture-tree overrides (tests/test_contracts.py drives the CLI
    # against tests/analysis_fixtures/ with these; baseline suppression
    # is skipped when any is set):
    parser.add_argument(
        "--programs", metavar="FILE",
        help="audit ProgramSpecs from FILE's PROGRAMS tuple instead of "
             "the built-in registry (jaxpr pass)",
    )
    parser.add_argument(
        "--root", metavar="DIR",
        help="lint DIR instead of loghisto_tpu/ (locks/imports passes)",
    )
    parser.add_argument(
        "--package", metavar="NAME",
        help="package name under --root (imports pass)",
    )
    parser.add_argument(
        "--frontier", action="append", metavar="MODULE",
        help="override the jax-free frontier module list (imports pass)",
    )
    args = parser.parse_args(argv)
    selected = tuple(args.passes) if args.passes else PASSES
    overridden = bool(args.programs or args.root or args.frontier)

    if "jaxpr" in selected:
        _force_cpu_devices()

    if args.list:
        _force_cpu_devices()
        from loghisto_tpu.analysis.jaxpr_audit import PROGRAMS

        for spec in PROGRAMS:
            c = spec.contract
            print(f"{spec.name:40s} dispatches={c.dispatches} "
                  f"pallas={c.pallas_calls} donated={c.donated} "
                  f"stream_psums={c.stream_psums} "
                  f"no_dense_MB={bool(c.forbidden_shapes)}  "
                  f"[{spec.factory}]")
        return 0

    from loghisto_tpu.analysis import apply_baseline

    findings = []
    for name in selected:
        if name == "jaxpr":
            findings.extend(_run_jaxpr_pass(args.programs))
        elif name == "imports":
            from loghisto_tpu.analysis import import_lint

            if args.root and args.package:
                graph = import_lint.build_import_graph(
                    package_root=os.path.join(args.root, args.package),
                    package=args.package,
                    repo_root=args.root,
                )
                findings.extend(import_lint.frontier_findings(
                    frontier=tuple(args.frontier or ()), graph=graph,
                ))
            else:
                findings.extend(import_lint.run())
        elif name == "locks":
            from loghisto_tpu.analysis import lock_lint

            findings.extend(
                lock_lint.run(args.root) if args.root else lock_lint.run()
            )

    survivors = (list(findings) if overridden
                 else apply_baseline(findings, passes=selected))
    for finding in sorted(survivors, key=lambda f: (f.path, f.line)):
        print(finding.render())
    suppressed = len(findings) - sum(
        1 for f in survivors if f.pass_name != "baseline"
    )
    print(
        f"analysis: {len(survivors)} finding(s), {suppressed} "
        f"baseline-suppressed, passes={','.join(selected)}",
        file=sys.stderr,
    )
    return 1 if survivors else 0


if __name__ == "__main__":
    raise SystemExit(main())
