"""``python -m sheeprl_tpu.analysis`` — the graft-lint/jit/sync/audit CLI.

Subcommands, one exit-code contract (CI relies on it):

- ``lint`` (the default — bare paths keep working): AST rules GL001-GL008;
- ``jit``: purity & trace-hygiene analysis of the traced tier — corpus-wide
  tracedness model, PRNG key dataflow, host-sync-in-jit, constant baking,
  retrace hazards (rules GJ001-GJ005);
- ``audit``: AOT-lower every registered hot-path program on a virtual mesh
  and check donation aliasing, sharding declarations, dtype policy, baked
  constants, and the checked-in budget manifest (rules AUD001-AUD005);
- ``sync``: race & deadlock analysis of the async host runtime — per-class
  lockset model, lock-order graph, blocking-under-lock (rules GS001-GS005);
- ``sync-validate``: judge a runtime lock-sanitizer dump
  (``SHEEPRL_TPU_SYNC_DUMP``) — order cycles, inversions, over-budget holds;
- ``all``: lint + jit + sync + audit with one merged exit code and a single
  ``--format=github`` annotation stream (the CI front door); its
  ``--list-rules`` prints EVERY tier's catalog, and ``--select/--ignore``
  accept any rule from the merged catalog;
- ``tracecheck``: validate a runtime trace-event dump
  (``SHEEPRL_TPU_TRACECHECK_DUMP``) — post-warmup retraces are findings.

Exit codes: ``0`` clean, ``1`` at least one finding, ``2`` usage/internal
error. Formats: ``text``, ``json``, ``github`` (workflow annotations that
land inline on the PR diff). Every AST tier takes ``--strict-suppressions``:
stale ``# graft-*: disable`` directives (the rule no longer fires there) are
warnings by default, findings (exit 1) under the flag.

``audit`` re-executes itself in a worker subprocess with
``JAX_PLATFORMS=cpu`` and ``--xla_force_host_platform_device_count`` set
BEFORE JAX initializes — the mesh width is a process-boot property, and the
audit must run on a chip-less CPU sandbox.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
from typing import Any, Dict, List, Optional

from sheeprl_tpu.analysis.lint import (
    RULES,
    Finding,
    analyze_paths,
    apply_baseline,
    fingerprint,
    load_baseline,
    write_baseline,
)

DEFAULT_BASELINE = ".graft-lint-baseline.json"


def _parse_rules(spec: Optional[str], catalog: Optional[Dict[str, str]] = None) -> Optional[set]:
    catalog = RULES if catalog is None else catalog
    if not spec:
        return None
    rules = {r.strip().upper() for r in spec.split(",") if r.strip()}
    unknown = rules - set(catalog)
    if unknown:
        raise SystemExit2(f"unknown rule(s): {', '.join(sorted(unknown))} (known: {', '.join(sorted(catalog))})")
    return rules


class SystemExit2(Exception):
    pass


def _emit_text(findings: List[Finding], out) -> None:
    for f in findings:
        print(f.render(), file=out)


def _emit_github(findings: List[Finding], out, tool: str = "graft-lint") -> None:
    for f in findings:
        # '%' ',' and newlines must be escaped in workflow-command payloads
        msg = f.message.replace("%", "%25").replace("\r", "").replace("\n", "%0A")
        print(
            f"::error file={f.path},line={f.line},col={f.col},title={tool} {f.rule}::{msg} [in {f.function}]",
            file=out,
        )


def _merge_stale(
    findings: List[Finding], stale: List[Finding], strict: bool, tool: str
) -> List[Finding]:
    """Stale-suppression handling shared by the AST tiers: warn-level on
    stderr by default so fixed code surfaces its dead directives without
    breaking the build; ``--strict-suppressions`` merges them into the
    findings stream (exit 1) for the CI lane that keeps the tree honest."""
    if not stale:
        return findings
    if strict:
        merged = findings + stale
        merged.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
        return merged
    for f in stale:
        print(f"{tool}: warning: {f.render()}", file=sys.stderr)
    return findings


def _emit_json(findings: List[Finding], baselined: int, out, tool: str = "graft-lint", rules=None) -> None:
    payload = {
        "tool": tool,
        "rules": RULES if rules is None else rules,
        "baselined": baselined,
        "findings": [
            {
                "rule": f.rule,
                "path": f.path,
                "line": f.line,
                "col": f.col,
                "message": f.message,
                "function": f.function,
                "fingerprint": fingerprint(f),
            }
            for f in findings
        ],
    }
    json.dump(payload, out, indent=2)
    out.write("\n")


def lint_main(argv: List[str]) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m sheeprl_tpu.analysis",
        description="graft-lint: JAX/TPU-aware static analysis (rules GL001-GL008).",
    )
    parser.add_argument("paths", nargs="*", default=["sheeprl_tpu"], help="files/dirs to analyze")
    parser.add_argument("--format", choices=("text", "json", "github"), default="text")
    parser.add_argument(
        "--baseline",
        default=DEFAULT_BASELINE,
        help=f"baseline file of exempted pre-existing findings (default: {DEFAULT_BASELINE})",
    )
    parser.add_argument("--no-baseline", action="store_true", help="report everything, ignore the baseline")
    parser.add_argument(
        "--write-baseline",
        action="store_true",
        help="write the current findings to the baseline file and exit 0",
    )
    parser.add_argument("--select", help="comma-separated rules to run (default: all)")
    parser.add_argument("--ignore", help="comma-separated rules to skip")
    parser.add_argument("--list-rules", action="store_true", help="print the rule catalog and exit")
    parser.add_argument(
        "--strict-suppressions",
        action="store_true",
        help="stale `# graft-lint: disable` directives become findings (exit 1) instead of warnings",
    )
    args = parser.parse_args(argv)

    if args.list_rules:
        for rule, desc in sorted(RULES.items()):
            print(f"{rule}  {desc}")
        return 0

    try:
        select = _parse_rules(args.select)
        ignore = _parse_rules(args.ignore)
    except SystemExit2 as e:
        print(f"graft-lint: {e}", file=sys.stderr)
        return 2

    stale: List[Finding] = []
    try:
        findings = analyze_paths(args.paths, select=select, ignore=ignore, stale_out=stale)
    except Exception as e:  # pragma: no cover - internal error contract
        print(f"graft-lint: internal error: {e}", file=sys.stderr)
        return 2

    if args.write_baseline:
        try:
            write_baseline(args.baseline, findings)
        except OSError as e:
            print(f"graft-lint: cannot write baseline {args.baseline}: {e}", file=sys.stderr)
            return 2
        print(
            f"graft-lint: wrote {len(findings)} finding(s) to {args.baseline}",
            file=sys.stderr,
        )
        return 0

    baselined = 0
    if not args.no_baseline and os.path.exists(args.baseline):
        try:
            baseline = load_baseline(args.baseline)
        except (ValueError, OSError, json.JSONDecodeError) as e:
            print(f"graft-lint: unreadable baseline {args.baseline}: {e}", file=sys.stderr)
            return 2
        before = len(findings)
        findings = apply_baseline(findings, baseline)
        baselined = before - len(findings)

    # stale suppressions join AFTER the baseline: they describe directives,
    # not code, and must never consume a baseline slot
    findings = _merge_stale(findings, stale, args.strict_suppressions, "graft-lint")

    if args.format == "json":
        _emit_json(findings, baselined, sys.stdout)
    elif args.format == "github":
        _emit_github(findings, sys.stdout)
    else:
        _emit_text(findings, sys.stdout)

    summary = f"graft-lint: {len(findings)} finding(s)" + (f", {baselined} baselined" if baselined else "")
    print(summary, file=sys.stderr)
    return 1 if findings else 0


# --------------------------------------------------------------------------- #
# audit subcommand
# --------------------------------------------------------------------------- #


def _parse_mesh(spec: str):
    from sheeprl_tpu.analysis.programs import AuditMesh

    m = re.fullmatch(r"([a-z_][a-z0-9_]*)=(\d+)", spec.strip())
    if not m:
        raise SystemExit2(f"--mesh must look like 'dp=2', got {spec!r}")
    return AuditMesh(devices=int(m.group(2)), axes=(m.group(1),))


def _source_to_path(source: str, fallback: str) -> str:
    return source.replace(".", "/") + ".py" if source else fallback


def _audit_emit_github(findings, budgets_path: str, out) -> None:
    for f in findings:
        msg = f.message.replace("%", "%25").replace("\r", "").replace("\n", "%0A")
        anchor = budgets_path if f.rule == "AUD005" else _source_to_path(f.source, budgets_path)
        print(
            f"::error file={anchor},line=1,title=graft-audit {f.rule}::[{f.program}] {msg}",
            file=out,
        )


def _audit_worker(args) -> int:
    """Runs with the virtual mesh env already set by the parent: lower every
    selected program, judge budgets, print ONE json document."""
    import jax

    # the audit compiles for a virtual CPU mesh: pin the platform before
    # backend init so it never takes a chip (same pattern as
    # __graft_entry__ / collective_analysis workers)
    jax.config.update("jax_platforms", "cpu")
    # The persistent compilation cache is DISABLED for audits: an executable
    # loaded from the cache reports zeroed memory_analysis() (alias/temp
    # sizes) — the donation check and every budget measurement would read
    # garbage on warm runs. Cold compiles keep the measurements reproducible.
    jax.config.update("jax_enable_compilation_cache", False)

    from sheeprl_tpu.analysis.audit import run_audit
    from sheeprl_tpu.analysis.budgets import load_manifest
    from sheeprl_tpu.parallel.comm import set_grad_reduce_dtype

    mesh = _parse_mesh(args.mesh)
    # the wire dtype the drivers resolve on this mesh (grad_reduce_dtype=auto)
    set_grad_reduce_dtype(mesh.wire_dtype, fresh_run=True)

    select = [s.strip() for s in args.select.split(",")] if args.select else None
    manifest = None
    missing_manifest = False
    if not args.no_budgets and not args.write_budgets:
        if os.path.exists(args.budgets):
            manifest = load_manifest(args.budgets)
            if args.tolerance is not None:
                manifest["tolerance"] = float(args.tolerance)
        else:
            missing_manifest = True
    findings, measurements = run_audit(mesh, select=select, manifest=manifest)
    if missing_manifest:
        from sheeprl_tpu.analysis.audit import AuditFinding

        findings.append(
            AuditFinding(
                "AUD005",
                "<manifest>",
                f"budget manifest {args.budgets} not found — generate it with --write-budgets "
                "(every registered hot path must carry checked-in budgets)",
            )
        )
    json.dump(
        {
            "mesh": mesh.spec,
            "findings": [
                {"rule": f.rule, "program": f.program, "message": f.message, "source": f.source}
                for f in findings
            ],
            "measurements": measurements,
            "budgets_checked": manifest is not None,
        },
        sys.stdout,
    )
    sys.stdout.write("\n")
    return 0


def audit_main(argv: List[str]) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m sheeprl_tpu.analysis audit",
        description="graft-audit: compiled-program static analysis (rules AUD001-AUD005).",
    )
    parser.add_argument("--mesh", default="dp=2", help="virtual mesh, e.g. dp=2 (default) or dp=8")
    parser.add_argument("--select", help="comma-separated program names/globs (default: all registered)")
    parser.add_argument("--format", choices=("text", "json", "github"), default="text")
    parser.add_argument(
        "--budgets",
        default=None,
        help="budget manifest path (default: .graft-audit-budgets.json, searched upward from cwd)",
    )
    parser.add_argument("--no-budgets", action="store_true", help="skip the AUD005 manifest check")
    parser.add_argument(
        "--tolerance",
        type=float,
        default=None,
        help="override the manifest's budget tolerance (e.g. 0.10 for the CI drift lane)",
    )
    parser.add_argument(
        "--write-budgets",
        action="store_true",
        help="measure every selected program and (re)write the budget manifest, exit 0",
    )
    parser.add_argument("--list-programs", action="store_true", help="print the registered program inventory")
    parser.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    args = parser.parse_args(argv)

    from sheeprl_tpu.analysis.budgets import (
        DEFAULT_BUDGETS_PATH,
        manifest_from_measurements,
        write_manifest,
    )

    if args.budgets is None:
        # search upward so the CLI works from any checkout subdirectory
        d = os.getcwd()
        args.budgets = DEFAULT_BUDGETS_PATH
        while True:
            cand = os.path.join(d, DEFAULT_BUDGETS_PATH)
            if os.path.exists(cand):
                args.budgets = cand
                break
            parent = os.path.dirname(d)
            if parent == d:
                break
            d = parent

    try:
        mesh = _parse_mesh(args.mesh)
    except SystemExit2 as e:
        print(f"graft-audit: {e}", file=sys.stderr)
        return 2

    if args.worker:
        return _audit_worker(args)

    if args.list_programs:
        from sheeprl_tpu.analysis.programs import registered_names

        for name in registered_names():
            print(name)
        return 0

    # Re-exec in a worker with the virtual device width fixed pre-JAX-init.
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (flags + f" --xla_force_host_platform_device_count={mesh.devices}").strip()
    worker_argv = [sys.executable, "-m", "sheeprl_tpu.analysis", "audit", "--worker", "--mesh", args.mesh]
    if args.select:
        worker_argv += ["--select", args.select]
    worker_argv += ["--budgets", args.budgets]
    if args.tolerance is not None:
        worker_argv += ["--tolerance", str(args.tolerance)]
    if args.no_budgets or args.write_budgets:
        worker_argv += ["--no-budgets"]
    proc = subprocess.run(worker_argv, env=env, capture_output=True, text=True, timeout=3600)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr[-4000:])
        print(f"graft-audit: worker failed (rc={proc.returncode})", file=sys.stderr)
        return 2
    try:
        payload = json.loads(proc.stdout.strip().splitlines()[-1])
    except (json.JSONDecodeError, IndexError) as e:
        sys.stderr.write(proc.stderr[-2000:])
        print(f"graft-audit: unreadable worker output: {e}", file=sys.stderr)
        return 2

    from sheeprl_tpu.analysis.audit import AuditFinding

    findings = [AuditFinding(f["rule"], f["program"], f["message"], f.get("source", "")) for f in payload["findings"]]
    measurements: Dict[str, Dict[str, Any]] = payload["measurements"]

    if args.select and not measurements and not findings:
        print(
            f"graft-audit: --select {args.select!r} matched no registered program "
            "(see --list-programs) — refusing to report an empty selection as clean",
            file=sys.stderr,
        )
        return 2

    if args.write_budgets:
        if findings:
            for f in findings:
                print(f.render(), file=sys.stderr)
            print(
                f"graft-audit: refusing to write budgets over {len(findings)} live finding(s) — "
                "fix the programs first",
                file=sys.stderr,
            )
            return 1
        manifest = manifest_from_measurements(measurements, payload["mesh"])
        if args.select and os.path.exists(args.budgets):
            # a SELECTED re-baseline merges into the existing manifest — a
            # wholesale rewrite would delete every unselected program's row
            from sheeprl_tpu.analysis.budgets import load_manifest

            try:
                existing = load_manifest(args.budgets)
            except (ValueError, OSError, json.JSONDecodeError) as e:
                print(f"graft-audit: unreadable manifest {args.budgets}: {e}", file=sys.stderr)
                return 2
            existing["programs"].update(manifest["programs"])
            manifest = existing
        try:
            write_manifest(args.budgets, manifest)
        except OSError as e:
            print(f"graft-audit: cannot write {args.budgets}: {e}", file=sys.stderr)
            return 2
        print(
            f"graft-audit: wrote budgets for {len(measurements)} program(s) to {args.budgets}",
            file=sys.stderr,
        )
        return 0

    if args.format == "json":
        from sheeprl_tpu.analysis.audit import AUDIT_RULES

        json.dump(
            {
                "tool": "graft-audit",
                "mesh": payload["mesh"],
                "rules": AUDIT_RULES,
                "budgets_checked": payload["budgets_checked"],
                "findings": [
                    {"rule": f.rule, "program": f.program, "message": f.message, "source": f.source}
                    for f in findings
                ],
                "measurements": measurements,
            },
            sys.stdout,
            indent=2,
        )
        sys.stdout.write("\n")
    elif args.format == "github":
        _audit_emit_github(findings, os.path.relpath(args.budgets), sys.stdout)
    else:
        for f in findings:
            print(f.render())
    print(
        f"graft-audit: {len(findings)} finding(s) over {len(measurements)} program(s) "
        f"(mesh {payload['mesh']}, budgets {'checked' if payload['budgets_checked'] else 'skipped'})",
        file=sys.stderr,
    )
    return 1 if findings else 0


# --------------------------------------------------------------------------- #
# sync subcommand (graft-sync: race & deadlock analysis, rules GS001-GS005)
# --------------------------------------------------------------------------- #


def sync_main(argv: List[str]) -> int:
    from sheeprl_tpu.analysis.sync import SYNC_RULES, analyze_sync_paths

    parser = argparse.ArgumentParser(
        prog="python -m sheeprl_tpu.analysis sync",
        description="graft-sync: race & deadlock static analysis over the async host runtime (GS001-GS005).",
    )
    parser.add_argument("paths", nargs="*", default=["sheeprl_tpu"], help="files/dirs to analyze")
    parser.add_argument("--format", choices=("text", "json", "github"), default="text")
    parser.add_argument("--select", help="comma-separated rules to run (default: all)")
    parser.add_argument("--ignore", help="comma-separated rules to skip")
    parser.add_argument("--list-rules", action="store_true", help="print the rule catalog and exit")
    parser.add_argument(
        "--strict-suppressions",
        action="store_true",
        help="stale `# graft-sync: disable` directives become findings (exit 1) instead of warnings",
    )
    args = parser.parse_args(argv)

    if args.list_rules:
        for rule, desc in sorted(SYNC_RULES.items()):
            print(f"{rule}  {desc}")
        return 0

    try:
        select = _parse_rules(args.select, catalog=SYNC_RULES)
        ignore = _parse_rules(args.ignore, catalog=SYNC_RULES)
    except SystemExit2 as e:
        print(f"graft-sync: {e}", file=sys.stderr)
        return 2

    stale: List[Finding] = []
    try:
        findings = analyze_sync_paths(args.paths, select=select, ignore=ignore, stale_out=stale)
    except Exception as e:  # pragma: no cover - internal error contract
        print(f"graft-sync: internal error: {e}", file=sys.stderr)
        return 2
    findings = _merge_stale(findings, stale, args.strict_suppressions, "graft-sync")

    if args.format == "json":
        _emit_json(findings, 0, sys.stdout, tool="graft-sync", rules=SYNC_RULES)
    elif args.format == "github":
        _emit_github(findings, sys.stdout, tool="graft-sync")
    else:
        _emit_text(findings, sys.stdout)
    print(f"graft-sync: {len(findings)} finding(s)", file=sys.stderr)
    return 1 if findings else 0


# --------------------------------------------------------------------------- #
# jit subcommand (graft-jit: traced-tier purity & hygiene, rules GJ001-GJ005)
# --------------------------------------------------------------------------- #


def jit_main(argv: List[str]) -> int:
    from sheeprl_tpu.analysis.jit import JIT_RULES, analyze_jit_paths

    parser = argparse.ArgumentParser(
        prog="python -m sheeprl_tpu.analysis jit",
        description=(
            "graft-jit: static purity & trace-hygiene analysis over the traced/JAX tier "
            "(GJ001-GJ005 — PRNG key dataflow, host-sync-in-jit, constant baking, retrace hazards)."
        ),
    )
    parser.add_argument("paths", nargs="*", default=["sheeprl_tpu"], help="files/dirs to analyze")
    parser.add_argument("--format", choices=("text", "json", "github"), default="text")
    parser.add_argument("--select", help="comma-separated rules to run (default: all)")
    parser.add_argument("--ignore", help="comma-separated rules to skip")
    parser.add_argument("--list-rules", action="store_true", help="print the rule catalog and exit")
    parser.add_argument(
        "--strict-suppressions",
        action="store_true",
        help="stale `# graft-jit: disable` directives become findings (exit 1) instead of warnings",
    )
    args = parser.parse_args(argv)

    if args.list_rules:
        for rule, desc in sorted(JIT_RULES.items()):
            print(f"{rule}  {desc}")
        return 0

    try:
        select = _parse_rules(args.select, catalog=JIT_RULES)
        ignore = _parse_rules(args.ignore, catalog=JIT_RULES)
    except SystemExit2 as e:
        print(f"graft-jit: {e}", file=sys.stderr)
        return 2

    stale: List[Finding] = []
    try:
        findings = analyze_jit_paths(args.paths, select=select, ignore=ignore, stale_out=stale)
    except Exception as e:  # pragma: no cover - internal error contract
        print(f"graft-jit: internal error: {e}", file=sys.stderr)
        return 2
    findings = _merge_stale(findings, stale, args.strict_suppressions, "graft-jit")

    if args.format == "json":
        _emit_json(findings, 0, sys.stdout, tool="graft-jit", rules=JIT_RULES)
    elif args.format == "github":
        _emit_github(findings, sys.stdout, tool="graft-jit")
    else:
        _emit_text(findings, sys.stdout)
    print(f"graft-jit: {len(findings)} finding(s)", file=sys.stderr)
    return 1 if findings else 0


def sync_validate_main(argv: List[str]) -> int:
    from sheeprl_tpu.analysis.lockstats import validate_payload

    parser = argparse.ArgumentParser(
        prog="python -m sheeprl_tpu.analysis sync-validate",
        description=(
            "Validate a graft-sync runtime-sanitizer dump (SHEEPRL_TPU_SYNC_DUMP): "
            "lock-order cycles, recorded inversions and over-budget holds are findings."
        ),
    )
    parser.add_argument("dump", help="path to the JSON dump a sanitized run exported")
    args = parser.parse_args(argv)
    try:
        with open(args.dump, "r", encoding="utf-8") as fh:
            payload = json.load(fh)
        if payload.get("tool") != "graft-sync":
            raise ValueError(f"not a graft-sync dump (tool={payload.get('tool')!r})")
        problems, summary = validate_payload(payload)
    except (OSError, ValueError, json.JSONDecodeError, AttributeError) as e:
        print(f"sync-validate: unreadable dump {args.dump}: {e}", file=sys.stderr)
        return 2
    for p in problems:
        print(f"SYNC {p}")
    print(
        "sync-validate: {locks} lock(s), {edges} order edge(s) — {cycles} cycle(s), "
        "{inversions} inversion(s), {over_budget_locks} over-budget lock(s)".format(**summary),
        file=sys.stderr,
    )
    return 1 if problems else 0


# --------------------------------------------------------------------------- #
# all subcommand: lint + jit + sync + audit, one exit code / annotation stream
# --------------------------------------------------------------------------- #


def _merged_catalogs() -> List:
    """``(tool, catalog)`` for every tier, light imports only — AUDIT_RULES
    lives in a module whose top level never touches JAX, so listing the full
    catalog costs no compile machinery."""
    from sheeprl_tpu.analysis.audit import AUDIT_RULES
    from sheeprl_tpu.analysis.jit import JIT_RULES
    from sheeprl_tpu.analysis.lint import SUPPRESSION_RULE
    from sheeprl_tpu.analysis.sync import SYNC_RULES

    return [
        ("graft-lint", {**RULES, SUPPRESSION_RULE: "stale suppression directive (see --strict-suppressions)"}),
        ("graft-jit", JIT_RULES),
        ("graft-sync", SYNC_RULES),
        ("graft-audit", AUDIT_RULES),
    ]


def all_main(argv: List[str]) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m sheeprl_tpu.analysis all",
        description=(
            "Run every static tier — graft-lint (GL), graft-jit (GJ), graft-sync (GS), "
            "graft-audit (AUD) — with one merged exit code and a single --format stream "
            "(CI runs exactly this)."
        ),
    )
    parser.add_argument("paths", nargs="*", default=["sheeprl_tpu"], help="files/dirs for the AST tiers")
    parser.add_argument(
        "--format",
        choices=("text", "github"),
        default="text",
        help="text or github (line-oriented streams that concatenate cleanly); "
        "for machine-readable JSON run the individual tiers, each emits one document",
    )
    parser.add_argument("--mesh", default="dp=2", help="virtual audit mesh (default dp=2)")
    parser.add_argument("--tolerance", type=float, default=None, help="audit budget tolerance override")
    parser.add_argument("--skip-audit", action="store_true", help="AST tiers only (no compile pass)")
    parser.add_argument(
        "--select",
        help="comma-separated rules from ANY tier's catalog; tiers with no selected rule are skipped "
        "(an AUD rule selects the whole audit pass — it has no per-rule filter)",
    )
    parser.add_argument("--ignore", help="comma-separated rules from any tier's catalog to skip")
    parser.add_argument(
        "--list-rules", action="store_true", help="print EVERY tier's rule catalog and exit"
    )
    parser.add_argument(
        "--strict-suppressions",
        action="store_true",
        help="stale `# graft-*: disable` directives become findings (exit 1) in every AST tier",
    )
    args = parser.parse_args(argv)

    catalogs = _merged_catalogs()

    if args.list_rules:
        for tool, catalog in catalogs:
            print(f"{tool}:")
            for rule, desc in sorted(catalog.items()):
                print(f"  {rule}  {desc}")
        return 0

    merged: Dict[str, str] = {}
    for _tool, catalog in catalogs:
        merged.update(catalog)
    try:
        select = _parse_rules(args.select, catalog=merged)
        ignore = _parse_rules(args.ignore, catalog=merged)
    except SystemExit2 as e:
        print(f"analysis all: {e}", file=sys.stderr)
        return 2

    def tier_argv(catalog: Dict[str, str]) -> Optional[List[str]]:
        """Per-tier --select/--ignore subset; None = the selection names no
        rule of this tier, skip it entirely."""
        extra: List[str] = []
        if select is not None:
            sub = select & set(catalog)
            if not sub:
                return None
            extra += ["--select", ",".join(sorted(sub))]
        if ignore is not None:
            sub = ignore & set(catalog)
            if set(catalog) - sub == set():
                return None  # every rule of the tier ignored
            if sub:
                extra += ["--ignore", ",".join(sorted(sub))]
        return extra

    strict = ["--strict-suppressions"] if args.strict_suppressions else []
    rcs: Dict[str, object] = {}
    for tool, tier_main, catalog in (
        ("lint", lint_main, catalogs[0][1]),
        ("jit", jit_main, catalogs[1][1]),
        ("sync", sync_main, catalogs[2][1]),
    ):
        extra = tier_argv(catalog)
        if extra is None:
            rcs[tool] = "skipped"
            continue
        rcs[tool] = tier_main(list(args.paths) + ["--format", args.format] + extra + strict)
    if args.skip_audit or (select is not None and not (select & set(catalogs[3][1]))):
        rcs["audit"] = "skipped"
    else:
        audit_argv = ["--format", args.format, "--mesh", args.mesh]
        if args.tolerance is not None:
            audit_argv += ["--tolerance", str(args.tolerance)]
        rcs["audit"] = audit_main(audit_argv)

    print(
        "analysis all: lint={lint} jit={jit} sync={sync} audit={audit}".format(**rcs),
        file=sys.stderr,
    )
    codes = [rc for rc in rcs.values() if isinstance(rc, int)]
    if any(rc == 2 for rc in codes):
        return 2
    return 1 if any(rc == 1 for rc in codes) else 0


# --------------------------------------------------------------------------- #
# tracecheck-dump subcommand
# --------------------------------------------------------------------------- #


def tracecheck_main(argv: List[str]) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m sheeprl_tpu.analysis tracecheck",
        description=(
            "Validate a tracecheck dump artifact (SHEEPRL_TPU_TRACECHECK_DUMP): "
            "post-warmup retraces on any registered hot path are findings."
        ),
    )
    parser.add_argument("dump", help="path to the JSON dump a run exported")
    args = parser.parse_args(argv)
    try:
        with open(args.dump, "r", encoding="utf-8") as fh:
            payload = json.load(fh)
        entries = payload["entries"]
    except (OSError, json.JSONDecodeError, KeyError) as e:
        print(f"tracecheck: unreadable dump {args.dump}: {e}", file=sys.stderr)
        return 2
    bad = 0
    for name, rep in sorted(entries.items()):
        retraces = int(rep.get("post_warmup_compiles", 0))
        line = (
            f"{name}: {rep.get('calls', 0)} calls, {rep.get('compiles', 0)} compiles, "
            f"{retraces} post-warmup"
        )
        if retraces > int(rep.get("budget", 0)):
            print(f"RETRACE {line}")
            bad += 1
        else:
            print(f"ok      {line}")
    print(f"tracecheck: {bad} hot path(s) over budget", file=sys.stderr)
    return 1 if bad else 0


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "audit":
        return audit_main(argv[1:])
    if argv and argv[0] == "tracecheck":
        return tracecheck_main(argv[1:])
    if argv and argv[0] == "jit":
        return jit_main(argv[1:])
    if argv and argv[0] == "sync":
        return sync_main(argv[1:])
    if argv and argv[0] == "sync-validate":
        return sync_validate_main(argv[1:])
    if argv and argv[0] == "all":
        return all_main(argv[1:])
    if argv and argv[0] == "lint":
        argv = argv[1:]
    return lint_main(argv)


if __name__ == "__main__":
    sys.exit(main())
