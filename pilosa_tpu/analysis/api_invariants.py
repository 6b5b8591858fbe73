"""API-invariant pass: registries that must stay in sync across layers.

Rules (codes):

* API001 — a stats emission (`*.stats.count("name", ...)` etc.) whose
  literal name is not declared in `utils/stats.py` `STAT_NAMES` (or
  covered by a `STAT_PREFIXES` prefix for dynamically-built families).
  Dashboards reference declared names; an undeclared emission is a
  metric nothing can find.
* API002 — a declared STAT_NAMES entry that no module emits: stale
  registry (dynamically-prefixed families are exempt — their full names
  never appear as literals).
* API003 — a config knob (dataclass field in `cli/config.py`) whose
  kebab-case name is missing from `docs/configuration.md`.
* API004 — a `server` CLI flag in `cli/main.py` that maps to no config
  knob (flags are overrides of config; an unmapped flag silently does
  nothing).
* API005 — a config knob with no corresponding `server` CLI flag
  (every knob must be settable from the command line, per the
  config-precedence contract flags > env > file > defaults).
* API006 — a span started (`start_span` / `start_span_from_headers` /
  `record_span`) with a literal name not declared in `utils/tracing.py`
  `SPAN_NAMES`. The flight recorder's assembly, dashboards, and the
  slow-query log key on these names; an undeclared span is a stage
  nothing can attribute.
* API007 — a declared SPAN_NAMES entry no module starts: stale
  registry (same contract as API002 for STAT_NAMES). A
  `TraceAnnotation("name", ...)` with a literal name starts one too,
  in utils/tracing.py itself as well: the profiler-clock-only names
  (`runtime.gc`, `node.tick`) are annotations and never spans.
* API009 — a config knob no module ever reads at runtime: the field
  name never appears as an attribute read outside `cli/config.py`
  itself (flag tables and argparse strings don't count — only a real
  `cfg.section.knob` access does). A knob that parses, documents, and
  round-trips but influences nothing is dead configuration.

All facts are extracted statically from the ASTs — the pass never
imports the package, so it works on broken/half-edited trees too.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Sequence, Set, Tuple

from pilosa_tpu.analysis.framework import (
    Finding,
    Module,
    Pass,
    dotted_name,
)

__all__ = ["ApiInvariantsPass"]

_EMIT_METHODS = {"count", "gauge", "histogram", "timing", "set_value", "timer"}

# span-starting callables (methods on a tracer, or the module-level
# helpers in utils/tracing.py that route to the active trace's tracer)
_SPAN_METHODS = {"start_span", "start_span_from_headers", "record_span"}
# a profiler annotation under a literal name (API006/API007 as a span)
_ANNOTATION = "TraceAnnotation"

# server flags that intentionally do NOT map to config knobs
_NON_KNOB_FLAGS = {
    "config",  # selects the TOML file the knobs come from
    "join",  # one-shot boot action, not persistent configuration
    "help",
}

# Config dataclass -> TOML/doc section name ("" = top-level)
_SECTION_CLASSES = {
    "Config": "",
    "ClusterConfig": "cluster",
    "SchedConfig": "sched",
    "TenantsConfig": "tenants",
    "HbmConfig": "hbm",
    "BsiConfig": "bsi",
    "IngestConfig": "ingest",
    "WalConfig": "wal",
    "MeshConfig": "mesh",
    "CacheConfig": "cache",
    "ResizeConfig": "resize",
    "TierConfig": "tier",
    "CoherenceConfig": "coherence",
    "AntiEntropyConfig": "anti_entropy",
    "MetricConfig": "metric",
    "TracingConfig": "tracing",
    "TelemetryConfig": "telemetry",
    "TLSConfig": "tls",
}


def _stats_receiver(call: ast.Call) -> bool:
    """True when the call target reads like a StatsClient emission:
    `stats.count(...)`, `self.stats.timing(...)`,
    `self.server.stats.count(...)`, or the inline labeled-family form
    `self.stats.with_tags("index:a").gauge(...)` (the child client is
    ephemeral — the emission still must name a declared stat)."""
    fn = call.func
    if not isinstance(fn, ast.Attribute) or fn.attr not in _EMIT_METHODS:
        return False
    recv = fn.value
    if (
        isinstance(recv, ast.Call)
        and isinstance(recv.func, ast.Attribute)
        and recv.func.attr == "with_tags"
    ):
        recv = recv.func.value
    name = dotted_name(recv)
    return name is not None and name.split(".")[-1] == "stats"


class ApiInvariantsPass(Pass):
    name = "api-invariants"
    rules = (
        "API001", "API002", "API003", "API004", "API005", "API006",
        "API007", "API008", "API009",
    )

    def __init__(self, docs_path: Optional[str] = None):
        # resolved lazily against the module set's repo root when None
        self._docs_path = docs_path

    def run(self, modules: Sequence[Module]) -> List[Finding]:
        findings: List[Finding] = []
        by_rel = {m.rel: m for m in modules}
        stats_mod = by_rel.get("pilosa_tpu/utils/stats.py")
        tracing_mod = by_rel.get("pilosa_tpu/utils/tracing.py")
        config_mod = by_rel.get("pilosa_tpu/cli/config.py")
        main_mod = by_rel.get("pilosa_tpu/cli/main.py")
        if stats_mod is not None:
            self._check_stats(modules, stats_mod, findings)
        if tracing_mod is not None:
            self._check_spans(modules, tracing_mod, findings)
        if config_mod is not None:
            knobs = self._config_knobs(config_mod)
            self._check_docs(config_mod, knobs, findings)
            if main_mod is not None:
                self._check_flags(main_mod, knobs, findings)
            self._check_knob_reads(modules, config_mod, knobs, findings)
        return findings

    # -- stats registry ----------------------------------------------------

    def _declared(
        self, stats_mod: Module
    ) -> Tuple[Set[str], Set[str], int, int]:
        names: Set[str] = set()
        prefixes: Set[str] = set()
        names_line = prefixes_line = 1
        for stmt in stats_mod.tree.body:
            if not (
                isinstance(stmt, ast.Assign)
                and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)
            ):
                continue
            target = stmt.targets[0].id
            if target not in ("STAT_NAMES", "STAT_PREFIXES"):
                continue
            for node in ast.walk(stmt.value):
                if isinstance(node, ast.Constant) and isinstance(
                    node.value, str
                ):
                    if target == "STAT_NAMES":
                        names.add(node.value)
                    else:
                        prefixes.add(node.value)
            if target == "STAT_NAMES":
                names_line = stmt.lineno
            else:
                prefixes_line = stmt.lineno
        return names, prefixes, names_line, prefixes_line

    def _check_stats(
        self,
        modules: Sequence[Module],
        stats_mod: Module,
        findings: List[Finding],
    ) -> None:
        names, prefixes, names_line, _ = self._declared(stats_mod)
        emitted: Set[str] = set()
        for m in modules:
            if m.rel == stats_mod.rel:
                continue  # the client plumbing itself, not emissions
            for node in ast.walk(m.tree):
                if not (
                    isinstance(node, ast.Call) and _stats_receiver(node)
                ):
                    continue
                if not node.args:
                    continue
                arg = node.args[0]
                if isinstance(arg, ast.Constant) and isinstance(
                    arg.value, str
                ):
                    emitted.add(arg.value)
                    if arg.value not in names and not any(
                        arg.value.startswith(p) for p in prefixes
                    ):
                        findings.append(
                            Finding(
                                code="API001",
                                path=m.rel,
                                line=node.lineno,
                                message=(
                                    f"stat {arg.value!r} emitted but not "
                                    "declared in utils/stats.py "
                                    "STAT_NAMES"
                                ),
                            )
                        )
                elif isinstance(arg, ast.JoinedStr):
                    # dynamic name: its literal leading part must sit
                    # under a declared prefix
                    lead = ""
                    if arg.values and isinstance(
                        arg.values[0], ast.Constant
                    ):
                        lead = str(arg.values[0].value)
                    if not any(lead.startswith(p) for p in prefixes):
                        findings.append(
                            Finding(
                                code="API001",
                                path=m.rel,
                                line=node.lineno,
                                message=(
                                    "dynamically-built stat name "
                                    f"(leading literal {lead!r}) not "
                                    "covered by utils/stats.py "
                                    "STAT_PREFIXES"
                                ),
                            )
                        )
        for name in sorted(names - emitted):
            findings.append(
                Finding(
                    code="API002",
                    path=stats_mod.rel,
                    line=names_line,
                    message=(
                        f"STAT_NAMES declares {name!r} but no module "
                        "emits it — stale registry entry"
                    ),
                )
            )
        self._check_labels(stats_mod, names, prefixes, findings)

    @staticmethod
    def _declared_labels(
        stats_mod: Module,
    ) -> Tuple[Dict[str, Tuple[str, ...]], int]:
        """Parse the STAT_LABELS literal: family name -> label-key tuple
        (tools/prom_lint.py loads the runtime dict; the gate checks the
        declaration itself stays coherent)."""
        labels: Dict[str, Tuple[str, ...]] = {}
        line = 1
        for stmt in stats_mod.tree.body:
            targets = (
                stmt.targets
                if isinstance(stmt, ast.Assign)
                else [stmt.target]
                if isinstance(stmt, ast.AnnAssign) and stmt.value is not None
                else []
            )
            if not (
                len(targets) == 1
                and isinstance(targets[0], ast.Name)
                and targets[0].id == "STAT_LABELS"
                and isinstance(stmt.value, ast.Dict)
            ):
                continue
            line = stmt.lineno
            for k, v in zip(stmt.value.keys, stmt.value.values):
                if not (
                    isinstance(k, ast.Constant) and isinstance(k.value, str)
                ):
                    continue
                keys = tuple(
                    e.value
                    for e in ast.walk(v)
                    if isinstance(e, ast.Constant)
                    and isinstance(e.value, str)
                )
                labels[k.value] = keys
        return labels, line

    def _check_labels(
        self,
        stats_mod: Module,
        names: Set[str],
        prefixes: Set[str],
        findings: List[Finding],
    ) -> None:
        """API008: every labeled family in STAT_LABELS must name a
        DECLARED stat with a non-empty label-key set — a typo'd family
        name would make prom_lint enforce labels on a series nobody
        emits while the real family renders unchecked."""
        labels, line = self._declared_labels(stats_mod)
        for family, keys in sorted(labels.items()):
            declared = family in names or any(
                family.startswith(p) for p in prefixes
            )
            if not declared:
                findings.append(
                    Finding(
                        code="API008",
                        path=stats_mod.rel,
                        line=line,
                        message=(
                            f"STAT_LABELS entry {family!r} is not a "
                            "declared stat (STAT_NAMES/STAT_PREFIXES) — "
                            "labeled-family rule would never match"
                        ),
                    )
                )
            if not keys:
                findings.append(
                    Finding(
                        code="API008",
                        path=stats_mod.rel,
                        line=line,
                        message=(
                            f"STAT_LABELS entry {family!r} declares no "
                            "label keys — an empty label set means the "
                            "family is unlabeled; remove the entry"
                        ),
                    )
                )

    # -- span-name registry ------------------------------------------------

    @staticmethod
    def _declared_spans(tracing_mod: Module) -> Tuple[Set[str], int]:
        names: Set[str] = set()
        line = 1
        for stmt in tracing_mod.tree.body:
            if not (
                isinstance(stmt, ast.Assign)
                and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)
                and stmt.targets[0].id == "SPAN_NAMES"
            ):
                continue
            for node in ast.walk(stmt.value):
                if isinstance(node, ast.Constant) and isinstance(
                    node.value, str
                ):
                    names.add(node.value)
            line = stmt.lineno
        return names, line

    def _check_spans(
        self,
        modules: Sequence[Module],
        tracing_mod: Module,
        findings: List[Finding],
    ) -> None:
        names, names_line = self._declared_spans(tracing_mod)
        started: Set[str] = set()
        for m in modules:
            # the tracer plumbing itself holds no start sites, but its
            # annotations under a literal name count
            methods = (
                {_ANNOTATION}
                if m.rel == tracing_mod.rel
                else _SPAN_METHODS | {_ANNOTATION}
            )
            for node in ast.walk(m.tree):
                if not isinstance(node, ast.Call) or not node.args:
                    continue
                fn = node.func
                # tracer.start_span("x") / tracing.record_span("x", ...)
                # method style, or a from-imported bare call
                if isinstance(fn, ast.Attribute):
                    if fn.attr not in methods:
                        continue
                elif isinstance(fn, ast.Name):
                    if fn.id not in methods:
                        continue
                else:
                    continue
                arg = node.args[0]
                if not (
                    isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)
                ):
                    continue
                started.add(arg.value)
                if arg.value not in names:
                    findings.append(
                        Finding(
                            code="API006",
                            path=m.rel,
                            line=node.lineno,
                            message=(
                                f"span {arg.value!r} started but not "
                                "declared in utils/tracing.py SPAN_NAMES"
                            ),
                        )
                    )
        for name in sorted(names - started):
            findings.append(
                Finding(
                    code="API007",
                    path=tracing_mod.rel,
                    line=names_line,
                    message=(
                        f"SPAN_NAMES declares {name!r} but no module "
                        "starts it — stale registry entry"
                    ),
                )
            )

    # -- config knobs ------------------------------------------------------

    def _config_knobs(self, config_mod: Module) -> Dict[str, int]:
        """knob path ('bind', 'cluster.replicas', ...) -> decl line."""
        knobs: Dict[str, int] = {}
        section_class_names = set(_SECTION_CLASSES)
        for node in config_mod.tree.body:
            if not (
                isinstance(node, ast.ClassDef)
                and node.name in _SECTION_CLASSES
            ):
                continue
            section = _SECTION_CLASSES[node.name]
            for stmt in node.body:
                if not (
                    isinstance(stmt, ast.AnnAssign)
                    and isinstance(stmt.target, ast.Name)
                ):
                    continue
                # skip sub-config aggregation fields on Config itself
                ann = stmt.annotation
                ann_name = (
                    ann.id
                    if isinstance(ann, ast.Name)
                    else dotted_name(ann) or ""
                )
                if ann_name in section_class_names:
                    continue
                field = stmt.target.id
                path = f"{section}.{field}" if section else field
                knobs[path] = stmt.lineno
        return knobs

    def _docs_text(self, config_mod: Module) -> Tuple[str, str]:
        if self._docs_path is not None:
            docs_path = self._docs_path
        else:
            root = os.path.dirname(
                os.path.dirname(os.path.dirname(config_mod.path))
            )
            docs_path = os.path.join(root, "docs", "configuration.md")
        try:
            with open(docs_path, encoding="utf-8") as fh:
                return fh.read(), docs_path
        except OSError:
            return "", docs_path

    def _check_docs(
        self,
        config_mod: Module,
        knobs: Dict[str, int],
        findings: List[Finding],
    ) -> None:
        text, _ = self._docs_text(config_mod)
        for path, line in sorted(knobs.items()):
            kebab = path.split(".")[-1].replace("_", "-")
            if kebab not in text:
                findings.append(
                    Finding(
                        code="API003",
                        path=config_mod.rel,
                        line=line,
                        message=(
                            f"config knob {path!r} ({kebab!r}) is not "
                            "documented in docs/configuration.md"
                        ),
                    )
                )

    def _check_knob_reads(
        self,
        modules: Sequence[Module],
        config_mod: Module,
        knobs: Dict[str, int],
        findings: List[Finding],
    ) -> None:
        """API009: a declared knob nothing ever reads. A knob counts as
        read only when its field name appears as an attribute access
        (`cfg.section.knob`, `self.knob`) in some module other than the
        config declarations themselves — flag tables, argparse strings
        and TOML keys are plumbing, not consumption."""
        read: Set[str] = set()
        for m in modules:
            if m.rel == config_mod.rel:
                continue
            for node in ast.walk(m.tree):
                if isinstance(node, ast.Attribute):
                    read.add(node.attr)
        for path, line in sorted(knobs.items()):
            field = path.split(".")[-1]
            if field not in read:
                findings.append(
                    Finding(
                        code="API009",
                        path=config_mod.rel,
                        line=line,
                        message=(
                            f"config knob {path!r} is declared (and "
                            "documented, and flagged) but never read at "
                            "runtime — no module accesses `.{0}`; wire "
                            "it up or delete it".format(field)
                        ),
                    )
                )

    # -- CLI flags ---------------------------------------------------------

    @staticmethod
    def _server_flags(main_mod: Module) -> Dict[str, int]:
        """--flag-name (sans dashes, snake_cased) -> line, for the
        `server` subparser plus the top-level parser."""
        flags: Dict[str, int] = {}
        server_vars: Set[str] = set()
        parser_vars: Set[str] = set()
        for node in ast.walk(main_mod.tree):
            if isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Call
            ):
                callee = dotted_name(node.value.func) or ""
                if callee.endswith(".add_parser"):
                    args = node.value.args
                    if (
                        args
                        and isinstance(args[0], ast.Constant)
                        and args[0].value == "server"
                    ):
                        for t in node.targets:
                            if isinstance(t, ast.Name):
                                server_vars.add(t.id)
                elif callee.endswith("ArgumentParser"):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            parser_vars.add(t.id)
        for node in ast.walk(main_mod.tree):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "add_argument"
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id in (server_vars | parser_vars)
            ):
                continue
            for arg in node.args:
                if (
                    isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)
                    and arg.value.startswith("--")
                ):
                    flags[arg.value[2:].replace("-", "_")] = node.lineno
        return flags

    def _check_flags(
        self,
        main_mod: Module,
        knobs: Dict[str, int],
        findings: List[Finding],
    ) -> None:
        flags = self._server_flags(main_mod)
        knob_matchers: Dict[str, str] = {}  # acceptable flag name -> knob
        for path in knobs:
            if "." in path:
                section, field = path.split(".", 1)
                knob_matchers[f"{section}_{field}"] = path
                knob_matchers.setdefault(field, path)
            else:
                knob_matchers[path] = path
        for flag, line in sorted(flags.items()):
            if flag in _NON_KNOB_FLAGS:
                continue
            if flag not in knob_matchers:
                findings.append(
                    Finding(
                        code="API004",
                        path=main_mod.rel,
                        line=line,
                        message=(
                            f"server flag --{flag.replace('_', '-')} "
                            "maps to no config knob in cli/config.py"
                        ),
                    )
                )
        matched_knobs = {
            knob_matchers[f] for f in flags if f in knob_matchers
        }
        for path, line in sorted(knobs.items()):
            if path not in matched_knobs:
                findings.append(
                    Finding(
                        code="API005",
                        path="pilosa_tpu/cli/config.py",
                        line=line,
                        message=(
                            f"config knob {path!r} has no `server` CLI "
                            "flag in cli/main.py"
                        ),
                    )
                )
