"""definition-drift — multiple definition sites of one fact must
agree; known single-definition-site registries must stay single.

* **D001** — every key in config.yaml must exist as a field on the
  corresponding vgate_tpu/config.py model (a renamed/removed model
  field silently orphans the yaml knob: pydantic ignores it and the
  operator's setting stops doing anything).
* **D002** — every config model field must be *discoverable*: its name
  appears as a key in config.yaml or is mentioned in docs/ (the
  operations knob tables).  This is how "secret knobs" — added in
  code, never annotated anywhere an operator reads — get caught.
* **D003** — the priority-tier vocabulary has ONE definition site
  (``admission.TIERS``, per the PR-4 hardening): any other
  tuple/list/set literal of exactly {"interactive", "standard",
  "batch"} in package code is a drifting copy.
* **D004** — ``DEVICE_PEAKS`` (TPU roofline peaks) is assigned only in
  vgate_tpu/observability/roofline.py; everything else imports it.
* **D005** — drill scripts must take their ports from the
  ``VGT_DRILL_PORTS`` registry in scripts/_drill_lib.sh; a literal
  ``873x`` port in any other script is the foot-gun PR 6 removed.
* **D006** — ``VGT_LOCK_ORDER`` / ``VGT_LOCK_ALIASES`` (the lock-
  acquisition order contract) are assigned only in
  vgate_tpu/analysis/lock_order.py; the lock-order checker and the
  runtime witness both read that one site, so a second copy would
  let them disagree about which orders are legal.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set, Tuple

from vgate_tpu.analysis import _astutil as A
from vgate_tpu.analysis.core import Checker, Project, Violation

_CONFIG_PY = "vgate_tpu/config.py"
_CONFIG_YAML = "config.yaml"
_TIER_SET = {"interactive", "standard", "batch"}
_TIERS_HOME = "vgate_tpu/admission.py"
_PEAKS_HOME = "vgate_tpu/observability/roofline.py"
_LOCK_ORDER_HOME = "vgate_tpu/analysis/lock_order.py"
_LOCK_ORDER_NAMES = {"VGT_LOCK_ORDER", "VGT_LOCK_ALIASES"}
_PORT_RE = re.compile(r"\b873[0-9]\b")

# container annotations whose yaml value is free-form (operator-keyed
# dicts like admission.key_tiers) — D001 stops recursing there
_OPEN_CONTAINERS = {"Dict", "dict", "Mapping"}


class _Model:
    """One config.py BaseModel: field -> nested model class (or None
    for leaves), plus the raw annotation text for container detection."""

    def __init__(self) -> None:
        self.fields: Dict[str, Optional[str]] = {}
        self.open_fields: Set[str] = set()
        self.lines: Dict[str, int] = {}


def _collect_models(tree: ast.AST) -> Dict[str, _Model]:
    models: Dict[str, _Model] = {}
    class_names = {
        n.name
        for n in getattr(tree, "body", [])
        if isinstance(n, ast.ClassDef)
    }
    for node in getattr(tree, "body", []):
        if not isinstance(node, ast.ClassDef):
            continue
        model = _Model()
        for item in node.body:
            if not isinstance(item, ast.AnnAssign) or not isinstance(
                item.target, ast.Name
            ):
                continue
            fname = item.target.id
            if fname.startswith("_") or fname == "model_config":
                continue
            ann_names = {
                sub.id
                for sub in ast.walk(item.annotation)
                if isinstance(sub, ast.Name)
            } | {
                sub.attr
                for sub in ast.walk(item.annotation)
                if isinstance(sub, ast.Attribute)
            }
            nested = next(
                (n for n in ann_names if n in class_names), None
            )
            model.fields[fname] = nested
            if ann_names & _OPEN_CONTAINERS:
                model.open_fields.add(fname)
            model.lines[fname] = item.lineno
        models[node.name] = model
    return models


def _yaml_load(text: str):
    try:
        import yaml
    except ImportError:  # pragma: no cover - yaml is a repo dep
        return None
    try:
        return yaml.safe_load(text)
    except yaml.YAMLError:
        return None


def _yaml_key_lines(lines: List[str]) -> Dict[str, int]:
    """Best-effort line numbers for top-of-block yaml keys (display
    only; fingerprints are line-free)."""
    out: Dict[str, int] = {}
    for i, text in enumerate(lines, start=1):
        m = re.match(r"^\s*([A-Za-z_][A-Za-z0-9_]*)\s*:", text)
        if m and m.group(1) not in out:
            out[m.group(1)] = i
    return out


class DefinitionDriftChecker(Checker):
    name = "definition-drift"
    description = (
        "config.yaml <-> config.py <-> docs knob drift; TIERS / "
        "DEVICE_PEAKS / drill-port single-definition-site registries"
    )
    scope = (
        _CONFIG_PY,
        _CONFIG_YAML,
        "docs/*.md",
        "vgate_tpu/**/*.py",
        "benchmarks/**/*.py",
        "scripts/*.sh",
        "scripts/*.py",
    )

    def run(self, project: Project) -> List[Violation]:
        out: List[Violation] = []
        self._check_config_drift(project, out)
        self._check_registries(project, out)
        return out

    # -- config.yaml <-> config.py <-> docs ---------------------------

    def _check_config_drift(
        self, project: Project, out: List[Violation]
    ) -> None:
        cfg_ctx = project.context(_CONFIG_PY)
        yaml_ctx = project.context(_CONFIG_YAML)
        if cfg_ctx.tree is None or not yaml_ctx.text:
            return
        models = _collect_models(cfg_ctx.tree)
        root = models.get("VGTConfig")
        data = _yaml_load(yaml_ctx.text)
        if root is None or not isinstance(data, dict):
            return
        key_lines = _yaml_key_lines(yaml_ctx.lines)
        docs_text = "\n".join(
            ctx.text for ctx in project.files("docs/*.md")
        )
        yaml_text = yaml_ctx.text

        def walk_yaml(
            node: dict, model: _Model, prefix: str
        ) -> None:
            for key, value in node.items():
                path = f"{prefix}{key}"
                if key not in model.fields:
                    out.append(
                        Violation(
                            checker=self.name,
                            path=_CONFIG_YAML,
                            line=key_lines.get(str(key), 1),
                            rule="D001",
                            message=(
                                f"config.yaml key {path!r} has no "
                                "matching field on the config.py "
                                "model — the knob is silently dead"
                            ),
                            symbol=path,
                        )
                    )
                    continue
                nested = model.fields[key]
                if (
                    nested
                    and isinstance(value, dict)
                    and key not in model.open_fields
                ):
                    walk_yaml(value, models[nested], path + ".")

        walk_yaml(data, root, "")

        def yaml_paths(node, prefix=""):
            out = set()
            if isinstance(node, dict):
                for k, v in node.items():
                    p = f"{prefix}{k}"
                    out.add(p)
                    out |= yaml_paths(v, p + ".")
            return out

        present_paths = yaml_paths(data)

        def walk_model(
            model: _Model, prefix: str, cls_name: str
        ) -> None:
            for fname, nested in model.fields.items():
                path = f"{prefix}{fname}"
                if nested and fname not in model.open_fields:
                    walk_model(models[nested], path + ".", nested)
                    continue
                # real keys are matched against the PARSED yaml at
                # the exact dotted path (a bare `enabled:` under some
                # other section must not vacuously satisfy
                # foo.enabled); a commented-out `# knob: value` line —
                # the repo's convention for documenting optional
                # knobs — is matched textually
                in_yaml = path in present_paths or (
                    re.search(
                        rf"^\s*#\s*{re.escape(fname)}\s*:",
                        yaml_text,
                        re.MULTILINE,
                    )
                    is not None
                )
                # docs matching: the dotted path always counts; the
                # bare field name counts only when it is distinctive
                # (contains an underscore) — a knob named `enabled` or
                # `level` would otherwise be vacuously "documented" by
                # any prose word, defeating the secret-knob check
                in_docs = (
                    re.search(
                        rf"\b{re.escape(path)}\b", docs_text
                    )
                    is not None
                    or (
                        "_" in fname
                        and re.search(
                            rf"\b{re.escape(fname)}\b", docs_text
                        )
                        is not None
                    )
                )
                if not in_yaml and not in_docs:
                    out.append(
                        Violation(
                            checker=self.name,
                            path=_CONFIG_PY,
                            line=model.lines.get(fname, 1),
                            rule="D002",
                            message=(
                                f"config knob {path!r} "
                                f"({cls_name}.{fname}) appears "
                                "neither in config.yaml nor "
                                "anywhere under docs/ — operators "
                                "cannot discover it"
                            ),
                            symbol=path,
                        )
                    )

        walk_model(root, "", "VGTConfig")

    # -- single-definition-site registries ----------------------------

    def _check_registries(
        self, project: Project, out: List[Violation]
    ) -> None:
        py_files = project.files(
            "vgate_tpu/**/*.py",
            "benchmarks/**/*.py",
            "scripts/*.py",
        )
        for ctx in py_files:
            tree = ctx.tree
            if tree is None:
                continue
            # the analysis package itself must be able to name the
            # vocabulary it polices
            in_analysis = ctx.relpath.startswith("vgate_tpu/analysis/")
            if ctx.relpath != _TIERS_HOME and not in_analysis:
                for node in ast.walk(tree):
                    tup = A.string_tuple(node) if isinstance(
                        node, (ast.Tuple, ast.List, ast.Set)
                    ) else None
                    if tup and set(tup) == _TIER_SET:
                        out.append(
                            Violation(
                                checker=self.name,
                                path=ctx.relpath,
                                line=node.lineno,
                                rule="D003",
                                message=(
                                    "literal copy of the priority-"
                                    "tier vocabulary — import "
                                    "admission.TIERS (the single "
                                    "definition site) instead"
                                ),
                                symbol=f"{ctx.relpath}:TIERS",
                            )
                        )
            if ctx.relpath != _PEAKS_HOME:
                for node in getattr(tree, "body", []):
                    names: List[Tuple[str, int]] = []
                    if isinstance(node, ast.Assign):
                        names = [
                            (t.id, node.lineno)
                            for t in node.targets
                            if isinstance(t, ast.Name)
                        ]
                    elif isinstance(
                        node, ast.AnnAssign
                    ) and isinstance(node.target, ast.Name):
                        names = [(node.target.id, node.lineno)]
                    for name, line in names:
                        if name == "DEVICE_PEAKS":
                            out.append(
                                Violation(
                                    checker=self.name,
                                    path=ctx.relpath,
                                    line=line,
                                    rule="D004",
                                    message=(
                                        "DEVICE_PEAKS reassigned "
                                        "outside observability/"
                                        "roofline.py — import the "
                                        "shared table so live "
                                        "gauges and benches can "
                                        "never disagree on peaks"
                                    ),
                                    symbol=(
                                        f"{ctx.relpath}:DEVICE_PEAKS"
                                    ),
                                )
                            )
            if ctx.relpath != _LOCK_ORDER_HOME:
                for node in getattr(tree, "body", []):
                    names = []
                    if isinstance(node, ast.Assign):
                        names = [
                            (t.id, node.lineno)
                            for t in node.targets
                            if isinstance(t, ast.Name)
                        ]
                    elif isinstance(
                        node, ast.AnnAssign
                    ) and isinstance(node.target, ast.Name):
                        names = [(node.target.id, node.lineno)]
                    for name, line in names:
                        if name in _LOCK_ORDER_NAMES:
                            out.append(
                                Violation(
                                    checker=self.name,
                                    path=ctx.relpath,
                                    line=line,
                                    rule="D006",
                                    message=(
                                        f"{name} assigned outside "
                                        "analysis/lock_order.py — "
                                        "the lock-order checker and "
                                        "the runtime witness must "
                                        "read ONE registry (import "
                                        "it instead)"
                                    ),
                                    symbol=f"{ctx.relpath}:{name}",
                                )
                            )
        for ctx in project.files("scripts/*.sh"):
            if ctx.relpath == "scripts/_drill_lib.sh":
                continue
            for i, text in enumerate(ctx.lines, start=1):
                m = _PORT_RE.search(text)
                if m:
                    out.append(
                        Violation(
                            checker=self.name,
                            path=ctx.relpath,
                            line=i,
                            rule="D005",
                            message=(
                                f"literal drill port {m.group(0)} — "
                                "resolve it via drill_port <name> "
                                "from the VGT_DRILL_PORTS registry "
                                "in scripts/_drill_lib.sh"
                            ),
                            symbol=f"{ctx.relpath}:{m.group(0)}",
                        )
                    )
