"""metrics-naming: one exported-metric naming scheme, documented both ways.

Every exported row renders as ``<prefix>_<family>_<name>`` (obs/metrics.py),
and OBSERVABILITY.md carries a table row per family describing its source —
that table is the operator contract dashboards are built against.  This pass
pins the scheme statically:

* the ``PREFIX`` constant in ``OBS_METRICS_MODULE`` must equal the declared
  ``METRIC_PREFIX`` (rename drift breaks every scrape config at once);
* every ``sample(<family>, <name>, ...)`` literal: family matches
  ``[a-z][a-z0-9]*`` and name fragments match snake_case (f-string name
  templates are checked on their constant fragments);
* every ``counter_dict_provider(<family>, ...)`` and
  ``labelled_counter_provider(<family>, ...)`` literal family likewise
  (those adapters stamp the family onto a whole accessor's counters);
* families used in code ⊆ families documented in the OBSERVABILITY.md
  table (rows shaped ``| `fam` | ...``), and documented families ⊆ used —
  both directions, so the doc can neither lag nor advertise ghosts.

Doc cross-checks run only when the doc is loaded (bare fixtures and
installed-package runs skip them).  Escape: ``#: metric-ok <reason>``.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set, Tuple

from sparkucx_tpu.analysis.base import (
    Finding,
    Program,
    callee_name,
    register_global,
)
from sparkucx_tpu.analysis.config import (
    METRIC_PREFIX,
    OBS_METRICS_MODULE,
    TRACE_DOC,
)

PASS = "metrics-naming"
ESCAPE = "#: metric-ok"

_FAMILY_RE = re.compile(r"^[a-z][a-z0-9]*$")
_NAME_FRAGMENT_RE = re.compile(r"^[a-z0-9_]*$")
#: a family row in the OBSERVABILITY.md table: ``| `fam` | source |``
_DOC_ROW_RE = re.compile(r"^\|\s*`([a-z0-9_]+)`\s*\|", re.MULTILINE)


def _escaped(lines: List[str], lineno: int) -> bool:
    return 1 <= lineno <= len(lines) and ESCAPE in lines[lineno - 1]


def _str_arg(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _name_fragments(node: ast.AST) -> Optional[List[str]]:
    """Constant fragments of a metric-name argument: a literal yields
    itself, an f-string yields its constant pieces, anything else None
    (dynamic names come from accessor dict keys — not checkable here)."""
    lit = _str_arg(node)
    if lit is not None:
        return [lit]
    if isinstance(node, ast.JoinedStr):
        return [
            v.value
            for v in node.values
            if isinstance(v, ast.Constant) and isinstance(v.value, str)
        ]
    return None


@register_global(PASS)
def metrics_naming_pass(program: Program) -> List[Finding]:
    findings: List[Finding] = []
    used_families: Dict[str, Tuple[str, int]] = {}  # family -> first use site

    for rel, (tree, source) in sorted(program.modules.items()):
        lines = source.splitlines()
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            callee = callee_name(node)
            if callee == "sample" and node.args:
                fam = _str_arg(node.args[0])
                if fam is not None:
                    used_families.setdefault(fam, (rel, node.lineno))
                    if not _FAMILY_RE.match(fam) and not _escaped(lines, node.lineno):
                        findings.append(Finding(rel, node.lineno, PASS, (
                            f"metric family '{fam}' breaks the "
                            f"{METRIC_PREFIX}_<family>_<name> scheme — "
                            f"families are [a-z][a-z0-9]*")))
                if len(node.args) > 1:
                    frags = _name_fragments(node.args[1])
                    if frags is not None:
                        bad = [f for f in frags if not _NAME_FRAGMENT_RE.match(f)]
                        if bad and not _escaped(lines, node.lineno):
                            findings.append(Finding(rel, node.lineno, PASS, (
                                f"metric name fragment {bad[0]!r} is not "
                                f"snake_case — exported rows must parse as "
                                f"{METRIC_PREFIX}_<family>_<name>")))
            elif callee in ("counter_dict_provider", "labelled_counter_provider") and node.args:
                fam = _str_arg(node.args[0])
                if fam is not None:
                    used_families.setdefault(fam, (rel, node.lineno))
                    if not _FAMILY_RE.match(fam) and not _escaped(lines, node.lineno):
                        findings.append(Finding(rel, node.lineno, PASS, (
                            f"metric family '{fam}' breaks the "
                            f"{METRIC_PREFIX}_<family>_<name> scheme — "
                            f"families are [a-z][a-z0-9]*")))

    # the PREFIX constant itself must match the declared scheme
    obs = program.module(OBS_METRICS_MODULE)
    if obs is not None:
        tree, _src = obs
        for node in tree.body:
            if (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == "PREFIX"
            ):
                val = _str_arg(node.value)
                if val != METRIC_PREFIX:
                    findings.append(Finding(OBS_METRICS_MODULE, node.lineno, PASS, (
                        f"PREFIX is {val!r} but the documented scheme is "
                        f"'{METRIC_PREFIX}_<family>_<name>' — update "
                        f"METRIC_PREFIX in analysis/config.py and "
                        f"OBSERVABILITY.md together")))

    doc = program.docs.get(TRACE_DOC)
    if doc is not None:
        documented: Set[str] = set(_DOC_ROW_RE.findall(doc))
        for fam, (rel, lineno) in sorted(used_families.items()):
            if fam not in documented:
                findings.append(Finding(rel, lineno, PASS, (
                    f"metric family '{fam}' has no row in the {TRACE_DOC} "
                    f"family table — every exported family is operator "
                    f"contract; document its source")))
        # reverse direction only when the program actually registers
        # families (a bare fixture module would otherwise flag every row)
        if used_families:
            for fam in sorted(documented - set(used_families)):
                findings.append(Finding(OBS_METRICS_MODULE, 1, PASS, (
                    f"{TRACE_DOC} documents metric family '{fam}' but no "
                    f"sample()/counter-provider site registers it — "
                    f"prune the stale row or restore the family")))

    findings.sort(key=lambda f: (f.path, f.line, f.message))
    return findings
