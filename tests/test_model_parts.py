"""The models layer's import rule: a family is its own mixer and cache
plus the shared parts of `paddle_tpu/models/parts.py`. A module of
`paddle_tpu/models/` imports from no sibling but `parts` and `scopes`
(the package's `__init__` aside), and no module of it imports a name
that starts with `_` from another module of the package."""

import ast
import pathlib

PACKAGE = pathlib.Path(__file__).resolve().parents[1] / "paddle_tpu"
MODELS = PACKAGE / "models"
SHARED = {"parts", "scopes"}


def _imports(path):
    """-> [(line, the module imported from as a dotted path under
    `paddle_tpu`, or None outside it, [names])] of one module of
    `models/`, every import statement wherever it sits."""
    found = []
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name.startswith("paddle_tpu."):
                    found.append((node.lineno, a.name[len("paddle_tpu."):],
                                  []))
        elif isinstance(node, ast.ImportFrom):
            names = [a.name for a in node.names]
            if node.level:  # relative to paddle_tpu.models
                base = ["models"][:max(0, 2 - node.level)]
                mod = ".".join(base + ([node.module] if node.module else []))
            elif (node.module or "").startswith("paddle_tpu"):
                mod = node.module[len("paddle_tpu"):].lstrip(".")
            else:
                mod = None
            if mod == "models":  # `from . import x`: x is the module
                found.extend((node.lineno, "models." + n, []) for n in names)
            else:
                found.append((node.lineno, mod, names))
    return found


def test_a_family_imports_only_the_shared_parts():
    bad = []
    for path in sorted(MODELS.glob("*.py")):
        if path.name == "__init__.py":
            continue
        for line, mod, names in _imports(path):
            where = "%s:%d" % (path.name, line)
            if mod is None:
                continue
            dotted = mod.split(".")
            if (dotted[0] == "models" and len(dotted) > 1
                    and dotted[1] != path.stem and dotted[1] not in SHARED):
                bad.append("%s imports from models.%s" % (where, dotted[1]))
            private = [n for n in names if n.startswith("_")]
            if private:
                bad.append("%s imports %s from %s"
                           % (where, ", ".join(private), mod or "."))
    assert not bad, "\n".join(bad)
