"""Every ``AREAL_*`` knob that ``constants.get_env_vars`` forwards to
spawned workers is read somewhere: a knob that is defined and forwarded
but that no line consults is a setting a user can make to no effect
(ISSUE 28 found two). Plain ``ast`` over ``areal_tpu/``; no import of the
code under test."""

import ast
import glob
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONSTANTS = os.path.join(REPO, "areal_tpu", "base", "constants.py")

# the accessors a knob is read through (``base/constants.py``'s tolerant
# parsers, ``worker_base._env_float``) and the plain ``os`` ones
READERS = {
    "env_flag", "env_int", "env_float", "env_str", "env_knob", "_env_float",
    "getenv", "get",
}


def _catalog():
    """``({symbol: literal}, [forwarded literals])`` from the module's
    ``X_ENV = "AREAL_..."`` assignments and ``get_env_vars``' list."""
    tree = ast.parse(open(CONSTANTS).read())
    consts = {
        t.id: n.value.value
        for n in tree.body
        if isinstance(n, ast.Assign)
        and isinstance(n.value, ast.Constant)
        and isinstance(n.value.value, str)
        for t in n.targets
    }
    fn = next(
        n for n in tree.body
        if isinstance(n, ast.FunctionDef) and n.name == "get_env_vars"
    )
    keys = next(
        n.value for n in ast.walk(fn)
        if isinstance(n, ast.Assign) and n.targets[0].id == "keys"
    )
    forwarded = [
        e.value if isinstance(e, ast.Constant) else consts[e.id]
        for e in keys.elts
    ]
    return consts, [k for k in forwarded if k.startswith("AREAL_")]


def _names(node):
    """What an expression names a knob by: its literal, or the symbol of
    a ``X_ENV`` / ``constants.X_ENV`` reference."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return {node.value}
    if isinstance(node, ast.Name):
        return {node.id}
    if isinstance(node, ast.Attribute):
        return {node.attr}
    return set()


def _knobs_read():
    """Every name or literal that a reader call, an ``os.environ[...]``
    lookup or an ``... in os.environ`` test consults under ``areal_tpu/``."""
    read = set()
    for path in glob.glob(
        os.path.join(REPO, "areal_tpu", "**", "*.py"), recursive=True
    ):
        for node in ast.walk(ast.parse(open(path).read())):
            if isinstance(node, ast.Call) and node.args:
                f = node.func
                name = f.attr if isinstance(f, ast.Attribute) else getattr(
                    f, "id", None
                )
                if name in READERS:
                    read |= _names(node.args[0])
            elif isinstance(node, ast.Subscript) and isinstance(
                node.ctx, ast.Load
            ):
                if "environ" in ast.dump(node.value):
                    read |= _names(node.slice)
            elif isinstance(node, ast.Compare) and any(
                isinstance(op, ast.In) for op in node.ops
            ):
                if any("environ" in ast.dump(c) for c in node.comparators):
                    read |= _names(node.left)
    return read


def test_every_forwarded_knob_has_a_reader():
    consts, knobs = _catalog()
    assert len(knobs) > 40, knobs       # the list was found, not an empty walk
    read = _knobs_read()
    read |= {consts[name] for name in read if name in consts}
    dead = [k for k in knobs if k not in read]
    assert dead == [], (
        f"forwarded to every worker but read by nothing: {dead}"
    )
