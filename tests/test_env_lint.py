"""Lint: the library takes its budgets and device counts as arguments.

No module under ``src/repro`` may read the process environment, so no
environment variable can change an answer.  Two modules are exempt
because they only locate caches (``compile_cache.py``: the XLA compile
cache; ``core/lutstore.py``: the QueueLUT store), and ``launch/dryrun.py``
may *set* ``XLA_FLAGS`` for its own process before JAX starts.  The
walker is AST-based, so comments and docstrings never trip it.
"""

import ast
import pathlib

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
CACHE_LOCATORS = {"compile_cache.py", "core/lutstore.py"}
ENV_NAMES = {"environ", "getenv", "environb", "getenvb"}


def _is_dryrun_flag_write(node, parents) -> bool:
    """``os.environ["XLA_FLAGS"] = ...``: a store, never a read."""
    sub = parents.get(node)
    return (isinstance(sub, ast.Subscript)
            and isinstance(sub.ctx, ast.Store)
            and isinstance(sub.slice, ast.Constant)
            and sub.slice.value == "XLA_FLAGS")


def _env_reads(path: pathlib.Path) -> list[str]:
    rel = path.relative_to(SRC).as_posix()
    tree = ast.parse(path.read_text(), str(path))
    parents = {c: n for n in ast.walk(tree) for c in ast.iter_child_nodes(n)}
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "os":
            hit = any(a.name in ENV_NAMES for a in node.names)
        elif isinstance(node, ast.Attribute):
            hit = (node.attr in ENV_NAMES
                   and isinstance(node.value, ast.Name)
                   and node.value.id == "os"
                   and not (rel == "launch/dryrun.py"
                            and _is_dryrun_flag_write(node, parents)))
        else:
            hit = False
        if hit:
            found.append(f"{rel}:{node.lineno}")
    return found


def test_library_reads_no_environment():
    bad = [hit for p in sorted(SRC.rglob("*.py"))
           if p.relative_to(SRC).as_posix() not in CACHE_LOCATORS
           for hit in _env_reads(p)]
    assert not bad, (
        "src/repro reads the environment; take the value as an argument "
        "and let the entry point resolve it: " + ", ".join(bad))
