"""Cross-namespace __all__ parity gates (round 4): every public name in the
reference module's __all__ must resolve in ours. Complements
test_api_parity*.py (root/nn/functional/sparse) with the remaining
namespaces."""
import ast
import functools
import os

import pytest

import paddle_tpu as paddle

pytestmark = pytest.mark.needs_reference

_REF = "/root/reference/python/paddle"


def _ref_all(relpath):
    path = os.path.join(_REF, relpath)
    names = []
    tree = ast.parse(open(path).read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if getattr(t, "id", None) == "__all__":
                    try:
                        names += ast.literal_eval(node.value)
                    except Exception:
                        pass
    return names


_CASES = [
    ("optimizer", "optimizer/__init__.py"),
    ("optimizer.lr", "optimizer/lr.py"),
    ("nn", "nn/__init__.py"),
    ("nn.functional", "nn/functional/__init__.py"),
    ("distributed", "distributed/__init__.py"),
    ("distributed.fleet", "distributed/fleet/__init__.py"),
    ("vision", "vision/__init__.py"),
    ("vision.ops", "vision/ops.py"),
    ("vision.transforms", "vision/transforms/__init__.py"),
    ("linalg", "linalg.py"),
    ("signal", "signal.py"),
    ("fft", "fft.py"),
    ("distribution", "distribution/__init__.py"),
    ("sparse", "sparse/__init__.py"),
    ("static", "static/__init__.py"),
    ("static.nn", "static/nn/__init__.py"),
    ("profiler", "profiler/__init__.py"),
    ("utils", "utils/__init__.py"),
    ("incubate", "incubate/__init__.py"),
    ("io", "io/__init__.py"),
    ("metric", "metric/__init__.py"),
    ("amp", "amp/__init__.py"),
    ("autograd", "autograd/__init__.py"),
    ("text", "text/__init__.py"),
    ("jit", "jit/__init__.py"),
    ("callbacks", "callbacks.py"),
    ("hub", "hub.py"),
]


@pytest.mark.parametrize("mod,relpath", _CASES,
                         ids=[c[0] for c in _CASES])
def test_namespace_all_parity(mod, relpath):
    ours = functools.reduce(getattr, mod.split("."), paddle)
    missing = sorted(n for n in _ref_all(relpath) if not hasattr(ours, n))
    assert missing == [], f"paddle.{mod} missing: {missing}"


def test_full_coverage_report_is_clean():
    """tools/gen_api_coverage.py resolves 100% of the audited reference
    namespaces; run it to regenerate API_COVERAGE.md after API changes."""
    import importlib.util
    import os
    import sys

    spec = importlib.util.spec_from_file_location(
        "gen_api_coverage",
        os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                     "gen_api_coverage.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    total_ref = total_have = 0
    gaps = {}
    for rel in mod._TOP_MODULES:
        names = sorted(set(mod._collect(rel)))
        if not names:
            continue
        dotted = (rel[:-3] if rel.endswith(".py") else rel).replace("/", ".")
        ours = mod._ours(dotted)
        missing = [n for n in names
                   if ours is None or not hasattr(ours, n)]
        total_ref += len(names)
        total_have += len(names) - len(missing)
        if missing:
            gaps[dotted or "paddle"] = missing
    assert gaps == {}, f"coverage regressions: {gaps}"
    assert total_ref >= 1330  # audit scope only grows
