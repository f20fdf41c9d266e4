"""Tier-1's view of the on-chip benchmark's own tests: every test of
`chipbench/tests/` runs here under its module's name, so that the harness,
its references, comparisons, warm-ups and readers are guarded by what the
driver counts (`python -m pytest chipbench/tests` runs them alone).

Each module is imported once and its tests and fixtures are bound in this
file's namespace: a test as `test_<module>__<name>`, so that two modules may
name a test alike, and a fixture under its own name (two modules that define
one fixture name differently would have to rename one: asserted below)."""

import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _bind():
    folder = os.path.join(ROOT, "chipbench", "tests")
    fixtures = {}
    for fname in sorted(os.listdir(folder)):
        if not (fname.startswith("test_") and fname.endswith(".py")):
            continue
        short = fname[len("test_"):-len(".py")]
        mod = importlib.import_module(f"chipbench.tests.{fname[:-3]}")
        for name, obj in vars(mod).items():
            if name.startswith("test_") and callable(obj):
                globals()[f"test_{short}__{name[len('test_'):]}"] = obj
            elif type(obj).__name__ == "FixtureFunctionDefinition" \
                    or hasattr(obj, "_pytestfixturefunction"):
                assert fixtures.setdefault(name, short) == short, (
                    f"fixture {name!r} is defined in test_{short}.py and "
                    f"test_{fixtures[name]}.py: rename one")
                globals()[name] = obj


_bind()
