"""Files found by name.  A metric's reader, a configuration's reference, its
comparison and its warm-ups are each `<kind>/<name>.py` under the benchmark's
directory, and a later PR brings a new one by adding the file and naming it in
data: nothing here, and nothing that calls this, knows any of the names.

Like `traffic.load_mix`, every function takes the directory it looks in, so a
test can lay files of its own in a temporary one and find them the same way.
This module imports neither JAX nor the program."""

from __future__ import annotations

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

# A configuration's key -> the directory its value names a file of.  There is
# no default for any of them: a configuration that names none fails the run.
CONFIG_PIECES = {"reference": "references", "comparison": "comparisons",
                 "warmups": "warmups"}
ORDERED_LISTS = ("warmups",)     # keys that name several files, in order


class MissingPiece(Exception):
    """A configuration names no file for a piece, or one that is not there."""


def path_of(kind: str, name: str, root: str = HERE) -> str:
    """`<root>/<kind>/<name>.py`, which has to be there."""
    path = os.path.join(root, kind, name + ".py")
    if not os.path.isfile(path):
        raise MissingPiece(f"{kind}/{name}.py is not there (looked in {root})")
    return path


def load(kind: str, name: str, root: str = HERE, needs=()):
    """The module `<root>/<kind>/<name>.py`, which must define `needs`."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{abs(hash((root, name)))}",
        path_of(kind, name, root))
    mod = importlib.util.module_from_spec(spec)
    # As `import` does: the module lives as long as the process, and with it
    # what it has compiled (a reference's programs stay loaded in the worker,
    # as they did when the reference was imported by name).
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    lacks = [n for n in needs if not hasattr(mod, n)]
    if lacks:
        raise MissingPiece(f"{kind}/{name}.py defines no {', '.join(lacks)}")
    return mod


def named(config: dict, root: str = HERE) -> dict:
    """What a configuration names: {"reference": name, "comparison": name,
    "warmups": [names]}, each a file that is there.  Raises MissingPiece with
    the key or the file's name in the message; imports nothing."""
    out = {}
    for key, kind in CONFIG_PIECES.items():
        value = config.get(key)
        names = value if key in ORDERED_LISTS else [value]
        if not names or not isinstance(names, list) \
                or not all(isinstance(n, str) and n for n in names):
            raise MissingPiece(
                f"the configuration names no {key!r}: it has to name "
                + ("a list of files" if key in ORDERED_LISTS else "one file")
                + f" under {kind}/; there is no default")
        for n in names:
            try:
                path_of(kind, n, root)
            except MissingPiece as e:
                raise MissingPiece(f"the configuration's {key!r} names {n!r}: "
                                   f"{e}") from None
        out[key] = value
    return out
