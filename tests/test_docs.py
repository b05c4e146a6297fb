"""The table of ``TFOS_*`` environment variables in ``docs/API.md`` against
the names the package reads: each name is in the table, and the table names
nothing else.  The table is the count of the package's switches; a later
change that adds or removes one shows here."""

import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NAME = re.compile(r"TFOS_[A-Z0-9_]+")
KINDS = {"deployment", "fallback", "A/B switch", "diagnostic"}


def _names_the_package_reads():
    names = set()
    for folder, _, files in os.walk(os.path.join(ROOT, "tensorflowonspark_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as f:
                    names.update(_NAME.findall(f.read()))
    return sorted(names)


def _table():
    """``{name: (default, selects, kind)}`` from the rows of the table."""
    rows = {}
    with open(os.path.join(ROOT, "docs", "API.md")) as f:
        for line in f:
            cells = [c.strip() for c in line.strip().strip("|").split("|")]
            if len(cells) == 4 and _NAME.fullmatch(cells[0].strip("`")):
                rows[cells[0].strip("`")] = tuple(cells[1:])
    return rows


@pytest.mark.parametrize("name", _names_the_package_reads())
def test_every_env_name_the_package_reads_is_in_the_table(name):
    default, selects, kind = _table()[name]
    assert default and selects
    assert kind in KINDS


def test_the_table_names_nothing_the_package_does_not_read():
    assert sorted(_table()) == _names_the_package_reads()
