"""One inventory of environment switches: the "Engine tunables (env only)"
list of docs/configuration.md names every `PILOSA_TPU_*` variable the
package reads through `os.environ` by itself (config overrides,
`PILOSA_TPU_<SECTION>__<KEY>`, are `cli/config.py`'s and have their own
section), and names none the package no longer reads."""

import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
_READ = re.compile(
    r"""os\.(?:environ\.get\(|environ\[|getenv\()\s*["'](PILOSA_TPU_[A-Z_]+)["']"""
)
_BULLET = re.compile(r"^- `(PILOSA_TPU_[A-Z_]+)", re.M)


def _read_by_package() -> dict:
    """name -> the files of pilosa_tpu/ (outside cli/config.py) reading it."""
    found = {}
    for path in sorted((ROOT / "pilosa_tpu").rglob("*.py")):
        rel = path.relative_to(ROOT).as_posix()
        if rel == "pilosa_tpu/cli/config.py":
            continue
        for name in _READ.findall(path.read_text()):
            found.setdefault(name, []).append(rel)
    return found


def _listed_on_page() -> set:
    page = (ROOT / "docs" / "configuration.md").read_text()
    section = page.split("Engine tunables (env only):", 1)[1].split("\n## ", 1)[0]
    return set(_BULLET.findall(section))


READ, LISTED = _read_by_package(), _listed_on_page()


@pytest.mark.parametrize("name", sorted(set(READ) | LISTED))
def test_switch_is_read_and_listed(name):
    assert name in LISTED, (
        f"{name} is read by {READ[name]} and docs/configuration.md's "
        "'Engine tunables (env only)' list does not name it"
    )
    assert name in READ, (
        f"docs/configuration.md lists {name}, which nothing in pilosa_tpu/ reads"
    )
