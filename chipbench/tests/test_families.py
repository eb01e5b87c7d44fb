"""The seam between the harness and a model family, on the CPU and in seconds
(`python -m pytest chipbench/tests/test_families.py -q`; no `Experiment` is
built):

- `run.py`, `check.py` and `program.py` name no family and nothing one family
  alone knows (a population's attribute, a trigger's keys, an input scale, a
  flax module name);
- every module under `families/` offers the whole interface of
  `families/__init__.py`, and every file under `configs/` names a family that
  is there;
- a family with no file is an error that names the file looked for.
"""
from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

from chipbench import families

CHIPBENCH = Path(families.__file__).resolve().parents[1]
FAMILIES = sorted(p.stem for p in (CHIPBENCH / "families").glob("*.py")
                  if p.stem != "__init__")
CONFIGS = sorted(p.stem for p in (CHIPBENCH / "configs").glob("*.json"))
FAMILY_WORDS = re.compile(
    "|".join(FAMILIES + ["image_data", "poison_pattern", r"/\s*255",
                         r"Conv_\d", r"Dense_\d", r"BatchNorm_\d",
                         "BasicBlock"]), re.IGNORECASE)


@pytest.mark.parametrize("module", ["run.py", "check.py", "program.py"])
def test_the_harness_names_no_family(module):
    found = [(n, line) for n, line in enumerate(
        (CHIPBENCH / module).read_text().splitlines(), 1)
        if FAMILY_WORDS.search(line)]
    assert not found, found


@pytest.mark.parametrize("family", FAMILIES)
def test_a_family_offers_the_whole_interface(family):
    module = families.load(family)
    assert all(callable(getattr(module, n)) for n in families.INTERFACE)


@pytest.mark.parametrize("config", CONFIGS)
def test_a_configuration_names_a_family_that_is_there(config):
    loaded = json.loads((CHIPBENCH / "configs" / f"{config}.json").read_text())
    assert loaded["model"]["family"] in FAMILIES
    assert families.of(loaded).__name__.endswith(loaded["model"]["family"])


def test_a_family_without_a_file_fails_with_the_path():
    with pytest.raises(SystemExit) as e:
        families.load("no_such_family")
    assert str(CHIPBENCH / "families" / "no_such_family.py") in str(e.value)
    with pytest.raises(SystemExit, match="model.family"):
        families.of({"name": "bare", "model": {}})
