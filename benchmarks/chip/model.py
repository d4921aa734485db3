"""A configuration file, as the yardstick reads it, and the program's
``ArchConfig`` cut to it.

A configuration file ``configs/<name>.json`` holds, at its top level,
the keys of the source's ``config.json`` with the values it is run at,
and beside them:

* ``name``, ``arch`` (the program's config), ``family`` (the yardstick
  ``families/<family>.py``: sizes, weight layout, plain reference) and
  ``source``;
* ``reduced``: every key whose value here differs from the source, as
  ``{"key", "published", "here", "why"}``; ``here`` is the value at the
  top level;
* ``deployment``: over how many chips each layer is divided, and how;
* ``assumed``: what the source does not state and was set here.

Nothing here imports the program: the reference, the weights, the costs
and the check of the program's own config all start from this.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


class ConfigMismatch(RuntimeError):
    """The program's config departs from the configuration file, or the
    file's cut cannot be applied."""


def load_config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def field_value(obj, field: str):
    """The value of a dotted field (``moe.n_experts``)."""
    for name in field.split("."):
        obj = getattr(obj, name)
    return obj


def replace_field(obj, field: str, value):
    """``dataclasses.replace`` on a dotted field (``moe.n_experts``)."""
    head, _, rest = field.partition(".")
    if not dataclasses.is_dataclass(obj) or head not in {
            f.name for f in dataclasses.fields(obj)}:
        raise ConfigMismatch(f"the program's config has no field {field!r}")
    if rest:
        value = replace_field(getattr(obj, head), rest, value)
    return dataclasses.replace(obj, **{head: value})


def cut(conf: dict, family, arch_cfg):
    """The configuration as run, and the program's config cut to it: each
    ``reduced`` entry is applied through the family's key -> field map.
    Raises :class:`ConfigMismatch` where an entry disagrees with the
    file, names a key no field holds, or the cut program still departs
    from the sizes run."""
    for r in conf["reduced"]:
        key = r["key"]
        if conf.get(key) != r["here"]:
            raise ConfigMismatch(f"reduced {key}: the file runs "
                                 f"{conf.get(key)!r}, the entry says "
                                 f"{r['here']!r}")
        if key not in family.FIELDS:
            raise ConfigMismatch(f"reduced {key}: no field of the program's "
                                 f"config holds it")
        arch_cfg = replace_field(arch_cfg, family.FIELDS[key], r["here"])
    spec = family.spec_of(conf)
    bad = family.program_mismatches(spec, arch_cfg)
    if bad:
        raise ConfigMismatch(f"program config departs from {conf['name']}: "
                             f"{bad}")
    return spec, arch_cfg
