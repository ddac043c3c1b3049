"""Small widths and sizes for running the harness on the CPU in tests.

``shrink(cell)`` keeps a cell's files and changes only sizes, so every
driver, reference and metric file runs as it would on the chip.  The sizes
belong to the files they shrink: a configuration's reference module and a
mix's driver each define ``SMALL``."""
from __future__ import annotations

import dataclasses

import harness


def small_config(conf: dict) -> dict:
    """``conf`` at its reference's ``SMALL`` widths.  ``SMALL["program"]``
    is merged field by field into the file's ``"program"``, so no width the
    file sets there reaches a CPU test; ``SMALL["groups"]`` replaces the
    layer layout.  A configuration with no reference stays as it is."""
    if "reference" not in conf:
        return conf
    small = harness.load_module("reference", conf["reference"]).SMALL
    out = dict(conf, **small)
    if "program" in conf or "program" in small:
        out["program"] = dict(conf.get("program", {}), **small.get("program", {}))
    return out


def shrink(cell):
    traffic = dict(cell.traffic, **harness.load_module("drivers", cell.traffic["driver"]).SMALL)
    return dataclasses.replace(cell, config=small_config(cell.config), traffic=traffic)


def use(harness, monkeypatch) -> None:
    """Make ``harness.cell`` return shrunk cells for the rest of a test."""
    real = harness.cell
    monkeypatch.setattr(harness, "cell", lambda name: shrink(real(name)))
