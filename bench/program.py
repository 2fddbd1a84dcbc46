"""The system under test, as the configuration file describes it.

The structure module (bench/structures/) maps the file onto the
program's own registered architecture (`program_arch` in the file), so a
cell runs exactly the sizes and scales its file names, and refuses a
program structure its reference does not implement.
"""
from __future__ import annotations

import os
import sys

import structures
from harness import ROOT

sys.path.insert(0, os.path.join(ROOT, "src"))


def program_arch(cfg: dict):
    """The program's ArchConfig for this configuration file."""
    return structures.of(cfg).program_arch(cfg)
