"""Plain float32 forward passes, one module per architecture, written apart
from ``repro.models``: they import nothing of the program.  Each module
has ``layer(sizes, p, x, mm)`` for one decoder layer over (n, L, d) rows and
``head(sizes, params, x, mm)`` for the final norm and the output head.  A
module may also have ``step_flops(sizes, n_occ, pos)`` and
``step_bytes(sizes, n_occ, pos)``, the counts of one engine step of its
architecture (``chipbench/counts.py``).

A configuration file names its module by the key ``reference``; without
it, by its ``family``."""
import importlib


def for_config(sizes):
    """The reference module of configuration file ``sizes``."""
    return importlib.import_module(f"{__name__}.{sizes.get('reference', sizes['family'])}")
