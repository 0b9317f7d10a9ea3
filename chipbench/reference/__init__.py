"""Plain float32 forward passes, one module per model family, written apart
from ``repro.models``: they import nothing of the program.  Each module
has ``layer(sizes, p, x, mm)`` for one decoder layer over (n, L, d) rows and
``head(sizes, params, x, mm)`` for the final norm and the output head."""
