"""The on-chip benchmark of the semi-external SpMM serving stack (see
``run.py``).  A package, so its modules import as ``bench.<name>``."""
