"""Sycamore-style random circuits on a register sharded over a mesh.

The circuit, its gate matrices and the seeded initial state are those of
``families/rcs.py``; only the plain reference differs
(``reference/rcs_mesh.py`` keeps the state sharded as the program does).
"""

from .rcs import build_program, initial_planes, layers  # noqa: F401
