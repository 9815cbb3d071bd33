"""Graph generators of the benchmark, kept here so the yardstick cannot
move with the program under test."""
from .chung_lu import chung_lu_edges
from .kronecker import kronecker_edges, labelling

GENERATORS = {"kronecker": kronecker_edges, "chung_lu": chung_lu_edges}

__all__ = ["GENERATORS", "chung_lu_edges", "kronecker_edges", "labelling"]
