"""Generic drivers: each runs every traffic mix that names it."""
