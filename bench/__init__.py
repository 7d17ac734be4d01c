"""The chip benchmark of the Honeycomb store (see run.py)."""
