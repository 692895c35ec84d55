"""On-chip benchmark of the node-aware SpMV operator (see run.py)."""
