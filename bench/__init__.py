"""A benchmark of the retrieval system on the chip: see run.py."""
