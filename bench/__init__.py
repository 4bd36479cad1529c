"""The benchmark: harness, generator, references and readers (see run.py)."""
