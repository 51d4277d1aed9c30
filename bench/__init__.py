"""The benchmark: harness, drivers, references and per-layer readers."""
