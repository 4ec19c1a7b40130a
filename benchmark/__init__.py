"""The benchmark of this repository: see benchmark/README.md."""
