"""The benchmark's own yardstick: nothing here imports the program except
``lib/program.py``, which builds the system under test."""
