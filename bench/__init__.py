"""The benchmark of Fed-Sophia federated training on the chip: the
harness (``run.py``), the plain reference of the output check, the
trace reduction and the work counts, and the cells' data files."""
