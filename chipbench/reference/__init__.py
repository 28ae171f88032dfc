"""Plain float32 references for the cells' correctness check.

Nothing here imports the code under test (``src/repro``): the hash
family, the sketch estimators and the optimizer are written out again
from their published definitions."""
