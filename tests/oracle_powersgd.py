"""The NumPy oracle of the reference PowerSGD reduction: the benchmark's own
(``benchmark/reference/oracle_powersgd.py``), so the reducer tests and the
cells' reference check hold the reducer to one oracle."""

from benchmark.reference.oracle_powersgd import (  # noqa: F401
    matricize,
    orthogonalize_np,
    powersgd_reduce_np,
)
