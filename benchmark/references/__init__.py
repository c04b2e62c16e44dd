"""Plain references, one module a kind of job: the data made from the seed and
the comparison that decides ``correct``, independent of the code under test."""
