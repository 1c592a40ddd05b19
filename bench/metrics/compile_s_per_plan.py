"""Seconds per plan that JAX spent tracing, lowering and compiling
(backend compiles include persistent-cache reads), as the union of its
own monitoring spans over the traced plans."""


def read(record):
    if not record["plans"]:
        return None
    return record["compile"]["union_s"] / record["plans"]
